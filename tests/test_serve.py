"""Continuous-batching serve stack: scheduler logic, slot-pool engine, and
the vectorized-position decode path (single device; the multi-device trace
replay goes through dist_check in tests/test_distributed.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import transformer as tfm
from repro.parallel.context import ParallelCtx
from repro.serve.engine import ServeEngine
from repro.serve.scheduler import Scheduler, default_buckets

CTX = ParallelCtx()


# --------------------------------------------------------------------------
# scheduler (pure python)
# --------------------------------------------------------------------------


def test_default_buckets_ladder():
    assert default_buckets(128, 1) == (16, 32, 64, 128)
    assert default_buckets(128, 8) == (16, 32, 64, 128)
    # every bucket a multiple of n; cap always present
    bs = default_buckets(96, 8)
    assert all(b % 8 == 0 for b in bs) and bs[-1] == 96


def test_scheduler_admission_fifo_and_retire():
    s = Scheduler(2, (16, 32), 64)
    r0 = s.submit(np.arange(8), 4, arrival_tick=0)
    r1 = s.submit(np.arange(16), 4, arrival_tick=0)
    r2 = s.submit(np.arange(8), 4, arrival_tick=1)
    # tick 0: two free slots, FIFO among arrived requests; r2 not arrived yet
    assigned = s.admit(0)
    assert [(sl, r.rid) for sl, r in assigned] == [(0, r0.rid), (1, r1.rid)]
    assert s.admit(0) == [] and s.pending == 1
    # r2 arrives but no slot is free until one retires
    assert s.admit(1) == []
    done = s.retire(0, tick=3)
    assert done.rid == r0.rid and done.finish_tick == 3
    assigned = s.admit(4)
    assert [(sl, r.rid) for sl, r in assigned] == [(0, r2.rid)]
    assert s.active_slots() == [0, 1] and not s.pending
    s.retire(1, tick=5)
    with pytest.raises(ValueError):
        s.retire(1, tick=5)  # already free


def test_scheduler_bucketing_and_validation():
    s = Scheduler(1, (16, 32), 48)
    assert s.bucket_for(1) == 16
    assert s.bucket_for(16) == 16
    assert s.bucket_for(17) == 32
    with pytest.raises(ValueError):
        s.submit(np.arange(40), 16)  # 40 + 16 > 48
    with pytest.raises(ValueError):
        s.submit(np.arange(8), 0)
    with pytest.raises(ValueError):
        s.bucket_for(33)  # no bucket can hold it
    exact = Scheduler(1, (16,), 64, exact=True)
    assert exact.bucket_for(13) == 13  # SSM archs: no pad-correction
    # exact mode cannot pad its way to sp divisibility (hybrid archs still
    # shard attention prefill) -> reject at admission, not deep inside jit
    exact_sp = Scheduler(1, (16,), 64, exact=True, multiple=4)
    assert exact_sp.bucket_for(16) == 16
    with pytest.raises(ValueError):
        exact_sp.bucket_for(17)
    # the SSD chunked scan: per-device length must be <= or a multiple of chunk
    exact_chunk = Scheduler(1, (16,), 64, exact=True, chunk=8)
    assert exact_chunk.bucket_for(6) == 6
    assert exact_chunk.bucket_for(16) == 16
    with pytest.raises(ValueError):
        exact_chunk.bucket_for(12)


def test_pack_groups_binpack_toward_bucket_boundaries():
    """The bin-packing planner sorts by length and packs toward bucket
    boundaries: a 9+8+16 burst lands as a boundary-snug 16+9 row plus a
    padding-free 8 (48 padded tokens) where greedy crams one 64-bucket row."""
    from repro.serve.scheduler import Request

    def reqs(lengths):
        return [
            (slot, Request(slot, np.zeros(ln, np.int32), 4))
            for slot, ln in enumerate(lengths)
        ]

    s = Scheduler(8, (16, 32, 64), 128)

    def cost(groups):
        return sum(s.bucket_for(sum(len(r.prompt) for _, r in g)) for g in groups)

    burst = reqs((9, 8, 16))
    packed = s.pack_groups(burst, pack_max=4, plan="binpack")
    greedy = s.pack_groups(burst, pack_max=4, plan="greedy")
    assert cost(greedy) == 64  # 33 real tokens crammed into one 64 row
    assert cost(packed) == 48, [
        [len(r.prompt) for _, r in g] for g in packed
    ]
    assert sorted(sum(len(r.prompt) for _, r in g) for g in packed) == [8, 25]
    # every admitted slot appears exactly once in the plan
    assert sorted(sl for g in packed for sl, _ in g) == [0, 1, 2]

    # dense bursts that fit one bucket row beat any split: binpack keeps the
    # greedy plan as a candidate, so it is NEVER costlier than greedy
    for lengths in ((9, 16, 8, 30), (17, 16), (31, 2, 31, 2), (16, 16, 16)):
        p = s.pack_groups(reqs(lengths), pack_max=4, plan="binpack")
        g = s.pack_groups(reqs(lengths), pack_max=4, plan="greedy")
        assert cost(p) <= cost(g), (lengths, cost(p), cost(g))
        assert sorted(sl for grp in p for sl, _ in grp) == list(range(len(lengths)))
    with pytest.raises(ValueError):
        s.pack_groups(burst, pack_max=4, plan="nope")


# --------------------------------------------------------------------------
# engine: slot pool, cache ownership, retrace bounds
# --------------------------------------------------------------------------


def _engine(**kw):
    cfg = get_config("granite-8b").reduced()
    params = tfm.init_params(cfg, jax.random.PRNGKey(1))
    kw.setdefault("max_seq", 64)
    kw.setdefault("num_slots", 2)
    return cfg, params, ServeEngine(cfg, params, **kw)


def test_cache_allocated_once_across_generates(monkeypatch):
    """The slot-pool cache is allocated in __init__ and reused: a second
    generate() call must not allocate (or trace) anything new."""
    calls = {"n": 0}
    orig = tfm.init_cache

    def counting(*a, **k):
        calls["n"] += 1
        return orig(*a, **k)

    monkeypatch.setattr(tfm, "init_cache", counting)
    cfg, params, eng = _engine()
    assert calls["n"] == 1  # the pool, eagerly, at construction
    prompts = (np.arange(16, dtype=np.int32).reshape(2, 8) * 5) % cfg.vocab_size
    out1 = eng.generate(prompts, max_new_tokens=4)
    after_first = calls["n"]  # +1 per bucket TRACE (inside jit), not per call
    out2 = eng.generate(prompts, max_new_tokens=4)
    assert calls["n"] == after_first, "second generate re-allocated the cache"
    np.testing.assert_array_equal(out1, out2)
    assert eng.decode_trace_count == 1


def test_retrace_bounded_by_buckets():
    """Retraces are a function of (bucket, pack-size) pairs, not the actual
    prompt-length mix: packed prefill keys are (bucket, k) and a fresh
    composition hitting the same keys must not trace anything new."""
    cfg, params, eng = _engine(num_slots=2, max_seq=64)
    rng = np.random.default_rng(0)
    lengths = [8, 16, 9, 30, 31, 12]
    for i, ln in enumerate(lengths):
        eng.submit(
            rng.integers(0, cfg.vocab_size, (ln,), dtype=np.int32),
            max_new_tokens=3,
            arrival_tick=i // 3,
        )
    eng.run()
    buckets = set(eng.scheduler.buckets)
    assert all(b in buckets and 1 <= k <= eng.pack_max
               for b, k in eng.prefill_trace_counts)
    assert all(v == 1 for v in eng.prefill_trace_counts.values())
    assert eng.decode_trace_count == 1
    keys_before = set(eng.prefill_trace_counts)
    # a fresh composition mapping to already-traced (bucket, k) keys: the
    # first batch's tick-0 pair packed into (32, 2), so 10+20 does too
    for ln in (10, 20):
        eng.submit(rng.integers(0, cfg.vocab_size, (ln,), dtype=np.int32), 3)
    eng.run()
    assert set(eng.prefill_trace_counts) == keys_before
    assert all(v == 1 for v in eng.prefill_trace_counts.values())
    assert eng.decode_trace_count == 1


def test_packed_prefill_matches_sequential():
    """Same-tick admissions pack into ONE prefill row under a document mask;
    every request's tokens must equal sequential single-request generation,
    and the un-packed engine must agree token-for-token too."""
    cfg, params, eng = _engine(num_slots=3, max_seq=128)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab_size, (ln,), dtype=np.int32) for ln in (16, 8, 8)]
    rids = [eng.submit(p, max_new_tokens=4, arrival_tick=0) for p in prompts]
    finished = eng.run()
    # the three same-tick prompts shared one packed (bucket=32, k=3) prefill
    assert eng.prefill_trace_counts == {(32, 3): 1}, eng.prefill_trace_counts
    seq_eng = ServeEngine(cfg, params, max_seq=128, num_slots=1)
    nopack = ServeEngine(cfg, params, max_seq=128, num_slots=3, pack_prefill=False)
    rids_np = [nopack.submit(p, max_new_tokens=4, arrival_tick=0) for p in prompts]
    fin_np = nopack.run()
    assert all(isinstance(key, int) for key in nopack.prefill_trace_counts)
    for rid, rid_np, p in zip(rids, rids_np, prompts):
        ref = seq_eng.generate(p[None, :], max_new_tokens=4)[0].tolist()
        assert finished[rid].generated == ref, (finished[rid].generated, ref)
        assert fin_np[rid_np].generated == ref


def test_continuous_matches_sequential():
    """A mixed-length arrival trace (slots at different depths per tick,
    padded prefill buckets) must reproduce sequential single-request
    generation token-for-token."""
    cfg, params, eng = _engine(num_slots=2, max_seq=64)
    rng = np.random.default_rng(7)
    trace = [(8, 0), (16, 0), (12, 2), (8, 3)]
    prompts = [rng.integers(0, cfg.vocab_size, (ln,), dtype=np.int32) for ln, _ in trace]
    rids = [
        eng.submit(p, max_new_tokens=5, arrival_tick=t)
        for p, (_, t) in zip(prompts, trace)
    ]
    finished = eng.run()
    seq_eng = ServeEngine(cfg, params, max_seq=64, num_slots=1)
    for rid, p in zip(rids, prompts):
        ref = seq_eng.generate(p[None, :], max_new_tokens=5)
        assert finished[rid].generated == ref[0].tolist(), rid


def test_continuous_matches_sequential_hybrid():
    """SSM/hybrid archs serve through the exact-prefill path (no padding:
    the recurrent state has no pad-correction) and must still reproduce
    sequential generation."""
    cfg = get_config("hymba-1.5b").reduced()
    params = tfm.init_params(cfg, jax.random.PRNGKey(4))
    eng = ServeEngine(cfg, params, max_seq=64, num_slots=2)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, (ln,), dtype=np.int32) for ln in (8, 16)]
    rids = [eng.submit(p, max_new_tokens=4, arrival_tick=t) for p, t in zip(prompts, (0, 1))]
    with pytest.raises(ValueError):  # 12 > chunk=8 and not a multiple of it
        eng.submit(rng.integers(0, cfg.vocab_size, (12,), dtype=np.int32), 4)
    finished = eng.run()
    seq_eng = ServeEngine(cfg, params, max_seq=64, num_slots=1)
    for rid, p in zip(rids, prompts):
        ref = seq_eng.generate(p[None, :], max_new_tokens=4)
        assert finished[rid].generated == ref[0].tolist(), rid


def test_eos_retirement_frees_slot():
    """A slot retiring on EOS is recycled for the queue; the finished
    request keeps the tokens up to (and including) the EOS."""
    cfg, params, _ = _engine()
    base = ServeEngine(cfg, params, max_seq=64, num_slots=1)
    prompt = (np.arange(8, dtype=np.int32) * 3) % cfg.vocab_size
    ref = base.generate(prompt[None, :], max_new_tokens=6)[0].tolist()
    eos = ref[2]  # force retirement at (no later than) the third token
    stop = ref.index(eos) + 1
    eng = ServeEngine(cfg, params, max_seq=64, num_slots=1, eos_id=eos)
    r0 = eng.submit(prompt, max_new_tokens=6)
    r1 = eng.submit(prompt[:4], max_new_tokens=2)
    finished = eng.run()
    assert finished[r0].generated == ref[:stop]  # stopped at EOS, inclusive
    assert 1 <= len(finished[r1].generated) <= 2  # queued request got the slot
    assert finished[r1].admit_tick >= finished[r0].finish_tick


# --------------------------------------------------------------------------
# vectorized-position decode: mixed depths == each request alone, bitwise
# --------------------------------------------------------------------------


def _prefill_one(cfg, params, prompt):
    S = len(prompt)
    cache = tfm.init_cache(cfg, 1, 32, dtype=jnp.float32)
    batch = {
        "tokens": jnp.asarray(prompt)[None, :],
        "positions": jnp.arange(S, dtype=jnp.int32),
    }
    logits, cache = tfm.prefill(params, cfg, CTX, batch, cache)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache


def test_mixed_depth_decode_bitwise():
    """decode_step over slots at different depths (pos: [B]) must produce
    BITWISE-identical logits to decoding each request in its own cache."""
    cfg = get_config("granite-8b").reduced()
    params = tfm.init_params(cfg, jax.random.PRNGKey(2))
    rng = np.random.default_rng(1)
    pa = rng.integers(0, cfg.vocab_size, (8,), dtype=np.int32)
    pb = rng.integers(0, cfg.vocab_size, (12,), dtype=np.int32)
    ta, cache_a = _prefill_one(cfg, params, pa)
    tb, cache_b = _prefill_one(cfg, params, pb)

    def merge(a, b):
        return jnp.concatenate([a, b], axis=1 if a.ndim > 1 else 0)

    cache = jax.tree.map(merge, cache_a, cache_b)
    np.testing.assert_array_equal(np.asarray(cache["pos"]), [8, 12])
    toks = jnp.concatenate([ta, tb], axis=0)
    step = jax.jit(lambda p, c, t: tfm.decode_step(p, c, t, cfg, CTX))
    for _ in range(3):
        toks, cache, logits = step(params, cache, toks)
        ta, cache_a, la = step(params, cache_a, ta)
        tb, cache_b, lb = step(params, cache_b, tb)
        np.testing.assert_array_equal(np.asarray(logits[0]), np.asarray(la[0]))
        np.testing.assert_array_equal(np.asarray(logits[1]), np.asarray(lb[0]))
        np.testing.assert_array_equal(np.asarray(toks), np.asarray(jnp.concatenate([ta, tb], 0)))


def test_padded_prefill_matches_exact():
    """Bucketed (right-padded) prefill: logits at the true last token and the
    subsequent decode are unaffected by pad tokens behind it."""
    cfg = get_config("granite-8b").reduced()
    params = tfm.init_params(cfg, jax.random.PRNGKey(3))
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, cfg.vocab_size, (11,), dtype=np.int32)
    t_exact, cache_exact = _prefill_one(cfg, params, prompt)

    bucket = 16
    toks = np.zeros((1, bucket), np.int32)
    toks[0, : len(prompt)] = prompt
    cache = tfm.init_cache(cfg, 1, 32, dtype=jnp.float32)
    batch = {
        "tokens": jnp.asarray(toks),
        "positions": jnp.arange(bucket, dtype=jnp.int32),
        "length": jnp.asarray([len(prompt)], jnp.int32),
    }
    logits, cache = tfm.prefill(params, cfg, CTX, batch, cache)
    t_pad = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    np.testing.assert_array_equal(np.asarray(t_pad), np.asarray(t_exact))
    assert int(cache["pos"][0]) == len(prompt)
    step = jax.jit(lambda p, c, t: tfm.decode_step(p, c, t, cfg, CTX))
    for _ in range(4):  # decode overwrites each pad entry before reading it
        t_pad, cache, lp = step(params, cache, t_pad)
        t_exact, cache_exact, le = step(params, cache_exact, t_exact)
        np.testing.assert_array_equal(np.asarray(lp), np.asarray(le))


# --------------------------------------------------------------------------
# paged KV cache: block-table engine == dense engine, prefix sharing
# --------------------------------------------------------------------------


def test_paged_engine_matches_dense():
    """The paged engine (page pool + block tables, serve/kv_pool.py) must
    reproduce the dense engine token-for-token on a mixed trace, with the
    same retrace bounds, and drain its pool on retirement."""
    cfg, params, dense = _engine(num_slots=2, max_seq=64)
    rng = np.random.default_rng(13)
    trace = [(8, 0), (16, 0), (12, 2), (8, 3)]
    prompts = [rng.integers(0, cfg.vocab_size, (ln,), dtype=np.int32) for ln, _ in trace]
    rids_d = [dense.submit(p, 5, arrival_tick=t) for p, (_, t) in zip(prompts, trace)]
    fin_d = dense.run()

    paged = ServeEngine(cfg, params, max_seq=64, num_slots=2, paged=True, page_size=4)
    rids_p = [paged.submit(p, 5, arrival_tick=t) for p, (_, t) in zip(prompts, trace)]
    fin_p = paged.run()
    for rd, rp in zip(rids_d, rids_p):
        assert fin_d[rd].generated == fin_p[rp].generated, (rd, rp)
    assert paged.decode_trace_count == 1
    assert set(paged.prefill_trace_counts) == set(dense.prefill_trace_counts)
    assert paged.allocator.pages_in_use == 0  # every retirement freed
    stats = paged.kv_cache_stats()
    assert stats["paged"] == 1 and stats["peak_page_bytes"] <= stats["cache_bytes"]


def test_paged_prefix_sharing_fewer_pages_same_tokens():
    """Two requests sharing a prompt prefix must allocate strictly fewer
    pages than two unrelated requests, produce identical tokens to unshared
    generation, and the owner retiring must not disturb the sharer."""
    cfg, params, _ = _engine()
    rng = np.random.default_rng(17)
    prefix = rng.integers(0, cfg.vocab_size, (8,), dtype=np.int32)
    pair = [
        np.concatenate([prefix, rng.integers(0, cfg.vocab_size, (ln,), dtype=np.int32)])
        for ln in (4, 6)
    ]

    def run_paged(prompts, budgets):
        eng = ServeEngine(cfg, params, max_seq=64, num_slots=2, paged=True, page_size=4)
        rids = [eng.submit(p, mt, arrival_tick=0) for p, mt in zip(prompts, budgets)]
        fin = eng.run()
        return [fin[r].generated for r in rids], eng.allocator.stats()

    # asymmetric budgets: the owner (slot 0) retires while the sharer is
    # still decoding through the shared pages
    toks, st = run_paged(pair, (2, 6))
    oracle = ServeEngine(cfg, params, max_seq=64, num_slots=1)
    refs = [oracle.generate(p[None], max_new_tokens=mt)[0].tolist()
            for p, mt in zip(pair, (2, 6))]
    assert toks == refs, (toks, refs)
    assert st["shared_hits"] == 2  # 8-token prefix = 2 chunks of 4
    unrelated = [rng.integers(0, cfg.vocab_size, (len(p),), dtype=np.int32) for p in pair]
    _, st_un = run_paged(unrelated, (2, 6))
    assert st["fresh_allocs"] < st_un["fresh_allocs"], (st, st_un)


def test_paged_admission_defers_on_small_pool():
    """A pool smaller than the slot count's worst case defers admission (the
    scheduler accounts pages, not rows) but still completes every request."""
    cfg, params, _ = _engine()
    rng = np.random.default_rng(19)
    # pool of 4 chunks x 8 tokens = 32 tokens; each request reserves
    # ceil((16+8)/8) = 3 pages, so two can never be resident together
    eng = ServeEngine(cfg, params, max_seq=64, num_slots=2, paged=True,
                      page_size=8, num_pages=4)
    prompts = [rng.integers(0, cfg.vocab_size, (16,), dtype=np.int32) for _ in range(2)]
    rids = [eng.submit(p, 8, arrival_tick=0) for p in prompts]
    fin = eng.run()
    a, b = fin[rids[0]], fin[rids[1]]
    assert len(a.generated) == len(b.generated) == 8
    assert b.admit_tick > a.admit_tick  # deferred until the pool freed
    oracle = ServeEngine(cfg, params, max_seq=64, num_slots=1)
    for rid, p in zip(rids, prompts):
        assert fin[rid].generated == oracle.generate(p[None], 8)[0].tolist()


# --------------------------------------------------------------------------
# donation: every jitted step updates the cache in place, over many ticks
# --------------------------------------------------------------------------

_DONATED_RUNS = {
    "dense": dict(),
    "paged": dict(paged=True, page_size=4),
    "chunked-prefill": dict(paged=True, page_size=4, prefill_chunk=8, tick_token_budget=12),
    "preempt-recompute": dict(paged=True, page_size=4, num_pages=13, prefill_chunk=8,
                              oversubscribe=2.0),
    "prefix-cow": dict(paged=True, page_size=4, prefill_chunk=8),
    "spec-verify": dict(paged=True, page_size=4, prefill_chunk=8, spec_k=4),
    "scrub-numeric": dict(paged=True, page_size=4, prefill_chunk=8),
}


@pytest.mark.parametrize("run", sorted(_DONATED_RUNS))
def test_donated_engine_logits_match_forward(run):
    """Multi-tick engine runs whose steps donate the cache: the first tick
    consumes the pool the engine was built with, no path reads a donated
    buffer afterwards, and every token served is the model's plain forward
    over the request's context (the same token; logits to fp reassociation,
    as in test_continuous_prefill.py).  Covers preemption with recompute,
    copy-on-write prefix sharing, chunked prefill, speculative verify, and
    the numeric-error scrub, which rewrites the pool through a donating jit
    too."""
    from repro.serve.config import ServeConfig

    cfg = get_config("granite-8b").reduced()
    params = tfm.init_params(cfg, jax.random.PRNGKey(1))
    rng = np.random.default_rng(23)
    if run == "prefix-cow":
        # two requests share an 8-token prefix (two whole pages)
        prefix = rng.integers(0, cfg.vocab_size, (8,), dtype=np.int32)
        prompts = [np.concatenate([prefix, rng.integers(0, cfg.vocab_size, (n,),
                                                        dtype=np.int32)]) for n in (4, 3)]
        prompts.append(rng.integers(0, cfg.vocab_size, (16,), dtype=np.int32))
    elif run == "spec-verify":
        # repeating prompts: the n-gram drafter finds drafts to verify
        loop = rng.integers(0, cfg.vocab_size, (4,), dtype=np.int32)
        prompts = [np.tile(loop, 4), np.tile(loop[::-1], 3), np.tile(loop, 2)]
    else:
        prompts = [rng.integers(0, cfg.vocab_size, (n,), dtype=np.int32)
                   for n in (16, 11, 16)]
    eng = ServeEngine(cfg, params, serve=ServeConfig(max_seq=64, num_slots=3,
                                                     **_DONATED_RUNS[run]))
    eng.capture_logits = True
    built = eng._cache["k"]
    rids = [eng.submit(p, 12) for p in prompts]
    hit = False
    while eng.has_work:
        if not hit and eng.scheduler.slots[1] is not None \
                and eng.scheduler.slots[1].generated:
            hit = True
            if run == "scrub-numeric":
                eng.poison_slot_cache(1)
            elif run == "prefix-cow":
                # the sharer takes a private copy of the first shared page,
                # as a write into it would (ensure_append's copy-on-write),
                # and decodes on through the copy
                cow = eng.allocator.ensure_append(1, 0)
                assert cow is not None
                eng._apply_copies([cow])
        eng.step()
    assert built.is_deleted()  # donated to the first step, never read again
    results = [eng._finished[r] for r in rids]
    stats = eng.kv_cache_stats()
    expect = {
        "preempt-recompute": stats.get("preemptions", 0) > 0,
        "prefix-cow": stats.get("shared_hits", 0) >= 1 and stats.get("cow_copies", 0) >= 1,
        "spec-verify": eng.spec_accepted > 0,
        "chunked-prefill": eng.chunk_launches > len(prompts),
        "scrub-numeric": [r.status for r in results].count("numeric_error") == 1,
    }
    assert expect.get(run, True), (run, stats)
    for key in ("k", "v"):  # the scrub left nothing non-finite in the pool
        assert np.isfinite(np.asarray(eng._cache[key])).all()

    @jax.jit
    def forward(tokens):
        S = tokens.shape[1]
        batch = {"tokens": tokens, "positions": jnp.arange(S, dtype=jnp.int32)}
        return tfm.forward(params, cfg, CTX, batch)[0][0]

    served = 0
    for rid, prompt, res in zip(rids, prompts, results):
        if res.status != "ok":
            continue
        seq = np.concatenate([prompt, np.asarray(res.generated, np.int32)])
        want = np.asarray(forward(jnp.asarray(seq[None, :-1])))[len(prompt) - 1:]
        got = np.stack(eng.debug_logits[rid])
        assert res.generated == np.argmax(want, axis=-1).tolist(), (run, rid)
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5, err_msg=f"{run} {rid}")
        served += 1
    assert served >= 2
