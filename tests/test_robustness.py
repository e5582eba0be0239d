"""Fault-tolerant serving (ISSUE 10): oversubscribed admission with
preempt-and-recompute, request lifecycle states, the NaN logit guard, and
the deterministic chaos harness.

Allocator level: idempotent free/rollback, informative exhaustion errors,
oversubscription admission math, seize/restore, invariant sweeps.

Engine level: victim selection policy, preempt-and-recompute token identity
vs the conservative engine, shared-prefix donors surviving preemption,
the NaN guard retiring exactly one slot while other rows commit
bitwise-unchanged, cancel/deadline/reject terminal paths all freeing
pages, and a seeded churn property (random cancels + deadlines + pool
pressure across dense / paged / int8) asserting the pool AND scale tables
drain to zero with every ok stream equal to the fault-free oracle.
"""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import jax

from repro.configs import get_config
from repro.models import transformer as tfm
from repro.serve.config import ServeConfig
from repro.serve.engine import ServeEngine, select_victim
from repro.serve.kv_pool import PageAllocator, PagedLayout, PoolExhausted
from repro.testing.chaos import ChaosConfig, ChaosInjector

CAP = 64
NEW = 8


def _alloc(num_pages=8, page_size=4, max_pages=8, n=1, **kw):
    return PageAllocator(PagedLayout(num_pages, page_size, max_pages, n), **kw)


# --------------------------------------------------------------------------
# allocator: idempotent free / rollback, informative errors, admission math
# --------------------------------------------------------------------------


def test_free_slot_idempotent():
    a = _alloc()
    a.alloc_slot(0, np.arange(6, dtype=np.int32), 4)
    assert a.pages_in_use == 2
    assert len(a.free_slot(0)) == 2  # both refs hit zero
    assert a.pages_in_use == 0
    # double free: no-op + counter, refcounts untouched
    assert a.free_slot(0) == []
    assert a.free_slot(0) == []
    assert a.double_free_noops == 2
    assert a.pages_in_use == 0 and (a.ref == 0).all()
    assert a.check_invariants() == []


def test_rollback_idempotent():
    a = _alloc()
    a.alloc_slot(0, np.arange(4, dtype=np.int32), 8)
    a.ensure_append(0, 4)
    assert a.slot_pages(0) == 2
    assert a.rollback(0, 4) == 1  # drop the speculative page
    noops = a.double_free_noops
    a.free_slot(0)
    assert a.rollback(0, 4) == 0  # rolled-back slot: idempotent no-op
    assert a.double_free_noops == noops + 1
    assert a.check_invariants() == []


def test_pool_exhausted_message_reports_occupancy():
    a = _alloc(num_pages=2, oversubscribe=2.0)
    a.alloc_slot(0, np.arange(8, dtype=np.int32), 0)  # 2 pages: pool full
    with pytest.raises(PoolExhausted) as ei:
        a.alloc_slot(1, np.arange(100, 104, dtype=np.int32), 0)
    msg = str(ei.value)
    for needle in ("2/2", "2 reserved", "virtual capacity of 4",
                   "oversubscribe=2.0", "free list empty"):
        assert needle in msg, (needle, msg)


def test_alloc_slot_unwinds_atomically_on_mid_prompt_exhaustion():
    a = _alloc(num_pages=3, oversubscribe=4.0)
    a.alloc_slot(0, np.arange(8, dtype=np.int32), 0)  # 2 of 3 pages
    with pytest.raises(PoolExhausted):
        a.alloc_slot(1, np.arange(200, 212, dtype=np.int32), 0)  # needs 3
    # the partial page grabbed before exhaustion was handed back
    assert a.slot_pages(1) == 0 and a.pages_in_use == 2
    assert (a.block_table[1] == PageAllocator.FREE).all()
    assert a.check_invariants() == []


def test_oversubscribe_admission_math():
    # conservative: lifetime pages must fit the physical pool
    a = _alloc(num_pages=4)
    assert a.can_admit(8, 8)  # 4 pages
    assert not a.can_admit(8, 12)  # 5 pages > 4
    # oversubscribed: lifetime books against virtual capacity, only prompt
    # pages + margin must fit physically
    b = _alloc(num_pages=4, oversubscribe=2.0)
    assert b.virtual_pages == 8
    assert b.can_admit(8, 12)  # 5 <= 8 virtual; 2 prompt + 1 margin <= 4
    assert not b.can_admit(8, 28)  # 9 lifetime > 8 virtual
    assert not b.can_admit(16, 0)  # 4 prompt + 1 margin > 4 physical
    b.alloc_slot(0, np.arange(8, dtype=np.int32), 12)
    assert b.pages_reserved == 5
    assert not b.can_admit(8, 12)  # 5 + 5 > 8 virtual
    # rejection: could never fit even an empty pool
    assert b.never_admittable(8, 60)  # 17 lifetime > 8 virtual
    assert b.never_admittable(20, 0)  # 5 prompt pages > 4 physical
    assert not b.never_admittable(8, 12)


def test_seize_restore_and_invariants():
    a = _alloc(num_pages=6)
    a.alloc_slot(0, np.arange(8, dtype=np.int32), 0)
    taken = a.seize_pages(3)
    assert len(taken) == 3 and a.stats()["seized_pages"] == 3
    assert a.check_invariants() == []  # conservation holds mid-squeeze
    with pytest.raises(PoolExhausted):
        a.alloc_slot(1, np.arange(300, 308, dtype=np.int32), 0)  # 1 free < 2
    a.restore_pages(taken)
    a.alloc_slot(1, np.arange(300, 308, dtype=np.int32), 0)
    a.free_slot(0), a.free_slot(1)
    assert a.pages_in_use == 0 and a.check_invariants() == []


def test_invariant_sweep_catches_corruption():
    a = _alloc()
    a.alloc_slot(0, np.arange(6, dtype=np.int32), 2)
    a.ref[int(a.block_table[0, 0])] += 1  # simulate a refcount leak
    assert any("ref" in p for p in a.check_invariants())


# --------------------------------------------------------------------------
# victim selection policy
# --------------------------------------------------------------------------


class _FakeReq:
    def __init__(self, rid, admit_tick):
        self.rid, self.admit_tick = rid, admit_tick


def test_select_victim_prefers_young_non_donors():
    a = _alloc(num_pages=16, max_pages=8)
    prefix = np.arange(8, dtype=np.int32)
    a.alloc_slot(0, prefix, 4)  # donor: slot 1 shares its pages
    a.alloc_slot(1, prefix, 4)
    a.alloc_slot(2, np.arange(100, 108, dtype=np.int32), 4)  # private
    slots = [_FakeReq(0, 0), _FakeReq(1, 5), _FakeReq(2, 3)]
    # youngest non-sharing slot loses first... but 0 and 1 SHARE pages, so
    # private slot 2 is preferred despite being older than slot 1
    assert select_victim(slots, a) == 2
    # among sharers only: youngest admit_tick first
    a.free_slot(2)
    slots[2] = None
    assert select_victim(slots, a) == 1
    # protection wins over policy
    assert select_victim(slots, a, protect={1}) == 0
    # nothing evictable
    assert select_victim(slots, a, protect={0, 1}) is None


def test_select_victim_skips_pageless_slots():
    a = _alloc()
    slots = [_FakeReq(0, 0), None]
    assert select_victim(slots, a) is None  # active but holds no pages yet


# --------------------------------------------------------------------------
# engine: preemption, NaN guard, lifecycle (shared module fixture)
# --------------------------------------------------------------------------


@pytest.fixture(scope="module")
def granite():
    cfg = get_config("granite-8b").reduced()
    params = tfm.init_params(cfg, jax.random.PRNGKey(1))
    return cfg, params


def _mk(cfg, params, chaos=None, **kw):
    return ServeEngine(cfg, params, serve=ServeConfig(
        max_seq=CAP, num_slots=3, **kw), chaos=chaos)


def _run(eng, prompts, new_tokens=NEW, deadlines=None, cancels=None):
    rids = [
        eng.submit(p, new_tokens,
                   deadline_ticks=None if deadlines is None else deadlines[i])
        for i, p in enumerate(prompts)
    ]
    cancels = cancels or {}
    while eng.has_work:
        for idx in cancels.get(eng._tick, []):
            eng.cancel(rids[idx])
        eng.step()
    return [eng._finished[r] for r in rids]


_PRESSURE = dict(paged=True, page_size=4, num_pages=13, prefill_chunk=8,
                 oversubscribe=2.0)


def test_preempt_recompute_token_identity(granite):
    cfg, params = granite
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, (16,), dtype=np.int32)
               for _ in range(3)]
    ref = _run(_mk(cfg, params, paged=True, page_size=4, num_pages=24,
                   prefill_chunk=8), prompts, 12)
    eng = _mk(cfg, params, health_every=1, **_PRESSURE)
    got = _run(eng, prompts, 12)
    assert eng.preemptions > 0, "13-page pool drove no preemption"
    for r, g in zip(ref, got):
        assert g.status == "ok"
        assert g.generated == r.generated
        assert (g.preemptions > 0) == (g.recompute_tokens > 0)
    assert eng.allocator.pages_in_use == 0
    assert sum(g.preemptions for g in got) == eng.preemptions


def test_shared_prefix_donor_preemption_safe(granite):
    """Preempting a prefix DONOR must not strip the sharer's committed
    pages: refcounts keep them resident, and both streams stay identical
    to the pressure-free run."""
    cfg, params = granite
    rng = np.random.default_rng(5)
    prefix = rng.integers(0, cfg.vocab_size, (12,), dtype=np.int32)
    prompts = [
        np.concatenate([prefix, rng.integers(0, cfg.vocab_size, (4,), dtype=np.int32)]),
        np.concatenate([prefix, rng.integers(0, cfg.vocab_size, (4,), dtype=np.int32)]),
        rng.integers(0, cfg.vocab_size, (16,), dtype=np.int32),
    ]
    ref = _run(_mk(cfg, params, paged=True, page_size=4, num_pages=24,
                   prefill_chunk=8), prompts, 12)
    eng = _mk(cfg, params, health_every=1, **_PRESSURE)
    got = _run(eng, prompts, 12)
    assert eng.allocator.stats()["shared_hits"] >= 1
    for r, g in zip(ref, got):
        assert g.status == "ok" and g.generated == r.generated
    assert eng.allocator.pages_in_use == 0


def test_nan_guard_isolates_one_slot(granite):
    """Poisoning one decoding slot's cache retires only that request
    (status numeric_error); every other slot's stream is bitwise-unchanged
    (batch rows are independent)."""
    cfg, params = granite
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, (8,), dtype=np.int32)
               for _ in range(3)]
    clean = _run(_mk(cfg, params, paged=True, page_size=4, prefill_chunk=8),
                 prompts)
    eng = _mk(cfg, params, paged=True, page_size=4, prefill_chunk=8)
    rids = [eng.submit(p, NEW) for p in prompts]
    poisoned = False
    while eng.has_work:
        if not poisoned and eng.scheduler.slots[1] is not None \
                and eng.scheduler.slots[1].generated:
            eng.poison_slot_cache(1)
            poisoned = True
        eng.step()
    got = [eng._finished[r] for r in rids]
    statuses = [g.status for g in got]
    assert statuses.count("numeric_error") == 1, statuses
    assert eng.numeric_errors == 1
    for c, g in zip(clean, got):
        if g.status == "ok":
            assert g.generated == c.generated
    assert eng.allocator.pages_in_use == 0
    eng.health()


def test_nan_guard_dense(granite):
    cfg, params = granite
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, cfg.vocab_size, (8,), dtype=np.int32)
               for _ in range(2)]
    eng = _mk(cfg, params)
    rids = [eng.submit(p, NEW) for p in prompts]
    eng.step()  # prefill + first decode
    eng.poison_slot_cache(0)
    while eng.has_work:
        eng.step()
    got = [eng._finished[r] for r in rids]
    assert got[0].status == "numeric_error"
    assert got[1].status == "ok" and len(got[1].generated) == NEW


def test_cancel_deadline_reject_free_everything(granite):
    cfg, params = granite
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab_size, (8,), dtype=np.int32)
               for _ in range(4)]
    prompts.append(rng.integers(0, cfg.vocab_size, (40,), dtype=np.int32))
    eng = _mk(cfg, params, paged=True, page_size=4, num_pages=8,
              prefill_chunk=8, oversubscribe=2.0)
    # rid 4's 40-token prompt (10 pages) can NEVER fit 8 physical pages
    got = _run(eng, prompts, 6,
               deadlines=[None, None, 2, None, None],
               cancels={1: [1]})
    statuses = [g.status for g in got]
    assert statuses[1] == "cancelled"
    assert statuses[4] == "rejected" and got[4].generated == []
    assert "deadline" in statuses
    assert eng.cancelled == 1 and eng.rejected_requests == 1
    assert eng.deadline_expired >= 1
    assert eng.allocator.pages_in_use == 0 and eng.allocator.pages_reserved == 0
    eng.health()


def test_cancel_unknown_rid_returns_none(granite):
    cfg, params = granite
    eng = _mk(cfg, params)
    assert eng.cancel(12345) is None


def test_chaos_trace_is_deterministic(granite):
    cfg, params = granite
    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, cfg.vocab_size, (16,), dtype=np.int32)
               for _ in range(4)]
    cc = ChaosConfig(seed=6, ticks=16, squeezes=2, squeeze_frac=0.5,
                     squeeze_hold=3, nan_ticks=1, drop_ticks=1)
    outs = []
    for _ in range(2):
        inj = ChaosInjector(cc)
        eng = _mk(cfg, params, chaos=inj, health_every=2, **_PRESSURE)
        got = _run(eng, prompts, 10)
        assert eng.allocator.pages_in_use == 0
        outs.append((inj.events, [(g.status, g.generated) for g in got]))
    assert outs[0] == outs[1]
    assert outs[0][0], "seeded trace injected nothing"


# --------------------------------------------------------------------------
# churn property: random cancels/deadlines under pressure, all modes
# --------------------------------------------------------------------------

_MODES = {
    "dense": dict(prefill_chunk=8),
    "paged": dict(paged=True, page_size=4, num_pages=13, prefill_chunk=8,
                  oversubscribe=2.0),
    "int8": dict(paged=True, page_size=4, num_pages=13, prefill_chunk=8,
                 oversubscribe=2.0, kv_dtype="int8"),
}
_ENGINES = {}  # (mode) -> reused engine: jit traces warm across examples
_ORACLES = {}  # (mode, prompt bytes) -> fault-free stream


def _churn_engine(granite, mode):
    if mode not in _ENGINES:
        cfg, params = granite
        _ENGINES[mode] = _mk(cfg, params, health_every=4, **_MODES[mode])
    return _ENGINES[mode]


def _oracle_stream(granite, mode, prompt):
    key = (mode, prompt.tobytes())
    if key not in _ORACLES:
        cfg, params = granite
        okey = "oracle-" + mode
        if okey not in _ENGINES:
            kw = dict(_MODES[mode], oversubscribe=1.0)  # roomy, fault-free
            if kw.get("paged"):
                kw["num_pages"] = 32
            kw.pop("oversubscribe")
            _ENGINES[okey] = _mk(cfg, params, **kw)
        res = _run(_ENGINES[okey], [prompt], NEW)
        _ORACLES[key] = res[0].generated
    return _ORACLES[key]


@pytest.mark.parametrize("mode", sorted(_MODES))
@settings(max_examples=4, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10**6))
def test_churn_drains_and_ok_streams_match_oracle(granite, mode, seed):
    cfg, params = granite
    rng = np.random.default_rng(seed)
    n_req = int(rng.integers(2, 5))
    prompts = [
        rng.integers(0, cfg.vocab_size, (int(rng.integers(6, 20)),),
                     dtype=np.int32)
        for _ in range(n_req)
    ]
    deadlines = [
        int(rng.integers(4, 14)) if rng.random() < 0.3 else None
        for _ in range(n_req)
    ]
    cancels = {}
    for i in range(n_req):
        if rng.random() < 0.3:
            cancels.setdefault(int(rng.integers(1, 10)), []).append(i)
    eng = _churn_engine(granite, mode)
    base = eng._tick
    rids = [
        eng.submit(p, NEW, arrival_tick=base, deadline_ticks=deadlines[i])
        for i, p in enumerate(prompts)
    ]
    while eng.has_work:
        for idx in cancels.get(eng._tick - base, []):
            eng.cancel(rids[idx])
        eng.step()
    got = [eng._finished[r] for r in rids]
    # terminal states are the documented set; every path freed its pages
    assert {g.status for g in got} <= {
        "ok", "cancelled", "deadline", "numeric_error", "rejected"
    }
    if eng.allocator is not None:
        assert eng.allocator.pages_in_use == 0
        assert eng.allocator.pages_reserved == 0
        assert eng.allocator.scale_entries_in_use == 0
    eng.health()
    for g, p in zip(got, prompts):
        if g.status == "ok":
            assert g.generated == _oracle_stream(granite, mode, p), (mode, seed)


# --------------------------------------------------------------------------
# launcher: the pressure gate must fail a trace that never preempts
# --------------------------------------------------------------------------


@pytest.mark.parametrize("num_pages,rc", [(7, 0), (12, 1)])
def test_launcher_check_preempts_gate(num_pages, rc, monkeypatch):
    """Each request needs 3 pages of 16 tokens: three slots outgrow 7 pages
    (preempts, exit 0) and fit in 12 (no preemption, exit 1)."""
    import sys

    from repro.launch import compile_cache
    from repro.launch import serve as launch_serve

    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(sys, "argv", [
        "serve", "--reduced", "--stream", "--trace", "32:0,32:0,32:1,32:2",
        "--slots", "3", "--paged", "--page-size", "16",
        "--num-pages", str(num_pages), "--prefill-chunk", "16",
        "--oversubscribe", "2.0", "--check-preempts",
    ])
    assert launch_serve.main() == rc
