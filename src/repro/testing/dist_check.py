"""Multi-device correctness battery, runnable as a subprocess.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m repro.testing.dist_check [check ...]

The main pytest process must stay at 1 CPU device (per the dry-run rules), so
tests/test_distributed.py launches this module in a child process with fake
devices and asserts on its JSON report.  Every check compares a distributed
computation against the single-device oracle on the gathered arrays.
"""

from __future__ import annotations

import json
import sys
import traceback

import numpy as np

from repro.compat import make_mesh


def _setup():
    import jax

    return jax


def _mk(key, *shape):
    import jax

    return jax.random.normal(key, shape, dtype=jnp_f32())


def jnp_f32():
    import jax.numpy as jnp

    return jnp.float32


# --------------------------------------------------------------------------


def check_mesh_attention_forward():
    """Mesh-Attention fwd == single-device ref for every (a,b), mask, GQA."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map

    from repro.core.mesh_attention import MeshAttentionConfig, mesh_attention
    from repro.core.tiling import factorizations, stripe_permutation, unstripe_permutation
    from repro.kernels import ref

    n = 8
    mesh = make_mesh((n,), ("sp",))
    B, S, H, Hkv, D = 2, n * 16, 4, 2, 16
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, H, D))
    k = jax.random.normal(kk, (B, S, Hkv, D))
    v = jax.random.normal(kv, (B, S, Hkv, D))

    results = {}
    for a, b in factorizations(n):
        for causal, window in [(False, None), (True, None), (True, 40)]:
            cfg = MeshAttentionConfig(
                axis_name="sp", n=n, a=a, causal=causal, window=window,
                block_q=16, block_kv=16,
            )
            f = shard_map(
                lambda q, k, v, cfg=cfg: mesh_attention(q, k, v, cfg),
                mesh=mesh,
                in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
                out_specs=P(None, "sp"),
            )
            if causal:
                perm = stripe_permutation(S, n)
                inv = unstripe_permutation(S, n)
                o = jax.jit(f)(q[:, perm], k[:, perm], v[:, perm])[:, inv]
                band = ref.causal_band()
                if window:
                    band = (0, 0, 0, window - 1)
            else:
                o = jax.jit(f)(q, k, v)
                band = None
            o_ref, _ = ref.attention_ref(q, k, v, band=band)
            err = float(jnp.max(jnp.abs(o - o_ref)))
            results[f"a{a}b{b}_causal{causal}_w{window}"] = err
            assert err < 2e-5, (a, b, causal, window, err)
    return results


def check_mesh_attention_backward():
    """custom_vjp (Alg. 3 ring program) == autodiff through the dense oracle."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    from repro.core.mesh_attention import MeshAttentionConfig, mesh_attention
    from repro.core.tiling import factorizations, stripe_permutation, unstripe_permutation
    from repro.kernels import ref

    n = 8
    mesh = make_mesh((n,), ("sp",))
    B, S, H, Hkv, D = 1, n * 8, 4, 2, 8
    key = jax.random.PRNGKey(1)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, H, D))
    k = jax.random.normal(kk, (B, S, Hkv, D))
    v = jax.random.normal(kv, (B, S, Hkv, D))
    perm = stripe_permutation(S, n)
    inv = unstripe_permutation(S, n)

    results = {}
    for a, b in factorizations(n):
        for causal in (False, True):
            for wire in ("qdod", "odoq"):
                cfg = MeshAttentionConfig(
                    axis_name="sp", n=n, a=a, causal=causal,
                    block_q=8, block_kv=8, bwd_wire=wire,
                )
                f = shard_map(
                    lambda q, k, v, cfg=cfg: mesh_attention(q, k, v, cfg),
                    mesh=mesh,
                    in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
                    out_specs=P(None, "sp"),
                )

                def loss_dist(q, k, v):
                    if causal:
                        o = f(q[:, perm], k[:, perm], v[:, perm])[:, inv]
                    else:
                        o = f(q, k, v)
                    return jnp.sum(jnp.sin(o))

                def loss_ref(q, k, v):
                    H = q.shape[2]
                    kr, vr = ref.repeat_kv(k, H), ref.repeat_kv(v, H)
                    s = jnp.einsum("bqhd,bkhd->bhqk", q, kr) * (D**-0.5)
                    if causal:
                        mask = jnp.tril(jnp.ones((S, S), bool))
                        s = jnp.where(mask[None, None], s, -1e30)
                    p = jax.nn.softmax(s, axis=-1)
                    o = jnp.einsum("bhqk,bkhd->bqhd", p, vr)
                    return jnp.sum(jnp.sin(o))

                g1 = jax.jit(jax.grad(loss_dist, argnums=(0, 1, 2)))(q, k, v)
                g2 = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
                errs = [float(jnp.max(jnp.abs(x - y))) for x, y in zip(g1, g2)]
                results[f"a{a}_causal{causal}_{wire}"] = max(errs)
                assert max(errs) < 5e-5, (a, causal, wire, errs)
    return results


def check_mesh_attention_pallas_interpret():
    """One full fwd+bwd config with the Pallas kernels (interpret=True) inside
    the ring program — validates the kernel/ring integration end to end."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    from repro.core.mesh_attention import MeshAttentionConfig, mesh_attention
    from repro.core.tiling import stripe_permutation, unstripe_permutation
    from repro.kernels import ops, ref

    ops.set_backend("pallas")
    try:
        n, a = 4, 2
        mesh = make_mesh((n,), ("sp",))
        B, S, H, Hkv, D = 1, n * 16, 2, 1, 8
        key = jax.random.PRNGKey(2)
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (B, S, H, D))
        k = jax.random.normal(kk, (B, S, Hkv, D))
        v = jax.random.normal(kv, (B, S, Hkv, D))
        perm = stripe_permutation(S, n)
        inv = unstripe_permutation(S, n)
        cfg = MeshAttentionConfig(
            axis_name="sp", n=n, a=a, causal=True, block_q=8, block_kv=8
        )
        # check_vma=False: the pallas hlo interpreter mixes varying and
        # uniform values inside its grid loop, tripping the vma checker
        # (jax-ml/jax interpreter limitation; compiled TPU path is fine).
        f = shard_map(
            lambda q, k, v: mesh_attention(q, k, v, cfg),
            mesh=mesh,
            in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
            out_specs=P(None, "sp"),
            check_vma=False,
        )

        def loss(q, k, v):
            return jnp.sum(jnp.sin(f(q[:, perm], k[:, perm], v[:, perm])[:, inv]))

        o = jax.jit(f)(q[:, perm], k[:, perm], v[:, perm])[:, inv]
        o_ref, _ = ref.attention_ref(q, k, v, band=ref.causal_band())
        err_o = float(jnp.max(jnp.abs(o - o_ref)))
        assert err_o < 2e-5, err_o

        g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

        def loss_ref(q, k, v):
            kr, vr = ref.repeat_kv(k, H), ref.repeat_kv(v, H)
            s = jnp.einsum("bqhd,bkhd->bhqk", q, kr) * (D**-0.5)
            mask = jnp.tril(jnp.ones((S, S), bool))
            s = jnp.where(mask[None, None], s, -1e30)
            o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), vr)
            return jnp.sum(jnp.sin(o))

        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        err_g = max(float(jnp.max(jnp.abs(x - y))) for x, y in zip(g, gr))
        assert err_g < 5e-5, err_g
        return {"fwd_err": err_o, "bwd_err": err_g}
    finally:
        ops.set_backend("auto")


def check_ring_equals_mesh_a1():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    from repro.core.mesh_attention import MeshAttentionConfig, mesh_attention
    from repro.core.ring_attention import ring_config

    n = 8
    mesh = make_mesh((n,), ("sp",))
    B, S, H, D = 1, n * 8, 2, 8
    key = jax.random.PRNGKey(3)
    q, k, v = (jax.random.normal(kk, (B, S, H, D)) for kk in jax.random.split(key, 3))

    def run(cfg):
        f = shard_map(
            lambda q, k, v: mesh_attention(q, k, v, cfg),
            mesh=mesh,
            in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
            out_specs=P(None, "sp"),
        )
        return jax.jit(f)(q, k, v)

    o_ring = run(ring_config("sp", n, block_q=8, block_kv=8))
    o_mesh = run(MeshAttentionConfig(axis_name="sp", n=n, a=1, block_q=8, block_kv=8))
    err = float(jnp.max(jnp.abs(o_ring - o_mesh)))
    assert err < 1e-6, err
    return {"err": err}


def check_ulysses():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    from repro.core.ulysses import ulysses_attention
    from repro.kernels import ref

    n = 2  # capped by Hkv=2
    mesh = make_mesh((n,), ("sp",))
    B, S, H, Hkv, D = 2, n * 16, 4, 2, 16
    key = jax.random.PRNGKey(4)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, H, D))
    k = jax.random.normal(kk, (B, S, Hkv, D))
    v = jax.random.normal(kv, (B, S, Hkv, D))
    results = {}
    for causal in (False, True):
        f = shard_map(
            lambda q, k, v, c=causal: ulysses_attention(q, k, v, "sp", n, causal=c),
            mesh=mesh,
            in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
            out_specs=P(None, "sp"),
        )
        o = jax.jit(f)(q, k, v)
        o_ref, _ = ref.attention_ref(q, k, v, band=ref.causal_band() if causal else None)
        err = float(jnp.max(jnp.abs(o - o_ref)))
        results[f"causal{causal}"] = err
        assert err < 2e-5, (causal, err)
    # head-cap limitation must raise
    try:
        ulysses_attention(q[:, :4], k[:, :4], v[:, :4], "sp", 4)
        raise AssertionError("expected ValueError for n > Hkv")
    except ValueError:
        pass
    return results


def check_striped_decode():
    """Incremental striped-cache decode == full attention at every step."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    from repro.core.decode_attention import striped_cache_decode, striped_cache_update
    from repro.kernels import ref

    n = 4
    mesh = make_mesh((n,), ("sp",))
    B, H, Hkv, D = 2, 4, 2, 8
    cap = 8  # local slots -> max context n*cap = 32
    T = 20
    key = jax.random.PRNGKey(5)
    qs = jax.random.normal(key, (T, B, 1, H, D))
    ks = jax.random.normal(jax.random.PRNGKey(6), (T, B, 1, Hkv, D))
    vs = jax.random.normal(jax.random.PRNGKey(7), (T, B, 1, Hkv, D))

    # the cache entries take layer stacks: a one-layer stack, layer 0
    def upd(kc, vc, kn, vn, pos):
        kc, vc = striped_cache_update(kc[None], vc[None], kn, vn, pos, 0, "sp", n)
        return kc[0], vc[0]

    def dec(q, kc, vc, pos):
        return striped_cache_decode(q, kc[None], vc[None], pos, 0, "sp", n)

    upd_f = jax.jit(
        shard_map(
            upd, mesh=mesh,
            in_specs=(P(None, "sp"), P(None, "sp"), P(None, None), P(None, None), P()),
            out_specs=(P(None, "sp"), P(None, "sp")),
        )
    )
    dec_f = jax.jit(
        shard_map(
            dec, mesh=mesh,
            in_specs=(P(None, None), P(None, "sp"), P(None, "sp"), P()),
            out_specs=P(None, None),
        )
    )
    k_cache = jnp.zeros((B, n * cap, Hkv, D))
    v_cache = jnp.zeros((B, n * cap, Hkv, D))
    max_err = 0.0
    for t in range(T):
        pos = jnp.int32(t)
        k_cache, v_cache = upd_f(k_cache, v_cache, ks[t], vs[t], pos)
        o = dec_f(qs[t], k_cache, v_cache, pos)
        o_ref, _ = ref.attention_ref(
            qs[t], ks[: t + 1, :, 0].transpose(1, 0, 2, 3), vs[: t + 1, :, 0].transpose(1, 0, 2, 3)
        )
        max_err = max(max_err, float(jnp.max(jnp.abs(o - o_ref))))
    assert max_err < 2e-5, max_err
    return {"max_err": max_err}


def check_decode_edge():
    """sharded_cache_decode/update edge cases on 8 fake devices: contiguous
    layout, sliding-window banding, empty-shard (den == 0) safety, and the
    per-slot position vector (mixed depths == per-row scalar decode)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    from repro.core.decode_attention import sharded_cache_decode, sharded_cache_update
    from repro.kernels import ref

    n = 8
    mesh = make_mesh((n,), ("sp",))
    B, H, Hkv, D = 2, 4, 2, 8
    m = 4  # local slots: global capacity n*m = 32
    T = 12
    qs = jax.random.normal(jax.random.PRNGKey(5), (T, B, 1, H, D))
    ks = jax.random.normal(jax.random.PRNGKey(6), (T, B, 1, Hkv, D))
    vs = jax.random.normal(jax.random.PRNGKey(7), (T, B, 1, Hkv, D))

    def build(layout, window=None, vec_pos=False, prune=True):
        pos_spec = P(None) if vec_pos else P()

        # the cache entries take layer stacks: a one-layer stack, layer 0
        def upd(kc, vc, kn, vn, pos):
            kc, vc = sharded_cache_update(
                kc[None], vc[None], kn, vn, pos, 0, "sp", n, layout=layout
            )
            return kc[0], vc[0]

        def dec(q, kc, vc, pos):
            return sharded_cache_decode(
                q, kc[None], vc[None], pos, 0, "sp", n, layout=layout, window=window,
                prune=prune,
            )

        upd_f = jax.jit(shard_map(
            upd, mesh=mesh,
            in_specs=(P(None, "sp"), P(None, "sp"), P(None, None), P(None, None), pos_spec),
            out_specs=(P(None, "sp"), P(None, "sp")),
            check_vma=False,
        ))
        dec_f = jax.jit(shard_map(
            dec, mesh=mesh,
            in_specs=(P(None, None), P(None, "sp"), P(None, "sp"), pos_spec),
            out_specs=P(None, None),
            check_vma=False,
        ))
        return upd_f, dec_f

    results = {}
    # 1+2+3: contiguous layout and striped+window, stepwise vs the dense
    # oracle.  Early steps (t < n under striping, t < m under contiguous)
    # leave most shards EMPTY — exercising the den == 0 psum guard.
    for name, layout, window in (
        ("contiguous", "contiguous", None),
        ("striped_window", "striped", 5),
        ("contiguous_window", "contiguous", 5),
    ):
        upd_f, dec_f = build(layout, window)
        k_cache = jnp.zeros((B, n * m, Hkv, D))
        v_cache = jnp.zeros((B, n * m, Hkv, D))
        max_err = 0.0
        for t in range(T):
            pos = jnp.int32(t)
            k_cache, v_cache = upd_f(k_cache, v_cache, ks[t], vs[t], pos)
            o = dec_f(qs[t], k_cache, v_cache, pos)
            assert not np.isnan(np.asarray(o)).any(), (name, t, "NaN")
            band = (t, 0, 0, (window - 1) if window else ref.BAND_INF)
            o_ref, _ = ref.attention_ref(
                qs[t],
                ks[: t + 1, :, 0].transpose(1, 0, 2, 3),
                vs[: t + 1, :, 0].transpose(1, 0, 2, 3),
                band=band,
            )
            max_err = max(max_err, float(jnp.max(jnp.abs(o - o_ref))))
        assert max_err < 2e-5, (name, max_err)
        results[name] = max_err

    # 4: per-slot position vector — rows at different depths in ONE call must
    # equal each row decoded alone at its own scalar depth
    for layout in ("striped", "contiguous"):
        upd_s, dec_s = build(layout)
        upd_v, dec_v = build(layout, vec_pos=True)
        depths = (3, 9)  # row 0 shallow, row 1 deep
        caches = []
        for b, depth in enumerate(depths):
            kc = jnp.zeros((1, n * m, Hkv, D))
            vc = jnp.zeros((1, n * m, Hkv, D))
            for t in range(depth):
                kc, vc = upd_s(kc, vc, ks[t, b : b + 1], vs[t, b : b + 1], jnp.int32(t))
            caches.append((kc, vc))
        kc = jnp.concatenate([c[0] for c in caches], axis=0)
        vc = jnp.concatenate([c[1] for c in caches], axis=0)
        pos_vec = jnp.asarray(depths, jnp.int32)
        # vector update writes each row at its own position...
        t = max(depths)  # any step index for fresh K/V
        kc2, vc2 = upd_v(kc, vc, ks[t], vs[t], pos_vec)
        o_vec = dec_v(qs[t], kc2, vc2, pos_vec)
        # ...and must match the per-row scalar path exactly
        max_err = 0.0
        for b, depth in enumerate(depths):
            kb, vb = upd_s(
                caches[b][0], caches[b][1],
                ks[t, b : b + 1], vs[t, b : b + 1], jnp.int32(depth),
            )
            o_b = dec_s(qs[t, b : b + 1], kb, vb, jnp.int32(depth))
            max_err = max(max_err, float(jnp.max(jnp.abs(o_vec[b : b + 1] - o_b))))
        assert max_err == 0.0, (layout, "vector pos != scalar pos", max_err)
        results[f"vec_pos_{layout}"] = max_err

    # 5: mask-pruned decode — the lax.cond shard skip under a sliding window
    # (shard-uniform window-start round-down) must be EXACT: bitwise equal to
    # the always-run-the-kernel program at every depth, scalar and vector pos.
    # window=3 < n=8 leaves most shards provably empty under both layouts.
    for layout in ("striped", "contiguous"):
        upd_f, dec_p = build(layout, window=3, prune=True)
        _, dec_u = build(layout, window=3, prune=False)
        k_cache = jnp.zeros((B, n * m, Hkv, D))
        v_cache = jnp.zeros((B, n * m, Hkv, D))
        for t in range(T):
            pos = jnp.int32(t)
            k_cache, v_cache = upd_f(k_cache, v_cache, ks[t], vs[t], pos)
            o_p = dec_p(qs[t], k_cache, v_cache, pos)
            o_u = dec_u(qs[t], k_cache, v_cache, pos)
            assert (np.asarray(o_p) == np.asarray(o_u)).all(), (layout, t)
        upd_v, dec_pv = build(layout, window=3, vec_pos=True, prune=True)
        _, dec_uv = build(layout, window=3, vec_pos=True, prune=False)
        pos_vec = jnp.asarray((3, 9), jnp.int32)  # mixed depths
        o_pv = dec_pv(qs[0], k_cache, v_cache, pos_vec)
        o_uv = dec_uv(qs[0], k_cache, v_cache, pos_vec)
        assert (np.asarray(o_pv) == np.asarray(o_uv)).all(), (layout, "vec")
        results[f"prune_exact_{layout}"] = 0.0
    return results


def check_serve_stream():
    """Continuous batching on a (2,4) mesh: a mixed-length arrival trace is
    served with slots at different depths decoding in one jitted step per
    tick; every request's tokens equal sequential single-request generation,
    and jit retraces are bounded by the bucket set."""
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.models import transformer as tfm
    from repro.parallel.context import ParallelCtx
    from repro.serve.engine import ServeEngine

    cfg = get_config("granite-8b").reduced()
    params = tfm.init_params(cfg, jax.random.PRNGKey(1))
    rng = np.random.default_rng(3)
    trace = [(16, 0), (32, 1), (64, 2), (16, 4)]
    prompts = [
        rng.integers(0, cfg.vocab_size, (ln,), dtype=np.int32) for ln, _ in trace
    ]
    new_tokens = 6

    mesh = make_mesh((2, 4), ("data", "model"))
    ctx = ParallelCtx(mesh=mesh, batch_axes=("data",), sp_axis="model",
                      block_q=8, block_kv=8)
    eng = ServeEngine(cfg, params, ctx=ctx, max_seq=128, num_slots=3)
    rids = [
        eng.submit(p, max_new_tokens=new_tokens, arrival_tick=tick)
        for p, (_, tick) in zip(prompts, trace)
    ]
    finished = eng.run()
    assert sum(eng.prefill_trace_counts.values()) == len({16, 32, 64})
    assert eng.decode_trace_count == 1, eng.decode_trace_count

    # sequential single-request oracle on a single device
    seq_eng = ServeEngine(cfg, params, max_seq=128, num_slots=1)
    for rid, p in zip(rids, prompts):
        ref_out = seq_eng.generate(p[None, :], max_new_tokens=new_tokens)
        got = finished[rid].generated
        assert got == ref_out[0].tolist(), (rid, got, ref_out[0].tolist())
    return {
        "tokens": {rid: finished[rid].generated for rid in rids},
        "prefill_traces": {str(k): v for k, v in eng.prefill_trace_counts.items()},
    }


def check_dispatch_seam():
    """The unified dispatch entry (registry + autotuned plan cache) ==
    single-device oracle for every backend it can route on this mesh."""
    import tempfile

    import jax
    import jax.numpy as jnp

    from repro.core.dispatch import (
        AttentionPlanConfig,
        distributed_attention,
        plan_from_ctx,
        plan_schedules,
    )
    from repro.core.am import CommModel
    from repro.core.tiling import stripe_permutation, unstripe_permutation
    from repro.kernels import ref
    from repro.parallel.context import ParallelCtx

    n = 8
    mesh = make_mesh((n,), ("sp",))
    B, S, H, Hkv, D = 2, n * 16, 4, 2, 16
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(21), 3)
    q = jax.random.normal(kq, (B, S, H, D))
    k = jax.random.normal(kk, (B, S, Hkv, D))
    v = jax.random.normal(kv, (B, S, Hkv, D))
    results = {}

    with tempfile.TemporaryDirectory() as cache_dir:
        base = ParallelCtx(mesh=mesh, sp_axis="sp", block_q=16, block_kv=16,
                           plan_cache_dir=cache_dir)
        cases = [
            ("mesh", dict(attn_impl="mesh"), True, "striped"),
            ("mesh_autotuned", dict(attn_impl="mesh", attn_autotune=True), True, "striped"),
            ("ring", dict(attn_impl="ring"), True, "striped"),
            # ulysses runs below on its own 2-device mesh (n=8 > Hkv=2 here)
        ]
        import dataclasses

        for name, over, causal, layout in cases:
            ctx = dataclasses.replace(base, **over)
            cfg = plan_from_ctx(ctx, causal=causal, layout=layout)
            f = jax.jit(lambda q, k, v, cfg=cfg, ctx=ctx: distributed_attention(
                q, k, v, cfg=cfg, ctx=ctx))
            if causal and layout == "striped":
                perm = stripe_permutation(S, n)
                inv = unstripe_permutation(S, n)
                o = f(q[:, perm], k[:, perm], v[:, perm])[:, inv]
                band = ref.causal_band()
            else:
                o, band = f(q, k, v), None
            o_ref, _ = ref.attention_ref(q, k, v, band=band)
            err = float(jnp.max(jnp.abs(o - o_ref)))
            results[name] = err
            assert err < 2e-5, (name, err)

        # ulysses routes when the head cap allows (2 devices over Hkv=2)
        mesh2 = make_mesh((2,), ("sp",))
        ctx2 = ParallelCtx(mesh=mesh2, sp_axis="sp", attn_impl="ulysses",
                           block_q=16, block_kv=16)
        cfg2 = plan_from_ctx(ctx2, causal=False, layout="contiguous")
        o = jax.jit(lambda q, k, v: distributed_attention(q, k, v, cfg=cfg2, ctx=ctx2))(q, k, v)
        o_ref, _ = ref.attention_ref(q, k, v)
        err = float(jnp.max(jnp.abs(o - o_ref)))
        results["ulysses"] = err
        assert err < 2e-5, ("ulysses", err)

        # the autotuned case must have persisted its plan; a fresh in-memory
        # state must round-trip it from disk
        import os

        from repro.core import dispatch as dsp

        plans = [fn for fn in os.listdir(cache_dir) if fn.endswith(".json")]
        assert plans, "autotuned run left no on-disk plan"
        dsp._MEM_CACHE.clear()
        cfg_at = plan_from_ctx(
            dataclasses.replace(base, attn_impl="mesh", attn_autotune=True),
            causal=True, layout="striped",
        )
        comm = CommModel(seq=S, hidden=H * D, n=n, kv_hidden=Hkv * D,
                         bytes_per_elem=4, batch=B)
        a, fwd, bwd = plan_schedules(cfg_at, comm)
        assert fwd.n == n and (bwd is None or bwd.n == n)
        results["plan_cache_files"] = len(plans)

    # unknown backend must fail loudly
    try:
        distributed_attention(q, k, v, cfg=AttentionPlanConfig(backend="nope", n=n))
        raise AssertionError("expected ValueError for unknown backend")
    except ValueError:
        pass
    return results


def check_pipeline_parallel():
    """GPipe pipeline over a 'pipe' axis == sequential layer application,
    forward AND gradients (autodiff through the ppermute schedule)."""
    import jax
    import jax.numpy as jnp

    from repro.parallel.pipeline import pipeline_apply, pipeline_stages

    L, D, M, mb = 8, 16, 6, 4
    n_stages = 4
    mesh = make_mesh((n_stages,), ("pipe",))
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    params = {
        "w": jax.random.normal(ks[0], (L, D, D)) / D**0.5,
        "b": jax.random.normal(ks[1], (L, D)) * 0.1,
    }
    x = jax.random.normal(ks[2], (M, mb, D))

    def layer_fn(lp, h):
        return jnp.tanh(h @ lp["w"] + lp["b"])

    def run_pipe(params, x):
        staged = pipeline_stages(params, n_stages)
        return pipeline_apply(layer_fn, staged, x, mesh=mesh, n_stages=n_stages)

    def run_seq(params, x):
        def body(h, lp):
            return layer_fn(lp, h), None

        out, _ = jax.lax.scan(lambda h, lp: body(h, lp), x.reshape(M * mb, D), params)
        return out.reshape(M, mb, D)

    y_pipe = jax.jit(run_pipe)(params, x)
    y_seq = jax.jit(run_seq)(params, x)
    err_fwd = float(jnp.max(jnp.abs(y_pipe - y_seq)))
    assert err_fwd < 1e-5, err_fwd

    g_pipe = jax.jit(jax.grad(lambda p: jnp.sum(jnp.sin(run_pipe(p, x)))))(params)
    g_seq = jax.jit(jax.grad(lambda p: jnp.sum(jnp.sin(run_seq(p, x)))))(params)
    err_bwd = max(
        float(jnp.max(jnp.abs(a - b)))
        for a, b in zip(jax.tree.leaves(g_pipe), jax.tree.leaves(g_seq))
    )
    assert err_bwd < 1e-5, err_bwd
    return {"fwd_err": err_fwd, "bwd_err": err_bwd}


def check_collective_mode():
    """Algorithm-1 collective mode (2-D attention axes, native all-gathers)
    == single-device oracle AND == the ring-decomposed implementation."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.core.mesh_attention import MeshAttentionConfig, mesh_attention
    from repro.core.mesh_attention_collective import mesh_attention_collective
    from repro.core.tiling import stripe_permutation, unstripe_permutation
    from repro.kernels import ref

    a, b = 2, 4
    n = a * b
    mesh2d = make_mesh((a, b), ("aq", "akv"))
    mesh1d = make_mesh((n,), ("sp",))
    B, S, H, Hkv, D = 2, n * 16, 4, 2, 16
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(kq, (B, S, H, D))
    k = jax.random.normal(kk, (B, S, Hkv, D))
    v = jax.random.normal(kv, (B, S, Hkv, D))
    results = {}
    for causal in (False, True):
        fcol = jax.jit(
            shard_map(
                lambda q, k, v, c=causal: mesh_attention_collective(
                    q, k, v, "aq", "akv", causal=c, block_q=16, block_kv=16
                ),
                mesh=mesh2d,
                in_specs=(P(None, ("aq", "akv")),) * 3,
                out_specs=P(None, ("aq", "akv")),
                check_vma=False,
            )
        )
        cfg = MeshAttentionConfig(axis_name="sp", n=n, a=a, causal=causal,
                                  block_q=16, block_kv=16)
        fring = jax.jit(
            shard_map(
                lambda q, k, v: mesh_attention(q, k, v, cfg),
                mesh=mesh1d,
                in_specs=(P(None, "sp"),) * 3,
                out_specs=P(None, "sp"),
                check_vma=False,
            )
        )
        if causal:
            perm = stripe_permutation(S, n)
            inv = unstripe_permutation(S, n)
            o_col = fcol(q[:, perm], k[:, perm], v[:, perm])[:, inv]
            o_ring = fring(q[:, perm], k[:, perm], v[:, perm])[:, inv]
            band = ref.causal_band()
        else:
            o_col, o_ring, band = fcol(q, k, v), fring(q, k, v), None
        o_ref, _ = ref.attention_ref(q, k, v, band=band)
        err_ref = float(jnp.max(jnp.abs(o_col - o_ref)))
        err_ring = float(jnp.max(jnp.abs(o_col - o_ring)))
        results[f"causal{causal}"] = {"vs_ref": err_ref, "vs_ring": err_ring}
        assert err_ref < 2e-5 and err_ring < 2e-5, results
    return results


def check_mla_latent_wire():
    """MLA latent-wire Mesh-Attention == the decompressed-KV standard path."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.data.pipeline import make_batch
    from repro.models import transformer as tfm
    from repro.parallel.context import ParallelCtx

    cfg = get_config("minicpm3-4b").reduced()
    mesh = make_mesh((2, 4), ("data", "model"))
    base = ParallelCtx(mesh=mesh, batch_axes=("data",), sp_axis="model",
                       block_q=8, block_kv=8)
    wire = dataclasses.replace(base, mla_latent_wire=True)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    batch = make_batch(cfg, 32, 2, ctx=base)
    l1, _ = jax.jit(lambda p: tfm.forward(p, cfg, base, batch))(params)
    l2, _ = jax.jit(lambda p: tfm.forward(p, cfg, wire, batch))(params)
    err = float(jnp.max(jnp.abs(l1 - l2)))
    assert err < 2e-5, err
    return {"err": err}


def check_moe_ep_manual():
    """Manual-EP MoE (all_to_all dispatch inside shard_map) == single-device
    (capacity pinned high so per-shard vs global capacity cannot drop)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.data.pipeline import make_batch
    from repro.models import transformer as tfm
    from repro.parallel.context import ParallelCtx

    cfg = get_config("qwen2-moe-a2.7b").reduced()
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0, mode="ep"))
    mesh = make_mesh((2, 4), ("data", "model"))
    ctx = ParallelCtx(mesh=mesh, batch_axes=("data",), sp_axis="model",
                      block_q=8, block_kv=8)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0), ctx=ctx)
    batch = make_batch(cfg, 32, 2, ctx=ctx)
    l_dist, _ = jax.jit(lambda p: tfm.forward(p, cfg, ctx, batch))(params)

    single = ParallelCtx()
    batch1 = make_batch(cfg, 32, 2, ctx=single)
    l_one, _ = jax.jit(lambda p: tfm.forward(p, cfg, single, batch1))(params)
    # undo the stripe permutation for comparison
    from repro.core.tiling import unstripe_permutation

    inv = unstripe_permutation(32, 4)
    err = float(jnp.max(jnp.abs(l_dist[:, inv] - l_one)))
    assert err < 3e-5, err
    return {"err": err}


def check_train_distributed():
    """End-to-end: FSDP+CP train on a (pod,data,model) fake mesh with int8
    cross-pod gradient compression, crash, elastic resume on a DIFFERENT
    mesh shape (resharding at restore), loss finite and decreasing."""
    import tempfile

    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.parallel.compression import CompressionConfig
    from repro.parallel.context import ParallelCtx
    from repro.train import checkpoint as ckpt
    from repro.train.loop import TrainConfig, fit

    cfg = get_config("granite-8b").reduced()

    def ctx_pods():
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        return ParallelCtx(mesh=mesh, batch_axes=("pod", "data"), sp_axis="model",
                           block_q=8, block_kv=8)

    def ctx_flat():
        mesh = make_mesh((4, 2), ("data", "model"))
        return ParallelCtx(mesh=mesh, batch_axes=("data",), sp_axis="model",
                           block_q=8, block_kv=8)

    with tempfile.TemporaryDirectory() as d:
        tcfg = TrainConfig(steps=4, seq=32, batch=4, ckpt_dir=d, ckpt_every=2,
                           compression=CompressionConfig(kind="int8"))
        try:
            fit(cfg, ctx_pods(), tcfg, hooks={"fail_at": 2})
            raise AssertionError("expected injected failure")
        except RuntimeError:
            pass
        assert ckpt.latest_step(d) == 2
        # elastic resume on a different mesh (no pod axis -> no compression)
        tcfg2 = TrainConfig(steps=4, seq=32, batch=4, ckpt_dir=d, ckpt_every=2)
        out = fit(cfg, ctx_flat(), tcfg2)
        assert out["step"] == 4 and not out["interrupted"]
        hist = out["history"]
        assert all(np.isfinite(hist))
        # single-device reference: loss magnitudes line up (same data stream)
        ref = fit(cfg, ParallelCtx(), TrainConfig(steps=4, seq=32, batch=4))
        assert abs(hist[-1] - ref["history"][-1]) / ref["history"][-1] < 0.2
        return {"hist": hist, "ref": ref["history"]}


def check_serve_distributed():
    """Engine generation on a sequence-parallel mesh == single-device."""
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.models import transformer as tfm
    from repro.parallel.context import ParallelCtx
    from repro.serve.engine import ServeEngine

    cfg = get_config("granite-8b").reduced()
    params = tfm.init_params(cfg, jax.random.PRNGKey(1))
    prompts = (np.arange(16, dtype=np.int32).reshape(1, 16) * 7) % cfg.vocab_size

    single = ServeEngine(cfg, params, max_seq=64).generate(prompts, max_new_tokens=6)

    mesh = make_mesh((2, 4), ("data", "model"))
    ctx = ParallelCtx(mesh=mesh, batch_axes=("data",), sp_axis="model",
                      block_q=8, block_kv=8)
    dist = ServeEngine(cfg, params, ctx=ctx, max_seq=64).generate(prompts, max_new_tokens=6)
    assert (single == dist).all(), (single, dist)
    return {"tokens": single.tolist()}


def check_mask_prune():
    """Mask-aware schedule pruning on an 8-fake-device (2, 4) mesh: a packed
    two-document workload (contiguous layout) prunes whole schedule blocks
    AND the comm steps that only fed them; the pruned schedule's forward and
    gradients are BITWISE identical to the unpruned schedule and match the
    dense masked oracle."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    from repro.core.masking import MaskSpec
    from repro.core.mesh_attention import MeshAttentionConfig, mesh_attention
    from repro.kernels import ref

    n = 4  # sequence-parallel width of the (2, 4) mesh's model axis
    mesh = make_mesh((2, 4), ("data", "sp"))
    B, S, H, Hkv, D = 2, 64, 4, 2, 8
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(31), 3)
    q = jax.random.normal(kq, (B, S, H, D))
    k = jax.random.normal(kk, (B, S, Hkv, D))
    v = jax.random.normal(kv, (B, S, Hkv, D))
    doc_lens = (32, 32)
    spec = MaskSpec.document(doc_lens)
    seg = jnp.asarray(spec.segment_array(S))

    empty = spec.empty_blocks(2, 2, layout="contiguous", n=n, seq=S)
    assert empty, "expected prunable blocks for the aligned two-document mask"

    def build(cfg):
        f = shard_map(
            lambda q, k, v, s: mesh_attention(q, k, v, cfg, seg=s),
            mesh=mesh,
            in_specs=(P("data", "sp"),) * 3 + (P("sp"),),
            out_specs=P("data", "sp"),
            check_vma=False,
        )
        return f

    cfg_pruned = MeshAttentionConfig(
        axis_name="sp", n=n, a=2, mask=spec, layout="contiguous", block_q=8, block_kv=8
    )
    cfg_unpruned = dataclasses_replace_schedules(cfg_pruned)
    fwd_p, bwd_p = cfg_pruned.schedules(S)
    fwd_u, bwd_u = cfg_unpruned.schedules(S)
    assert len(fwd_p.comm_ops()) < len(fwd_u.comm_ops()), (
        fwd_p.comm_ops(), fwd_u.comm_ops(),
    )
    assert len(bwd_p.comm_ops()) < len(bwd_u.comm_ops())
    assert set(fwd_p.skip) == set(empty)

    f_p, f_u = build(cfg_pruned), build(cfg_unpruned)
    o_p = jax.jit(f_p)(q, k, v, seg)
    o_u = jax.jit(f_u)(q, k, v, seg)
    assert (np.asarray(o_p) == np.asarray(o_u)).all(), "pruned fwd != unpruned bitwise"

    o_ref, _ = ref.attention_ref(q, k, v, band=ref.causal_band(), seg_q=seg, seg_kv=seg)
    err = float(jnp.max(jnp.abs(o_p - o_ref)))
    assert err < 2e-5, err

    def loss(f):
        return lambda q, k, v: jnp.sum(jnp.sin(f(q, k, v, seg)))

    g_p = jax.jit(jax.grad(loss(f_p), argnums=(0, 1, 2)))(q, k, v)
    g_u = jax.jit(jax.grad(loss(f_u), argnums=(0, 1, 2)))(q, k, v)
    for a_, b_ in zip(g_p, g_u):
        assert (np.asarray(a_) == np.asarray(b_)).all(), "pruned grad != unpruned bitwise"
    return {
        "fwd_err": err,
        "pruned_blocks": sorted(list(map(list, empty))),
        "fwd_comms_pruned": fwd_p.comm_ops(),
        "fwd_comms_unpruned": fwd_u.comm_ops(),
        "bwd_comms_pruned": bwd_p.comm_ops(),
        "bwd_comms_unpruned": bwd_u.comm_ops(),
    }


def dataclasses_replace_schedules(cfg):
    """The same config forced to run UNPRUNED (explicit full schedules)."""
    import dataclasses

    from repro.core import schedule as Sch

    return dataclasses.replace(
        cfg,
        fwd_schedule=Sch.greedy_forward_schedule(cfg.a, cfg.b),
        bwd_schedule=Sch.greedy_backward_schedule(cfg.a, cfg.b),
    )


def check_overlap_exact():
    """comm_overlap modes are BITWISE-equal transports: on the 8-fake-device
    (2, 4) mesh, serial vs overlap vs bidir produce identical forward outputs
    AND identical gradients — for the plain causal striped ring, for a
    mask-PRUNED contiguous document schedule (seg tuples on the wire,
    paper-wire odoq backward), and for the Algorithm-1 collective mode."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    from repro.core import schedule as Sch
    from repro.core.masking import MaskSpec
    from repro.core.mesh_attention import MeshAttentionConfig, mesh_attention
    from repro.core.mesh_attention_collective import mesh_attention_collective

    n = 4
    mesh = make_mesh((2, 4), ("data", "sp"))
    B, S, H, Hkv, D = 2, 64, 4, 2, 8
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(57), 3)
    q = jax.random.normal(kq, (B, S, H, D))
    k = jax.random.normal(kk, (B, S, Hkv, D))
    v = jax.random.normal(kv, (B, S, Hkv, D))
    spec = MaskSpec.document((32, 32))
    seg = jnp.asarray(spec.segment_array(S))

    cases = {
        "causal_striped": (
            MeshAttentionConfig(axis_name="sp", n=n, a=2, causal=True,
                                layout="striped", block_q=8, block_kv=8),
            None,
        ),
        "doc_pruned_odoq": (
            MeshAttentionConfig(axis_name="sp", n=n, a=2, mask=spec,
                                layout="contiguous", bwd_wire="odoq",
                                block_q=8, block_kv=8),
            seg,
        ),
    }
    # the pruned case must actually exercise a pruned schedule
    fwd_sched, _ = cases["doc_pruned_odoq"][0].schedules(S)
    assert fwd_sched.skip, "document mask should prune blocks"

    detail = {}
    for name, (cfg, seg_in) in cases.items():
        outs, grads = {}, {}
        for mode in Sch.COMM_OVERLAP_MODES:
            c = dataclasses.replace(cfg, comm_overlap=mode)
            if seg_in is None:
                f = shard_map(
                    lambda q, k, v, c=c: mesh_attention(q, k, v, c),
                    mesh=mesh, in_specs=(P("data", "sp"),) * 3,
                    out_specs=P("data", "sp"), check_vma=False,
                )
                outs[mode] = jax.jit(f)(q, k, v)
                loss = lambda q, k, v, f=f: jnp.sum(jnp.sin(f(q, k, v)))
                grads[mode] = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
            else:
                f = shard_map(
                    lambda q, k, v, s, c=c: mesh_attention(q, k, v, c, seg=s),
                    mesh=mesh, in_specs=(P("data", "sp"),) * 3 + (P("sp"),),
                    out_specs=P("data", "sp"), check_vma=False,
                )
                outs[mode] = jax.jit(f)(q, k, v, seg_in)
                loss = lambda q, k, v, f=f: jnp.sum(jnp.sin(f(q, k, v, seg_in)))
                grads[mode] = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
        for mode in ("overlap", "bidir"):
            assert (np.asarray(outs[mode]) == np.asarray(outs["serial"])).all(), (
                f"{name}: {mode} fwd != serial bitwise"
            )
            for g_m, g_s in zip(grads[mode], grads["serial"]):
                assert (np.asarray(g_m) == np.asarray(g_s)).all(), (
                    f"{name}: {mode} grad != serial bitwise"
                )
        detail[name] = {"modes": list(Sch.COMM_OVERLAP_MODES), "bitwise": True}

    # Algorithm-1 collective mode: the knob maps onto the group all-gathers
    mesh2d = make_mesh((2, 4), ("aq", "akv"))
    col_outs = {}
    for mode in Sch.COMM_OVERLAP_MODES:
        fcol = shard_map(
            lambda q, k, v, m=mode: mesh_attention_collective(
                q, k, v, "aq", "akv", causal=True, block_q=8, block_kv=8,
                comm_overlap=m,
            ),
            mesh=mesh2d, in_specs=(P(None, ("aq", "akv")),) * 3,
            out_specs=P(None, ("aq", "akv")), check_vma=False,
        )
        col_outs[mode] = jax.jit(fcol)(q, k, v)
    for mode in ("overlap", "bidir"):
        assert (np.asarray(col_outs[mode]) == np.asarray(col_outs["serial"])).all(), (
            f"collective: {mode} != serial bitwise"
        )
    detail["collective"] = {"modes": list(Sch.COMM_OVERLAP_MODES), "bitwise": True}
    return detail


def check_packed_prefill():
    """Packed serve prefill on a (2, 4) mesh: several same-tick prompts share
    ONE prefill row under a document mask, each document's K/V scattered into
    its own slot — and every request's tokens equal sequential per-request
    generation exactly."""
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.models import transformer as tfm
    from repro.parallel.context import ParallelCtx
    from repro.serve.engine import ServeEngine

    cfg = get_config("granite-8b").reduced()
    params = tfm.init_params(cfg, jax.random.PRNGKey(7))
    rng = np.random.default_rng(9)
    prompts = [
        rng.integers(0, cfg.vocab_size, (ln,), dtype=np.int32) for ln in (16, 8, 8)
    ]

    mesh = make_mesh((2, 4), ("data", "model"))
    ctx = ParallelCtx(mesh=mesh, batch_axes=("data",), sp_axis="model",
                      block_q=8, block_kv=8)
    eng = ServeEngine(cfg, params, ctx=ctx, max_seq=128, num_slots=3)
    rids = [eng.submit(p, max_new_tokens=5, arrival_tick=0) for p in prompts]
    finished = eng.run()
    # all three prompts went through a single packed (bucket=32, k=3) trace
    assert eng.prefill_trace_counts == {(32, 3): 1}, eng.prefill_trace_counts

    seq_eng = ServeEngine(cfg, params, max_seq=128, num_slots=1)
    tokens = {}
    for rid, p in zip(rids, prompts):
        ref_out = seq_eng.generate(p[None, :], max_new_tokens=5)
        got = finished[rid].generated
        assert got == ref_out[0].tolist(), (rid, got, ref_out[0].tolist())
        tokens[rid] = got
    return {"tokens": tokens}


def check_paged_serve():
    """Paged KV cache on a (2, 4) mesh: the paged engine (page pool + block
    tables + refcounted allocator) must be token-for-token identical to the
    dense engine on the mixed-length streaming trace, and a pair of requests
    sharing a 32-token prefix must allocate strictly fewer pages than an
    unshared pair while still matching the dense engine exactly.

    A second paged run forces ``decode_kernel="native"`` — the paged kernel
    (kernels/paged_decode.py: block table read in-kernel, no gather
    intermediate; interpret-mode Pallas on these CPU devices) — and must
    produce the same tokens, so native == gather == dense on the live serve
    trace.  The device block-table upload count must stay version-gated."""
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.models import transformer as tfm
    from repro.parallel.context import ParallelCtx
    from repro.serve.engine import ServeEngine

    cfg = get_config("granite-8b").reduced()
    params = tfm.init_params(cfg, jax.random.PRNGKey(1))
    rng = np.random.default_rng(3)
    trace = [(16, 0), (32, 1), (64, 2), (16, 4)]
    prompts = [
        rng.integers(0, cfg.vocab_size, (ln,), dtype=np.int32) for ln, _ in trace
    ]
    new_tokens = 6

    mesh = make_mesh((2, 4), ("data", "model"))
    ctx = ParallelCtx(mesh=mesh, batch_axes=("data",), sp_axis="model",
                      block_q=8, block_kv=8)

    def run_engine(prompt_list, arrivals, **kw):
        eng = ServeEngine(cfg, params, ctx=ctx, max_seq=128, num_slots=3, **kw)
        rids = [
            eng.submit(p, max_new_tokens=new_tokens, arrival_tick=t)
            for p, t in zip(prompt_list, arrivals)
        ]
        fin = eng.run()
        return [fin[r].generated for r in rids], eng

    arrivals = [t for _, t in trace]
    dense_toks, _ = run_engine(prompts, arrivals)
    # n=4, page_size=4 -> 16-token chunks; 8 logical pages cover max_seq=128
    # ("auto" resolves to the gather oracle on CPU: Pallas is off-policy here)
    paged_toks, paged_eng = run_engine(prompts, arrivals, paged=True, page_size=4)
    assert paged_toks == dense_toks, (paged_toks, dense_toks)
    assert paged_eng.decode_trace_count == 1, paged_eng.decode_trace_count
    assert paged_eng.allocator.pages_in_use == 0  # every retirement freed
    # the NATIVE paged kernel (forced; interpret-mode Pallas on CPU) must
    # reproduce the trace token-for-token on the (2, 4) mesh
    native_toks, _ = run_engine(
        prompts, arrivals, paged=True, page_size=4, decode_kernel="native"
    )
    assert native_toks == dense_toks, (native_toks, dense_toks)
    # block-table uploads are version-gated (bounded by allocator mutations,
    # not by sync calls; tests/test_paged_decode.py pins the strict in-page
    # property with a controlled page size)
    assert 0 < paged_eng.bt_uploads <= paged_eng.allocator.version, (
        paged_eng.bt_uploads, paged_eng.allocator.version,
    )

    # prefix sharing: two 48-token prompts with a common 32-token prefix
    # (= 2 shared chunks) vs two unrelated 48-token prompts
    prefix = rng.integers(0, cfg.vocab_size, (32,), dtype=np.int32)
    shared_pair = [
        np.concatenate([prefix, rng.integers(0, cfg.vocab_size, (16,), dtype=np.int32)])
        for _ in range(2)
    ]
    unshared_pair = [
        rng.integers(0, cfg.vocab_size, (48,), dtype=np.int32) for _ in range(2)
    ]
    dense_sh, _ = run_engine(shared_pair, [0, 0])
    paged_sh, eng_sh = run_engine(shared_pair, [0, 0], paged=True, page_size=4)
    _, eng_un = run_engine(unshared_pair, [0, 0], paged=True, page_size=4)
    assert paged_sh == dense_sh, (paged_sh, dense_sh)
    st_sh, st_un = eng_sh.allocator.stats(), eng_un.allocator.stats()
    assert st_sh["shared_hits"] == 2, st_sh
    assert st_sh["fresh_allocs"] < st_un["fresh_allocs"], (st_sh, st_un)
    return {
        "tokens": {i: t for i, t in enumerate(paged_toks)},
        "native_equals_gather_equals_dense": True,
        "bt_uploads": paged_eng.bt_uploads,
        "ticks": paged_eng._tick,
        "shared_stats": st_sh,
        "unshared_stats": st_un,
    }


def check_continuous_prefill():
    """Continuous (chunked, budgeted) prefill on a (2, 4) mesh: an engine
    ingesting prompts in 16-token chunks under a 24-token/tick budget must be
    token-for-token identical to the one-shot engine AND to sequential
    single-device generation — dense and paged (prefix-shared pages
    included) — while tracing exactly one [slots, chunk] chunk step and one
    decode step.  This is the acceptance gate for the chunked-prefill cache
    scatter, the banded multi-row chunk attention, and the budget scheduler
    composing with the striped sequence-parallel decode stack."""
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.models import transformer as tfm
    from repro.parallel.context import ParallelCtx
    from repro.serve.config import ServeConfig
    from repro.serve.engine import ServeEngine

    cfg = get_config("granite-8b").reduced()
    params = tfm.init_params(cfg, jax.random.PRNGKey(1))
    rng = np.random.default_rng(3)
    trace = [(16, 0), (32, 1), (64, 2), (16, 4)]
    prompts = [
        rng.integers(0, cfg.vocab_size, (ln,), dtype=np.int32) for ln, _ in trace
    ]
    arrivals = [t for _, t in trace]
    new_tokens = 6

    mesh = make_mesh((2, 4), ("data", "model"))
    ctx = ParallelCtx(mesh=mesh, batch_axes=("data",), sp_axis="model",
                      block_q=8, block_kv=8)

    def run_engine(prompt_list, arrive, **kw):
        serve = ServeConfig(max_seq=128, num_slots=3, **kw)
        eng = ServeEngine(cfg, params, ctx=ctx, serve=serve)
        rids = [
            eng.submit(p, max_new_tokens=new_tokens, arrival_tick=t)
            for p, t in zip(prompt_list, arrive)
        ]
        fin = eng.run()
        return [fin[r].generated for r in rids], eng

    dense_toks, _ = run_engine(prompts, arrivals)
    chunk_toks, chunk_eng = run_engine(
        prompts, arrivals, prefill_chunk=16, tick_token_budget=24
    )
    assert chunk_toks == dense_toks, (chunk_toks, dense_toks)
    assert chunk_eng.chunk_trace_count == 1, chunk_eng.chunk_trace_count
    assert chunk_eng.decode_trace_count == 1, chunk_eng.decode_trace_count
    stats = chunk_eng.tick_stats()
    assert sum(stats["prefill_tokens"]) == sum(ln for ln, _ in trace)
    assert max(stats["prefill_tokens"]) <= 24, stats["prefill_tokens"]

    # sequential single-device oracle
    oracle = ServeEngine(cfg, params, serve=ServeConfig(max_seq=128, num_slots=1))
    for toks, p in zip(chunk_toks, prompts):
        ref_out = oracle.generate(p[None, :], max_new_tokens=new_tokens)
        assert toks == ref_out[0].tolist(), (toks, ref_out[0].tolist())

    # paged + prefix sharing under chunked ingestion (same-tick admissions:
    # the sharer's credit is capped at the mid-prefill donor's watermark)
    prefix = rng.integers(0, cfg.vocab_size, (32,), dtype=np.int32)
    shared_pair = [
        np.concatenate([prefix, rng.integers(0, cfg.vocab_size, (16,), dtype=np.int32)])
        for _ in range(2)
    ]
    paged_toks, paged_eng = run_engine(
        prompts, arrivals, paged=True, page_size=4,
        prefill_chunk=16, tick_token_budget=24,
    )
    assert paged_toks == dense_toks, (paged_toks, dense_toks)
    assert paged_eng.allocator.pages_in_use == 0
    dense_sh, _ = run_engine(shared_pair, [0, 0])
    paged_sh, eng_sh = run_engine(
        shared_pair, [0, 0], paged=True, page_size=4,
        prefill_chunk=16, tick_token_budget=24,
    )
    assert paged_sh == dense_sh, (paged_sh, dense_sh)
    assert eng_sh.allocator.stats()["shared_hits"] == 2, eng_sh.allocator.stats()
    return {
        "tokens": {i: t for i, t in enumerate(chunk_toks)},
        "chunk_launches": chunk_eng.chunk_launches,
        "tick_prefill_tokens": stats["prefill_tokens"],
        "tick_decode_tokens": stats["decode_tokens"],
        "paged_equals_dense": True,
        "shared_stats": eng_sh.allocator.stats(),
    }


def check_spec_decode():
    """Speculative multi-token decode on a (2, 4) mesh: an engine verifying
    prompt-lookup drafts through the banded [slots, spec_k] chunk launch
    must be token-for-token identical to the vanilla one-token-per-tick
    engine AND to sequential single-device generation — dense and paged
    (page-level rollback included, pool draining to zero) — while tracing
    exactly one verify step.  This is the acceptance gate for the
    speculative verify/commit path composing with the striped
    sequence-parallel decode stack and the refcounted page pool."""
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.models import transformer as tfm
    from repro.parallel.context import ParallelCtx
    from repro.serve.config import ServeConfig
    from repro.serve.engine import ServeEngine

    cfg = get_config("granite-8b").reduced()
    params = tfm.init_params(cfg, jax.random.PRNGKey(1))
    rng = np.random.default_rng(3)
    # repetitive prompts drive acceptance through the drafting path; the
    # random prompt keeps rejection + fallback ticks in the same run
    prompts = [
        np.tile(np.array([7, 11, 13, 7], np.int32), 6),
        rng.integers(0, cfg.vocab_size, (32,), dtype=np.int32),
        np.full((16,), 5, np.int32),
    ]
    arrivals = [0, 1, 2]
    new_tokens = 12

    mesh = make_mesh((2, 4), ("data", "model"))
    ctx = ParallelCtx(mesh=mesh, batch_axes=("data",), sp_axis="model",
                      block_q=8, block_kv=8)

    def run_engine(**kw):
        serve = ServeConfig(max_seq=128, num_slots=3, **kw)
        eng = ServeEngine(cfg, params, ctx=ctx, serve=serve)
        rids = [
            eng.submit(p, max_new_tokens=new_tokens, arrival_tick=t)
            for p, t in zip(prompts, arrivals)
        ]
        fin = eng.run()
        return [fin[r].generated for r in rids], eng

    vanilla_toks, _ = run_engine()
    spec_toks, spec_eng = run_engine(spec_k=4, spec_max_misses=None)
    assert spec_toks == vanilla_toks, (spec_toks, vanilla_toks)
    assert spec_eng.verify_trace_count == 1, spec_eng.verify_trace_count
    assert spec_eng.spec_accepted > 0, "repetitive trace drove no accepts"

    paged_toks, paged_eng = run_engine(
        spec_k=4, spec_max_misses=None, paged=True, page_size=4
    )
    assert paged_toks == vanilla_toks, (paged_toks, vanilla_toks)
    assert paged_eng.allocator.pages_in_use == 0
    stats = paged_eng.allocator.stats()

    # sequential single-device oracle
    oracle = ServeEngine(cfg, params, serve=ServeConfig(max_seq=128, num_slots=1))
    for toks, p in zip(spec_toks, prompts):
        ref_out = oracle.generate(p[None, :], max_new_tokens=new_tokens)
        assert toks == ref_out[0].tolist(), (toks, ref_out[0].tolist())

    return {
        "tokens": {i: t for i, t in enumerate(spec_toks)},
        "verify_launches": spec_eng.verify_launches,
        "spec_proposed": spec_eng.spec_proposed,
        "spec_accepted": spec_eng.spec_accepted,
        "paged_equals_dense": True,
        "spec_rolled_back_pages": stats["spec_rolled_back_pages"],
    }


def check_quant_kv():
    """Quantized (int8) paged KV pool on a (2, 4) mesh: an engine storing
    pages as int8 codes + per-(token, kv-head) f32 scales replays the mixed
    streaming trace — prefix sharing, continuous prefill (chunk=16,
    budget=24) and speculative verify (spec_k=4) all in one run — and must
    track the fp paged engine with every per-token logit inside the
    documented quantization error bound (greedy flips allowed only on
    near-ties the bound itself explains), while pages AND scale-table
    entries drain back to zero.  This is the
    acceptance gate for quantize-on-write across all cache update paths
    (chunked prefill scatter, decode append, verify/rollback) composing
    with in-kernel dequant and the refcounted scale side table."""
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.models import transformer as tfm
    from repro.parallel.context import ParallelCtx
    from repro.serve.config import ServeConfig
    from repro.serve.engine import ServeEngine

    cfg = get_config("granite-8b").reduced()
    params = tfm.init_params(cfg, jax.random.PRNGKey(1))
    rng = np.random.default_rng(3)
    # repetitive prompts drive speculative accepts; the random prompt keeps
    # rejection/rollback ticks in the run; the shared prefix pair exercises
    # CoW scale copies under chunked ingestion
    prompts = [
        np.tile(np.array([7, 11, 13, 7], np.int32), 6),
        rng.integers(0, cfg.vocab_size, (32,), dtype=np.int32),
    ]
    prefix = rng.integers(0, cfg.vocab_size, (16,), dtype=np.int32)
    prompts += [
        np.concatenate([prefix, np.full((8,), 5, np.int32)]),
        np.concatenate([prefix, np.full((8,), 9, np.int32)]),
    ]
    arrivals = [0, 1, 2, 2]
    new_tokens = 12
    # documented elementwise cache bound is amax/254 (int8); after one
    # attention layer + lm head on the reduced config the empirical logit
    # error is ~0.04, so 0.25 is a conservative end-to-end ceiling
    logit_bound = 0.25

    mesh = make_mesh((2, 4), ("data", "model"))
    ctx = ParallelCtx(mesh=mesh, batch_axes=("data",), sp_axis="model",
                      block_q=8, block_kv=8)

    def run_engine(kv_dtype):
        serve = ServeConfig(
            max_seq=128, num_slots=3, paged=True, page_size=4,
            prefill_chunk=16, tick_token_budget=24,
            spec_k=4, spec_max_misses=None, kv_dtype=kv_dtype,
        )
        eng = ServeEngine(cfg, params, ctx=ctx, serve=serve)
        eng.capture_logits = True
        rids = [
            eng.submit(p, max_new_tokens=new_tokens, arrival_tick=t)
            for p, t in zip(prompts, arrivals)
        ]
        fin = eng.run()
        return [fin[r].generated for r in rids], [
            eng.debug_logits[r] for r in rids
        ], eng

    fp_toks, fp_logits, fp_eng = run_engine("fp")
    q_toks, q_logits, q_eng = run_engine("int8")
    assert fp_eng.allocator.scale_entries_in_use == 0  # fp pool has no scales

    # per-token logit comparison is meaningful only while both engines have
    # generated the same context.  Greedy argmax may legitimately flip on a
    # quantization-scale near-tie; when it does, both engines must score the
    # two candidates within 2x the elementwise bound, and the streams are
    # incomparable (different contexts) from there on.
    max_err = 0.0
    matched = 0
    total = 0
    flips = 0
    for rid, (tf, tq) in enumerate(zip(fp_toks, q_toks)):
        rows_fp, rows_q = fp_logits[rid], q_logits[rid]
        assert len(rows_fp) == len(tf), (len(rows_fp), len(tf))
        assert len(rows_q) == len(tq), (len(rows_q), len(tq))
        total += len(tf)
        for i, (a, b) in enumerate(zip(tf, tq)):
            lf = rows_fp[i].astype(np.float64)
            lq = rows_q[i].astype(np.float64)
            err = float(np.max(np.abs(lf - lq)))
            max_err = max(max_err, err)
            assert err <= logit_bound, (rid, i, err, logit_bound)
            if a != b:
                flips += 1
                assert lf[a] - lf[b] <= 2 * logit_bound, (rid, i, a, b, lf[a] - lf[b])
                assert lq[b] - lq[a] <= 2 * logit_bound, (rid, i, a, b, lq[b] - lq[a])
                break
            matched += 1
    assert matched >= total // 2, (matched, total)

    # the quantized pool and its scale side table drain together
    assert q_eng.allocator.pages_in_use == 0, q_eng.allocator.pages_in_use
    assert q_eng.allocator.scale_entries_in_use == 0
    stats = q_eng.allocator.stats()
    assert q_eng.allocator.quantized and stats["peak_in_use"] >= 1, stats
    assert q_eng.spec_accepted > 0, "repetitive trace drove no accepts"
    assert stats["shared_hits"] >= 1, stats

    kv = q_eng.kv_cache_stats()
    # storage: int8 codes (1B) + 2 * Hkv f32 scales per token vs 2 * Hkv * D
    # fp entries — the modeled per-token HBM footprint must stay under 0.55x
    hd = cfg.hd
    fp_tok_bytes = 2 * hd * fp_eng._cache["k"].dtype.itemsize
    q_tok_bytes = 2 * hd * 1 + 2 * 4
    ratio = q_tok_bytes / fp_tok_bytes
    assert ratio <= 0.55, ratio

    return {
        "tokens": {i: t for i, t in enumerate(q_toks)},
        "tokens_matched": matched,
        "tokens_total": total,
        "near_tie_flips": flips,
        "max_logit_err": max_err,
        "logit_bound": logit_bound,
        "bytes_per_token_ratio": ratio,
        "peak_pages_in_use": stats["peak_in_use"],
        "shared_hits": stats["shared_hits"],
        "spec_accepted": q_eng.spec_accepted,
        "dequant_fallbacks": kv["dequant_fallbacks"],
    }


def check_chaos_serve():
    """Fault-tolerant serving on a (2, 4) mesh: an OVERSUBSCRIBED engine
    (oversubscribe=2.0 over a 7-page pool) under real mid-decode pool
    exhaustion must preempt-and-recompute and still produce token streams
    IDENTICAL to the conservative (oversubscribe=1.0, ample pool) engine —
    prefix sharers included, whose committed pages are refcount-protected
    through a donor's preemption.  A chaos-injected NaN tick must retire
    exactly one request (status numeric_error) while every other stream is
    bitwise-unchanged, and the full seeded chaos trace (squeeze + NaN +
    dropped grants) must replay deterministically with pages AND int8 scale
    entries draining to zero.  This is the acceptance gate for ISSUE 10's
    preempt/recompute, NaN guard, and chaos harness composing with the
    striped sequence-parallel decode stack."""
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.models import transformer as tfm
    from repro.parallel.context import ParallelCtx
    from repro.serve.config import ServeConfig
    from repro.serve.engine import ServeEngine
    from repro.testing.chaos import ChaosConfig, ChaosInjector

    cfg = get_config("granite-8b").reduced()
    params = tfm.init_params(cfg, jax.random.PRNGKey(1))
    rng = np.random.default_rng(3)
    # page_size=4 on 4 sp shards -> 16 tokens/page.  32-token prompts + 12
    # new tokens = 3 lifetime pages each; three requests need 9 pages but
    # the oversubscribed pool has 7 -> guaranteed mid-decode exhaustion.
    prefix = rng.integers(0, cfg.vocab_size, (32,), dtype=np.int32)
    prompts = [
        rng.integers(0, cfg.vocab_size, (32,), dtype=np.int32),
        np.concatenate([prefix[:16], rng.integers(0, cfg.vocab_size, (16,), dtype=np.int32)]),
        np.concatenate([prefix[:16], rng.integers(0, cfg.vocab_size, (16,), dtype=np.int32)]),
    ]
    new_tokens = 12

    mesh = make_mesh((2, 4), ("data", "model"))
    ctx = ParallelCtx(mesh=mesh, batch_axes=("data",), sp_axis="model",
                      block_q=8, block_kv=8)

    def run_engine(chaos=None, **kw):
        serve = ServeConfig(max_seq=128, num_slots=3, paged=True, page_size=4,
                            prefill_chunk=16, **kw)
        eng = ServeEngine(cfg, params, ctx=ctx, serve=serve, chaos=chaos)
        rids = [eng.submit(p, max_new_tokens=new_tokens) for p in prompts]
        fin = eng.run()
        return [fin[r] for r in rids], eng

    # 1. preempt-and-recompute == uninterrupted, prefix sharers intact
    ref, _ = run_engine(num_pages=12)
    got, eng = run_engine(num_pages=7, oversubscribe=2.0, health_every=1)
    for r, g in zip(ref, got):
        assert g.status == "ok", g.status
        assert g.generated == r.generated, (r.generated, g.generated)
    assert eng.preemptions > 0, "7-page pool drove no preemption"
    assert eng.allocator.pages_in_use == 0
    assert eng.allocator.stats()["shared_hits"] >= 1

    # 2. one injected NaN retires exactly one request; the other slots'
    # streams are bitwise-unchanged vs the fault-free int8 run
    clean, _ = run_engine(num_pages=12, kv_dtype="int8")
    nan_cfg = ChaosConfig(seed=11, ticks=10, squeezes=0, nan_ticks=1,
                          drop_ticks=0)
    hurt, nan_eng = run_engine(num_pages=12, kv_dtype="int8",
                               chaos=ChaosInjector(nan_cfg))
    statuses = [g.status for g in hurt]
    assert statuses.count("numeric_error") == 1, statuses
    assert nan_eng.numeric_errors == 1
    survivors = 0
    for c, h in zip(clean, hurt):
        if h.status == "ok":
            assert h.generated == c.generated, (c.generated, h.generated)
            survivors += 1
    assert survivors == len(prompts) - 1
    assert nan_eng.allocator.pages_in_use == 0
    assert nan_eng.allocator.scale_entries_in_use == 0

    # 3. the full fault trace replays deterministically, pool + scales drain
    full_cfg = ChaosConfig(seed=5, ticks=14, squeezes=2, squeeze_frac=0.5,
                           squeeze_hold=3, nan_ticks=1, drop_ticks=1)
    runs = []
    for _ in range(2):
        inj = ChaosInjector(full_cfg)
        res, e = run_engine(num_pages=7, oversubscribe=2.0, kv_dtype="int8",
                            health_every=2, chaos=inj)
        assert e.allocator.pages_in_use == 0
        assert e.allocator.scale_entries_in_use == 0
        e.health()
        runs.append((inj.events, [(g.status, g.generated) for g in res], e))
    assert runs[0][0] == runs[1][0], (runs[0][0], runs[1][0])
    assert runs[0][1] == runs[1][1], (runs[0][1], runs[1][1])
    chaos_eng = runs[0][2]
    # ok streams match the fault-free engine of the SAME kv_dtype (int8
    # near-ties make fp an invalid oracle here)
    for c, (status, gen) in zip(clean, runs[0][1]):
        if status == "ok":
            assert gen == c.generated, (c.generated, gen)

    return {
        "tokens": {i: g.generated for i, g in enumerate(got)},
        "preemptions": eng.preemptions,
        "recompute_tokens": eng.recompute_tokens,
        "nan_statuses": statuses,
        "chaos_events": runs[0][0],
        "chaos_statuses": [s for s, _ in runs[0][1]],
        "chaos_preemptions": chaos_eng.preemptions,
        "chaos_dropped_grants": chaos_eng.chaos_dropped_grants,
        "deterministic_replay": True,
    }


CHECKS = {
    "mesh_fwd": check_mesh_attention_forward,
    "mesh_bwd": check_mesh_attention_backward,
    "mesh_pallas": check_mesh_attention_pallas_interpret,
    "ring_eq": check_ring_equals_mesh_a1,
    "ulysses": check_ulysses,
    "decode": check_striped_decode,
    "decode_edge": check_decode_edge,
    "train_dist": check_train_distributed,
    "serve_dist": check_serve_distributed,
    "serve_stream": check_serve_stream,
    "mla_wire": check_mla_latent_wire,
    "moe_ep": check_moe_ep_manual,
    "collective_mode": check_collective_mode,
    "pipeline": check_pipeline_parallel,
    "dispatch": check_dispatch_seam,
    "mask_prune": check_mask_prune,
    "overlap_exact": check_overlap_exact,
    "packed_prefill": check_packed_prefill,
    "paged_serve": check_paged_serve,
    "continuous_prefill": check_continuous_prefill,
    "spec_decode": check_spec_decode,
    "quant_kv": check_quant_kv,
    "chaos_serve": check_chaos_serve,
}


def main(argv):
    names = argv or list(CHECKS)
    report = {}
    failed = False
    for name in names:
        try:
            report[name] = {"ok": True, "detail": CHECKS[name]()}
        except Exception as e:  # noqa: BLE001
            failed = True
            report[name] = {"ok": False, "error": f"{e}", "tb": traceback.format_exc()}
    print(json.dumps(report))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
