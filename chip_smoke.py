"""Smoke run of the system's main path on a TPU.

    python chip_smoke.py             # one chip: serve + train, granite-8b widths
    python chip_smoke.py --chips 4   # 2x2 host: context-parallel attention and
                                     # training, Mesh-Attention tile vs ring

One chip.  granite-8b (d_model 4096, 32 heads over 8 KV heads, head_dim 128,
d_ff 14336, vocab 49152) at its published widths; only depth is cut, and
the cut is printed.  Weights are random from ``--seed``.
  * decode kernel: the paged decode kernel alone at the model's
    heads (32 over 8 KV heads, head_dim 128), bf16 and int8 pools on the
    engine's page geometry, slot depths 3 to 2080, against a float64
    reference.  Random-weight logits barely move when attention is wrong
    at long context, so the kernel is checked here, directly.
  * serve: 8 layers, bf16 weights and KV, through ``ServeEngine`` with the
    paged pool and the native decode kernel: 4 requests (prompts of
    128/512/1024/2048 tokens, 32 greedy tokens each).  The shortest and
    longest request's logits at the last prompt token and at the first
    decode step are checked against a float32 forward of the same weights
    (jnp reference attention, highest matmul precision).
  * train: depth cut until f32 params + AdamW moments (12 B per parameter)
    fit in 12 GB, which leaves room for gradients and activations; 3 steps
    of ``train.loop.fit`` at seq 2048 through the compiled Pallas
    flash-attention forward and backward; every loss must be finite.

Four chips (``--chips 4``, and nothing else runs).
  * attention: causal striped bf16, S=32768, H=32, Hkv=8, D=128 on a 4-way
    sequence axis; the Mesh-Attention tile (2, 2) and the ring (1, 4),
    forward and gradients, against each other and against a blocked float32
    reference on a subset of query rows.
  * training: 3 steps of the train-cut granite-8b on the launchers' 4-way
    sequence axis, under the tile and under the ring; the losses must agree.

Every phase prints its lines; any failure exits non-zero.  The last line of
a successful run is one JSON object naming the device.  Without a TPU the
script exits non-zero and prints no result.  JAX's persistent compilation
cache is kept where ``JAX_COMPILATION_CACHE_DIR`` says, else at
``.jax_cache`` in the checkout.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

GRANITE = "granite-8b"
SERVE_LAYERS = 8
PROMPT_LENS = (128, 512, 1024, 2048)
NEW_TOKENS = 32
MAX_SEQ = 4096
TRAIN_STATE_BUDGET = 12e9  # bytes of f32 params + AdamW m, v
TRAIN_BYTES_PER_PARAM = 12
TRAIN_SEQ = 2048
TRAIN_STEPS = 3
# served bf16 logits vs the float32 forward, relative to the largest
# reference logit: bf16 rounds each activation to 2**-9, and 8 layers of
# such roundings stay within a few percent of the logit scale
LOGIT_REL_BOUND = 0.05
# the paged decode kernel alone, on the engine's page geometry: slot depths
# from a lone page to past the longest prompt, against a float64 reference
# on the same stored K/V.  o is held to a share of each slot's largest
# |o|: bf16 probabilities and bf16 output rounding are 2**-9 each
DECODE_DEPTHS = (3, 160, 1056, 2080)
DECODE_PAGE_SIZE = 16  # the engine's default at one device
DECODE_LAYER = 2  # the kernel reads this layer of a stacked pool (layers 0-1 poison)
DECODE_O_REL_BOUND = 2e-2
DECODE_LSE_BOUND = 2e-2  # absolute; bf16 inputs, f32 scores and sums
POISON = 1e4  # unused page slots: a leaked read dominates the softmax
# four-chip attention (bf16 in, bf16 out, f32 softmax inside the kernels)
CP_SEQ, CP_HEADS, CP_KV_HEADS, CP_HEAD_DIM = 32768, 32, 8, 128
CP_REF_ROWS = ((1024, 128), (CP_SEQ - 128, 128))  # (first row, rows) checked
CP_REL_BOUND = 2e-2  # of max |reference|; bf16 output rounding is 2**-9
CP_TRAIN_SEQ = 8192
CP_LOSS_BOUND = 1e-3  # |loss(tile) - loss(ring)|, f32 params


class Failure(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise Failure(msg)


class CompileClock:
    """Sums JAX's backend-compile durations (a persistent-cache hit records
    only its retrieval) and counts cache hits."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def depth_cut(name, layers):
    from repro.configs import get_config

    cfg = get_config(name)
    return dataclasses.replace(cfg, num_layers=layers), cfg.num_layers


def param_count(cfg):
    import jax

    from repro.models import transformer as tfm

    shapes = jax.eval_shape(lambda: tfm.init_params(cfg, jax.random.PRNGKey(0)))
    return sum(math.prod(x.shape) for x in jax.tree.leaves(shapes))


def train_depth(name):
    """Deepest cut of ``name`` whose f32 training state fits the budget."""
    from repro.configs import get_config

    full = get_config(name).num_layers
    best = None
    for layers in range(1, full + 1):
        cfg, _ = depth_cut(name, layers)
        if param_count(cfg) * TRAIN_BYTES_PER_PARAM > TRAIN_STATE_BUDGET:
            break
        best = layers
    check(best is not None, f"{name}: one layer exceeds the training budget")
    return best


def decode_kernel_phase(cfg, *, depths, page_size, max_seq, seed):
    """``paged_flash_decode`` alone at the model's heads, bf16 and int8
    pools, physical pages shuffled and every unwritten slot poisoned,
    against a float64 numpy softmax over the same stored K/V.  The pages
    sit at layer ``DECODE_LAYER`` of a stacked pool whose layers below it
    are all poison, so a read of the wrong layer cannot pass.  Returns
    {kv_dtype: (o_rel_err, lse_err)}, the worst slot of each."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import kv_quant
    from repro.kernels import paged_decode as pk

    H, hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    group = H // hkv
    rng = np.random.default_rng(seed)
    max_pages = max_seq // page_size
    counts = [-(-d // page_size) for d in depths]
    num_pages = sum(counts) + 1  # one page no table names
    phys = rng.permutation(num_pages)
    bt = np.full((len(depths), max_pages), -1, np.int32)
    kv = rng.standard_normal((2, num_pages, page_size, hkv, D), np.float32)
    used = np.zeros((num_pages, page_size), bool)
    taken = 0
    for b, (d, c) in enumerate(zip(depths, counts)):
        bt[b, :c] = phys[taken:taken + c]
        taken += c
        flat = np.arange(d)
        used[bt[b, flat // page_size], flat % page_size] = True
    q = jnp.asarray(rng.standard_normal((len(depths), 1, H, D)), jnp.bfloat16)
    pos = jnp.asarray([d - 1 for d in depths], jnp.int32)
    q64 = np.asarray(q.astype(jnp.float32), np.float64)[:, 0]

    out = {}
    for kv_dtype in ("fp", "int8"):
        if kv_dtype == "fp":
            k_pool, v_pool = (jnp.asarray(np.where(used[..., None, None], x, POISON),
                                          jnp.bfloat16) for x in kv)
            scales = {}
            k64, v64 = (np.asarray(x.astype(jnp.float32), np.float64)
                        for x in (k_pool, v_pool))
        else:
            (k_pool, ks), (v_pool, vs) = (kv_quant.quantize(jnp.asarray(x), "int8")
                                          for x in kv)
            k64, v64 = (np.asarray(kv_quant.dequantize(c, s), np.float64)
                        for c, s in ((k_pool, ks), (v_pool, vs)))
            poison = jnp.asarray(~used)[..., None]
            ks, vs = (jnp.where(poison, POISON, s) for s in (ks, vs))
            k_pool, v_pool = (jnp.where(poison[..., None], jnp.int8(127), c)
                              for c in (k_pool, v_pool))
            scales = {"k_scale": ks, "v_scale": vs}

        def stack(x, fill):  # the pages at DECODE_LAYER, poison below it
            below = jnp.full((DECODE_LAYER,) + x.shape, fill, x.dtype)
            return jnp.concatenate([below, x[None]])

        fill = POISON if kv_dtype == "fp" else 127
        k_pool, v_pool = stack(k_pool, fill), stack(v_pool, fill)
        scales = {name: stack(s, POISON) for name, s in scales.items()}
        o, lse = pk.paged_flash_decode(q, k_pool, v_pool, jnp.asarray(bt), pos, 0,
                                       DECODE_LAYER, stride_kv=1, **scales)
        o = np.asarray(o.astype(jnp.float32), np.float64)[:, 0]
        lse = np.asarray(lse, np.float64)[..., 0]
        o_err = lse_err = 0.0
        for b, d in enumerate(depths):
            flat = np.arange(d)
            pages = bt[b, flat // page_size]
            k = np.repeat(k64[pages, flat % page_size], group, axis=1)  # [d, H, D]
            v = np.repeat(v64[pages, flat % page_size], group, axis=1)
            s = np.einsum("hd,thd->ht", q64[b], k) * D**-0.5
            m = s.max(-1, keepdims=True)
            p = np.exp(s - m)
            want = np.einsum("ht,thd->hd", p / p.sum(-1, keepdims=True), v)
            o_err = max(o_err, float(np.max(np.abs(o[b] - want)) / np.max(np.abs(want))))
            want_lse = (m + np.log(p.sum(-1, keepdims=True)))[:, 0]
            lse_err = max(lse_err, float(np.max(np.abs(lse[b] - want_lse))))
        out[kv_dtype] = (o_err, lse_err)
    return out


def reference_logits(params32, cfg, prompts):
    """Last-token logits of a float32 forward (jnp attention, highest
    matmul precision) for each prompt."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.models import transformer as tfm
    from repro.parallel.context import ParallelCtx

    @jax.jit
    def fwd(p, tokens):
        batch = {"tokens": tokens, "positions": jnp.arange(tokens.shape[1])}
        logits, _ = tfm.forward(p, cfg, ParallelCtx(), batch)
        return logits[0, -1]

    prev = ops.current_backend()
    ops.set_backend("ref")
    try:
        with jax.default_matmul_precision("highest"):
            return [jax.device_get(fwd(params32, jnp.asarray(p)[None])) for p in prompts]
    finally:
        ops.set_backend(prev)


def serve_phase(cfg, *, prompt_lens, new_tokens, max_seq, seed, ref_lens):
    """Serve seeded requests through ServeEngine; returns the printed lines'
    numbers.  ``ref_lens``: prompt lengths whose logits are checked, at the
    last prompt token (prefill) and at the first decode step (the paged
    decode kernel over the prompt's pages plus the first generated token)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import transformer as tfm
    from repro.parallel.context import ParallelCtx
    from repro.serve.config import ServeConfig
    from repro.serve.engine import ServeEngine

    params = tfm.init_params(cfg, jax.random.PRNGKey(seed), dtype=jnp.bfloat16)
    serve = ServeConfig(max_seq=max_seq, num_slots=len(prompt_lens),
                        cache_dtype=jnp.bfloat16, paged=True)
    eng = ServeEngine(cfg, params, ctx=ParallelCtx(), serve=serve)
    eng.capture_logits = True  # read at trace time: on for both runs
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, (n,), dtype=np.int32) for n in prompt_lens]

    def drain():
        rids = [eng.submit(p, max_new_tokens=new_tokens) for p in prompts]
        finished = {}
        prefill_s = decode_s = 0.0
        while eng.has_work:
            before = sum(eng.tick_prefill_tokens)
            t0 = time.perf_counter()
            finished.update((r.rid, r) for r in eng.step())
            dt = time.perf_counter() - t0
            if sum(eng.tick_prefill_tokens) > before:
                prefill_s += dt
            else:
                decode_s += dt
        return rids, [finished[r] for r in rids], prefill_s, decode_s

    out = {"decode_kernel": eng.decode_kernel}
    for run in ("cold", "warm"):
        rids, done, prefill_s, decode_s = drain()
        check(all(r.status == "ok" for r in done),
              f"serve: statuses {[r.status for r in done]}")
        tokens = sum(len(r.generated) for r in done)
        check(tokens == len(prompts) * new_tokens, f"serve: {tokens} tokens")
        out[run] = {"finished": len(done), "tokens": tokens,
                    "prefill_s": prefill_s, "decode_s": decode_s}
        if run == "cold":
            # (label, served logits, the tokens they follow) per check
            served = []
            for p, rid, r in zip(prompts, rids, done):
                if len(p) in ref_lens:
                    logits = eng.debug_logits[rid]
                    served.append((f"prompt {len(p)} prefill", logits[0], p))
                    served.append((f"prompt {len(p)} first decode step", logits[1],
                                   np.append(p, np.int32(r.generated[0]))))
    # free the engine first, and each bf16 leaf as soon as its float32 copy
    # exists: both copies and the cache at once would crowd a 16 GB chip
    del eng
    gc.collect()
    leaves, tree = jax.tree.flatten(params)
    del params
    for i, leaf in enumerate(leaves):
        leaves[i] = leaf.astype(jnp.float32)
        del leaf
    params32 = jax.tree.unflatten(tree, leaves)
    del leaves
    gc.collect()
    refs = reference_logits(params32, cfg, [tokens for _, _, tokens in served])
    del params32
    gc.collect()
    errs = {}
    for (label, got, _), ref in zip(served, refs):
        got = np.asarray(got, np.float32)
        check(bool(np.all(np.isfinite(got))), f"serve: non-finite logits ({label})")
        scale = float(np.max(np.abs(ref)))
        errs[label] = (float(np.max(np.abs(got - ref))), LOGIT_REL_BOUND * scale)
    out["logit_err"] = errs
    return out


def train_phase(cfg, ctx, *, seq, steps, seed):
    import numpy as np

    from repro.optim.adamw import AdamWConfig
    from repro.train.loop import TrainConfig, fit

    times = []
    t = [time.perf_counter()]

    def on_step(step, metrics):
        float(metrics["loss"])
        now = time.perf_counter()
        times.append(now - t[0])
        t[0] = now

    res = fit(cfg, ctx, TrainConfig(steps=steps, seq=seq, batch=1, seed=seed),
              AdamWConfig(total_steps=steps), hooks={"on_step": on_step})
    losses = res["history"]
    check(len(losses) == steps and bool(np.all(np.isfinite(losses))),
          f"train: losses {losses}")
    del res
    gc.collect()
    return losses, times


def cp_attention_phase(mesh, *, seq, heads, kv_heads, head_dim, ref_rows, seed,
                       block=128):
    """Mesh-Attention (2, 2) vs ring (1, 4) vs a float32 reference on the
    sequence axis ``sp`` of ``mesh``: forward output and (dq, dk, dv)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    from repro.core.mesh_attention import MeshAttentionConfig, mesh_attention
    from repro.core.tiling import stripe_permutation, unstripe_permutation
    from repro.kernels import ref

    n = mesh.shape["sp"]
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(keys[0], (1, seq, heads, head_dim), jnp.bfloat16)
    k = jax.random.normal(keys[1], (1, seq, kv_heads, head_dim), jnp.bfloat16)
    v = jax.random.normal(keys[2], (1, seq, kv_heads, head_dim), jnp.bfloat16)
    g = jax.random.normal(keys[3], (1, seq, heads, head_dim), jnp.bfloat16)
    perm = np.asarray(stripe_permutation(seq, n))
    inv = np.asarray(unstripe_permutation(seq, n))

    def run(a):
        cfg = MeshAttentionConfig(axis_name="sp", n=n, a=a, causal=True,
                                  layout="striped", block_q=block, block_kv=block)
        f = shard_map(lambda q, k, v: mesh_attention(q, k, v, cfg), mesh=mesh,
                      in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp"))

        @jax.jit
        def fwd_bwd(q, k, v, g):
            o, vjp = jax.vjp(f, q, k, v)
            return (o,) + vjp(g)

        outs = fwd_bwd(q[:, perm], k[:, perm], v[:, perm], g[:, perm])
        return [np.asarray(x[:, inv].astype(jnp.float32)) for x in outs]

    t0 = time.perf_counter()
    tile = run(2)
    tile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    ring = run(1)
    ring_s = time.perf_counter() - t0

    @jax.jit
    def ref_rows_fn(qs, k, v, gs, r0):
        def o_of(qs):
            band = (r0, 0, 0, ref.BAND_INF)
            return ref.attention_ref(qs, k, v, band=band)[0]

        o, vjp = jax.vjp(o_of, qs)
        return o, vjp(gs)[0]

    names = ("o", "dq", "dk", "dv")
    err = {}
    for i, name in enumerate(names):
        scale = float(np.max(np.abs(ring[i])))
        err[f"tile_vs_ring_{name}"] = (float(np.max(np.abs(tile[i] - ring[i]))),
                                       CP_REL_BOUND * scale)
    f32 = lambda x: x.astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        for r0, rows in ref_rows:
            sl = slice(r0, r0 + rows)
            o_ref, dq_ref = ref_rows_fn(f32(q[:, sl]), f32(k), f32(v), f32(g[:, sl]),
                                        jnp.int32(r0))
            for name, got, want in (("o", 0, o_ref), ("dq", 1, dq_ref)):
                want = np.asarray(want)
                bound = CP_REL_BOUND * float(np.max(np.abs(want)))
                for label, res in (("tile", tile), ("ring", ring)):
                    e = float(np.max(np.abs(res[got][:, sl] - want)))
                    err[f"{label}_vs_ref_{name}_rows{r0}"] = (e, bound)
    for name, (e, bound) in err.items():
        check(e <= bound, f"cp attention: {name} error {e} > bound {bound}")
    return err, tile_s, ring_s


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    kernels = os.environ.get("REPRO_KERNELS", "auto")
    if kernels != "auto":
        print(f"REPRO_KERNELS={kernels}: the smoke run takes only the default "
              "kernel policy", file=sys.stderr)
        return 1

    import jax

    from repro.kernels import ops
    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX found {dev.platform} devices", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"--chips {args.chips}: JAX found {len(devices)} devices", file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    clock = CompileClock()
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache {cache_dir}")
    check(ops.pallas_enabled() and ops.attention_backend() == "pallas",
          f"kernels resolve to {ops.attention_backend()}, not compiled Pallas")
    print(f"kernels: attention={ops.attention_backend()}")

    try:
        if args.chips == 1:
            run_one_chip(args.seed, clock)
        else:
            run_four_chips(args.seed, clock)
    except Failure as e:
        print(f"FAILED: {e}", file=sys.stderr)
        return 1
    print(f"compile: {clock.seconds:.1f} s in total, {clock.hits} persistent-cache hits")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}}))
    return 0


def run_one_chip(seed, clock):
    from repro.parallel.context import ParallelCtx

    cfg, full = depth_cut(GRANITE, SERVE_LAYERS)
    c0 = clock.seconds
    errs = decode_kernel_phase(cfg, depths=DECODE_DEPTHS, page_size=DECODE_PAGE_SIZE,
                               max_seq=MAX_SEQ, seed=seed)
    for kv_dtype, (o_err, lse_err) in errs.items():
        print(f"decode kernel: {kv_dtype} pool at layer {DECODE_LAYER} of a stack, "
              f"H={cfg.num_heads} Hkv={cfg.num_kv_heads} D={cfg.hd}, depths "
              f"{DECODE_DEPTHS}: o max abs "
              f"err {o_err:.4g} of the slot's max |o| (bound {DECODE_O_REL_BOUND}), "
              f"lse {lse_err:.4g} (bound {DECODE_LSE_BOUND})")
        check(o_err <= DECODE_O_REL_BOUND and lse_err <= DECODE_LSE_BOUND,
              f"decode kernel: {kv_dtype} pool off the reference")
    print(f"decode kernel: compile {clock.seconds - c0:.1f} s")

    print(f"serve: {GRANITE} at published widths, depth cut {full} -> "
          f"{cfg.num_layers} layers ({param_count(cfg) / 1e9:.2f} B params, bf16)")
    c0 = clock.seconds
    out = serve_phase(cfg, prompt_lens=PROMPT_LENS, new_tokens=NEW_TOKENS,
                      max_seq=MAX_SEQ, seed=seed,
                      ref_lens=(PROMPT_LENS[0], PROMPT_LENS[-1]))
    check(out["decode_kernel"] == "native",
          f"serve: decode kernel resolved to {out['decode_kernel']}")
    print(f"serve: decode kernel={out['decode_kernel']}, attention=pallas")
    for run in ("cold", "warm"):
        r = out[run]
        print(f"serve {run}: {r['finished']} requests finished, {r['tokens']} "
              f"tokens; prefill ticks {r['prefill_s']:.3f} s, decode ticks "
              f"{r['decode_s']:.3f} s (host clock)")
    for label, (err, bound) in out["logit_err"].items():
        print(f"serve: {label}: logits vs float32 forward max abs err "
              f"{err:.4g} (bound {bound:.4g})")
        check(err <= bound, f"serve: logit error {err} > {bound} ({label})")
    print(f"serve: compile {clock.seconds - c0:.1f} s")

    layers = train_depth(GRANITE)
    cfg, full = depth_cut(GRANITE, layers)
    print(f"train: {GRANITE} at published widths, depth cut {full} -> {layers} "
          f"layers ({param_count(cfg) / 1e9:.2f} B params, f32 + AdamW), "
          f"seq {TRAIN_SEQ}, batch 1")
    c0 = clock.seconds
    losses, times = train_phase(cfg, ParallelCtx(), seq=TRAIN_SEQ,
                                steps=TRAIN_STEPS, seed=seed)
    for i, (loss, dt) in enumerate(zip(losses, times)):
        print(f"train: step {i} loss {loss:.6f} ({dt:.3f} s host clock)")
    print(f"train: compile {clock.seconds - c0:.1f} s")


def run_four_chips(seed, clock):
    import jax

    from repro.compat import make_mesh
    from repro.launch.mesh import launch_context

    ctx = launch_context(jax.device_count())
    print(f"launch: mesh {dict(ctx.mesh.shape)}, sequence axis "
          f"{ctx.sp_axis!r} {ctx.sp_size}-way")
    check(ctx.sp_size == 4, f"launch: sequence axis is {ctx.sp_size}-way")

    mesh = make_mesh((4,), ("sp",))
    c0 = clock.seconds
    err, tile_s, ring_s = cp_attention_phase(
        mesh, seq=CP_SEQ, heads=CP_HEADS, kv_heads=CP_KV_HEADS,
        head_dim=CP_HEAD_DIM, ref_rows=CP_REF_ROWS, seed=seed)
    print(f"cp attention: S={CP_SEQ} H={CP_HEADS} Hkv={CP_KV_HEADS} "
          f"D={CP_HEAD_DIM} bf16 causal striped; tile (2,2) fwd+bwd "
          f"{tile_s:.3f} s, ring (1,4) {ring_s:.3f} s (host clock, compile "
          f"included)")
    for name, (e, bound) in err.items():
        print(f"cp attention: {name} max abs err {e:.4g} (bound {bound:.4g})")
    print(f"cp attention: compile {clock.seconds - c0:.1f} s")

    layers = train_depth(GRANITE)
    cfg, full = depth_cut(GRANITE, layers)
    losses = {}
    for label, a in (("tile (2,2)", 2), ("ring (1,4)", 1)):
        c0 = clock.seconds
        tctx = launch_context(4, mesh_a=a)
        losses[label], times = train_phase(cfg, tctx, seq=CP_TRAIN_SEQ,
                                           steps=TRAIN_STEPS, seed=seed)
        for i, (loss, dt) in enumerate(zip(losses[label], times)):
            print(f"cp train {label}: {GRANITE} {layers} layers, seq "
                  f"{CP_TRAIN_SEQ}: step {i} loss {loss:.6f} ({dt:.3f} s)")
        print(f"cp train {label}: compile {clock.seconds - c0:.1f} s")
    diff = max(abs(x - y) for x, y in zip(*losses.values()))
    print(f"cp train: max |loss(tile) - loss(ring)| {diff:.3g} (bound {CP_LOSS_BOUND})")
    check(diff <= CP_LOSS_BOUND, f"cp train: losses differ by {diff}")


if __name__ == "__main__":
    sys.exit(main())
