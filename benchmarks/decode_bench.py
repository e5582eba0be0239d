"""Decode-kernel benchmark: gather-then-dense vs paged-native.

    PYTHONPATH=src python -m benchmarks.decode_bench [--json-out PATH]

Benches ONE decode tick (cache append + flash-decode through
``dispatch.decode_attention_step``) over a paged KV pool at several depth
mixes and pool occupancies, for both kernel variants:

  * ``gather`` — ``paged_cache_gather`` materializes every slot's full
    virtual-capacity view, then the dense band kernel runs over it; HBM
    traffic scales with *capacity*.
  * ``native`` — the paged Pallas kernel (kernels/paged_decode.py) reads
    the block table in-kernel and touches only allocated, band-visible
    pages; HBM traffic scales with *depth*.

Two quantities per scenario:

  * **modeled HBM bytes/token** — the analytic K/V read volume each variant
    must move per generated token (the paper's data-locality axis; exact by
    construction, hardware-independent).
  * **measured tokens/s** — wall time of the jitted step on the current
    backend.  On CPU CI the native kernel runs in Pallas *interpret* mode, so
    its measured number reflects interpreter overhead, not TPU behavior —
    the JSON carries ``native_backend`` so trajectory readers can tell; the
    modeled bytes are the portable signal.

Every cell also carries an ``int8`` twin: the same pool stored quantized
(1-byte K/V elements + f32 per-(token, kv-head) scales, dequantized in-path)
with its own modeled bytes/token, measured tokens/s, and max |Δoutput| vs
the fp run — ``int8_native_bytes_ratio`` is the storage-traffic headline.

With >= 8 devices a (2, 4)-mesh engine section rides along: the mixed
16/32/64 serve trace, dense vs paged-gather vs paged-native (fp and int8)
tokens/s plus the int8 engine's max per-token |Δlogit| vs the fp engine.
Results accumulate per commit as ``BENCH_decode_bench_<sha>.json`` (CI).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

try:
    import repro  # noqa: F401
except ImportError:  # running from a checkout without `pip install -e .`
    sys.path.insert(
        0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    )

RESULTS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "results")

# op-level geometry (granite-8b reduced attention head layout)
H, HKV, HD = 4, 2, 32
PAGE_SIZE = 16
MAX_SEQ = 256  # virtual capacity per slot
DTYPE_BYTES = 4  # fp32 pools
SCALE_BYTES = 4  # f32 per-(token, kv-head) scale entries (quantized pools)
# storage bytes per K-or-V element by pool storage mode
KV_DTYPE_BYTES = {"fp": DTYPE_BYTES, "int8": 1, "fp8": 1}

SCENARIOS = [
    # (name, per-slot depths)
    ("shallow_uniform", [32, 32, 32, 32]),
    ("mixed_depth", [16, 32, 64, 128]),
    ("deep_uniform", [224, 224, 224, 224]),
]
OCCUPANCIES = (0.25, 0.5, 1.0)


def pages_for(depth: int, page_size: int = PAGE_SIZE) -> int:
    return -(-depth // page_size)


def modeled_hbm_bytes_per_token(
    kernel: str, depths, max_pages: int, kv_dtype: str = "fp"
) -> float:
    """K/V bytes one decode tick must read per generated token.

    gather: every slot's FULL virtual capacity is materialized from the pool
    (unallocated entries clamp to page 0 but are still moved), then the band
    kernel reads the gathered copy again — capacity-proportional either way;
    the model counts the pool-read side only (the dominant, irreducible term).

    native: only allocated pages whose positions the band admits are DMA'd
    (each slot's page loop runs over its visible range only) —
    depth-proportional.

    ``kv_dtype`` sets the storage width: a quantized pool moves 1-byte K/V
    elements plus one f32 scale per (token, kv-head) for each of K and V —
    for HD=32 that is (2*32*1 + 2*4) / (2*32*4) = 72/256 ≈ 0.28x per page.
    """
    elem = KV_DTYPE_BYTES[kv_dtype]
    per_page = PAGE_SIZE * HKV * (HD + HD) * elem  # K + V
    if kv_dtype != "fp":
        per_page += PAGE_SIZE * HKV * 2 * SCALE_BYTES  # K + V scale entries
    if kernel == "gather":
        pages_read = len(depths) * max_pages
    else:
        pages_read = sum(pages_for(d) for d in depths)
    return pages_read * per_page / len(depths)  # one token per slot per tick


def _build_case(rng, depths, occupancy):
    """Allocator-backed pool at the requested occupancy (pages_in_use /
    num_pages), plus the step operands."""
    import jax.numpy as jnp
    import numpy as np

    from repro.serve.kv_pool import PageAllocator, PagedLayout

    max_pages = MAX_SEQ // PAGE_SIZE
    used = sum(pages_for(d) for d in depths)
    num_pages = max(used, int(round(used / occupancy)))
    lay = PagedLayout(num_pages=num_pages, page_size=PAGE_SIZE,
                      max_pages=max_pages, n=1)
    alloc = PageAllocator(lay)
    for slot, d in enumerate(depths):
        alloc.alloc_slot(slot, rng.integers(0, 2**30, (d,), dtype=np.int32), 0)
    B = len(depths)
    k_pool = jnp.asarray(rng.normal(size=(num_pages, PAGE_SIZE, HKV, HD)), jnp.float32)
    v_pool = jnp.asarray(rng.normal(size=(num_pages, PAGE_SIZE, HKV, HD)), jnp.float32)
    bt = jnp.asarray(alloc.device_table(B))
    q = jnp.asarray(rng.normal(size=(B, 1, H, HD)), jnp.float32)
    k_new = jnp.asarray(rng.normal(size=(B, 1, HKV, HD)), jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(B, 1, HKV, HD)), jnp.float32)
    # overwrite each slot's last token: the target page is always allocated
    # (that is the engine's ensure_append contract)
    pos = jnp.asarray([d - 1 for d in depths], jnp.int32)
    occ = used / num_pages
    return (q, k_new, v_new, k_pool, v_pool, pos, bt), occ, max_pages


def bench_op_level(reps: int = 30, seed: int = 0):
    import jax
    import numpy as np

    from repro.core import dispatch, kv_quant
    from repro.parallel.context import ParallelCtx

    ctx = ParallelCtx()
    rng = np.random.default_rng(seed)
    rows = []
    for name, depths in SCENARIOS:
        for occupancy in OCCUPANCIES:
            operands, occ, max_pages = _build_case(rng, depths, occupancy)
            q, k_new, v_new, k_pool, v_pool, pos, bt = operands
            # int8 twin of the same pool: quantized storage + scale tables
            qk_pool, k_scale = kv_quant.quantize(k_pool, "int8")
            qv_pool, v_scale = kv_quant.quantize(v_pool, "int8")
            row = {
                "scenario": name,
                "depths": depths,
                "occupancy": round(occ, 3),
                "virtual_cap": MAX_SEQ,
            }
            fp_out = {}
            for kernel in ("gather", "native"):
                fn = jax.jit(
                    lambda q, kn, vn, kp, vp, pos, bt, _k=kernel:
                    dispatch.decode_attention_step(
                        q, kn, vn, kp[None], vp[None], pos, ctx, layer=0,
                        block_table=bt, decode_kernel=_k,
                    )
                )
                o, kp2, vp2 = fn(*operands)
                o.block_until_ready()
                t0 = time.perf_counter()
                for _ in range(reps):
                    o, kp2, vp2 = fn(*operands)
                o.block_until_ready()
                wall = (time.perf_counter() - t0) / reps
                fp_out[kernel] = np.asarray(o)
                row[kernel] = {
                    "us_per_tick": wall * 1e6,
                    "tokens_per_s": len(depths) / wall,
                    "hbm_bytes_per_token": modeled_hbm_bytes_per_token(
                        kernel, depths, max_pages
                    ),
                }
                # int8 cell for the same kernel: quantized pool + in-path
                # dequant (in-kernel for native, gather-side for the ref)
                fn_q = jax.jit(
                    lambda q, kn, vn, kp, vp, pos, bt, ks, vs, _k=kernel:
                    dispatch.decode_attention_step(
                        q, kn, vn, kp[None], vp[None], pos, ctx, layer=0,
                        block_table=bt, decode_kernel=_k,
                        k_scale=ks[None], v_scale=vs[None],
                    )
                )
                ops_q = (q, k_new, v_new, qk_pool, qv_pool, pos, bt,
                         k_scale, v_scale)
                o_q = fn_q(*ops_q)[0]
                o_q.block_until_ready()
                t0 = time.perf_counter()
                for _ in range(reps):
                    o_q = fn_q(*ops_q)[0]
                o_q.block_until_ready()
                wall_q = (time.perf_counter() - t0) / reps
                row[kernel + "_int8"] = {
                    "us_per_tick": wall_q * 1e6,
                    "tokens_per_s": len(depths) / wall_q,
                    "hbm_bytes_per_token": modeled_hbm_bytes_per_token(
                        kernel, depths, max_pages, kv_dtype="int8"
                    ),
                    "max_abs_err_vs_fp": float(
                        np.max(np.abs(np.asarray(o_q) - fp_out[kernel]))
                    ),
                }
            row["hbm_bytes_ratio"] = (
                row["native"]["hbm_bytes_per_token"]
                / row["gather"]["hbm_bytes_per_token"]
            )
            row["tokens_per_s_ratio"] = (
                row["native"]["tokens_per_s"] / row["gather"]["tokens_per_s"]
            )
            # the quantization headline: int8 native traffic vs fp native
            row["int8_native_bytes_ratio"] = (
                row["native_int8"]["hbm_bytes_per_token"]
                / row["native"]["hbm_bytes_per_token"]
            )
            rows.append(row)
    return rows


def bench_engine_mesh(seed: int = 0, new_tokens: int = 6):
    """(2, 4)-mesh serve-trace tokens/s: dense vs paged-gather vs paged-native
    (requires >= 8 devices; returns None otherwise)."""
    import jax

    if jax.device_count() < 8:
        return None
    import numpy as np

    from repro.configs import get_config
    from repro.models import transformer as tfm
    from repro.parallel.context import ParallelCtx
    from repro.serve.engine import ServeEngine

    cfg = get_config("granite-8b").reduced()
    params = tfm.init_params(cfg, jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    trace = [(16, 0), (32, 1), (64, 2), (16, 4)]
    prompts = [rng.integers(0, cfg.vocab_size, (ln,), dtype=np.int32) for ln, _ in trace]
    from repro.compat import make_mesh

    mesh = make_mesh((2, 4), ("data", "model"))
    ctx = ParallelCtx(mesh=mesh, batch_axes=("data",), sp_axis="model",
                      block_q=8, block_kv=8)
    out = {}
    tokens = {}
    logits = {}
    for mode, kw in (
        ("dense", {}),
        ("paged_gather", dict(paged=True, page_size=4, decode_kernel="gather")),
        ("paged_native", dict(paged=True, page_size=4, decode_kernel="native")),
        ("paged_native_int8", dict(paged=True, page_size=4,
                                   decode_kernel="native", kv_dtype="int8")),
    ):
        eng = ServeEngine(cfg, params, ctx=ctx, max_seq=128, num_slots=3, **kw)
        # capture per-token logits on the fp reference and the int8 engine so
        # the quantization error lands in the per-commit JSON
        eng.capture_logits = mode in ("dense", "paged_native_int8")

        def submit():
            base = eng._tick
            return [
                eng.submit(p, max_new_tokens=new_tokens, arrival_tick=base + t)
                for p, (_, t) in zip(prompts, trace)
            ]

        rids = submit()
        eng.run()  # warm every (bucket, k) prefill + the decode trace
        tokens[mode] = [eng._finished[r].generated for r in rids]
        if eng.capture_logits:
            logits[mode] = [eng.debug_logits[r] for r in rids]
        base_tick = eng._tick
        submit()
        t0 = time.perf_counter()
        eng.run()
        wall = time.perf_counter() - t0
        total = len(prompts) * new_tokens
        out[mode] = {
            "tokens_per_s": total / wall,
            "ticks": eng._tick - base_tick,
            "wall_s": wall,
        }
    out["native_equals_gather_equals_dense"] = (
        tokens["paged_native"] == tokens["paged_gather"] == tokens["dense"]
    )
    out["int8_tokens_equal_fp"] = tokens["paged_native_int8"] == tokens["dense"]
    out["int8_max_logit_err_vs_fp"] = max(
        float(np.max(np.abs(a - b)))
        for fp_rows, q_rows in zip(logits["dense"], logits["paged_native_int8"])
        for a, b in zip(fp_rows, q_rows)
    )
    return out


def run_bench(seed: int = 0, reps: int = 30):
    import jax

    rows = bench_op_level(reps=reps, seed=seed)
    half = [r for r in rows if r["occupancy"] <= 0.55 and r["occupancy"] >= 0.3]
    payload = {
        "geometry": {
            "heads": H, "kv_heads": HKV, "head_dim": HD,
            "page_size": PAGE_SIZE, "virtual_cap": MAX_SEQ,
            "dtype_bytes": DTYPE_BYTES,
            "kv_dtype_bytes": KV_DTYPE_BYTES, "scale_bytes": SCALE_BYTES,
        },
        "op_level": rows,
        "native_backend": (
            "pallas" if jax.default_backend() == "tpu" else "pallas-interpret"
        ),
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        # headline: at <= 50% occupancy the native kernel's modeled traffic
        # follows depth while gather pays full virtual capacity per row
        "hbm_bytes_ratio_at_half_occupancy": (
            sum(r["hbm_bytes_ratio"] for r in half) / len(half) if half else None
        ),
        # quantization headline: int8 native storage traffic vs fp native —
        # identical at every cell by construction (both scale with depth),
        # reported per row too so CI can gate each occupancy cell
        "int8_native_bytes_ratio": max(r["int8_native_bytes_ratio"] for r in rows),
        "int8_max_abs_err": max(
            r[k + "_int8"]["max_abs_err_vs_fp"]
            for r in rows for k in ("gather", "native")
        ),
    }
    mesh_section = bench_engine_mesh(seed=seed)
    if mesh_section is not None:
        payload["mesh_engine"] = mesh_section
    return payload


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=30)
    ap.add_argument("--json-out", default=os.path.join(RESULTS_DIR, "decode_bench.json"))
    args = ap.parse_args(argv)
    payload = run_bench(reps=args.reps)
    os.makedirs(os.path.dirname(args.json_out), exist_ok=True)
    with open(args.json_out, "w") as f:
        json.dump(payload, f, indent=1)
    print(json.dumps({
        "hbm_bytes_ratio_at_half_occupancy": payload["hbm_bytes_ratio_at_half_occupancy"],
        "int8_native_bytes_ratio": payload["int8_native_bytes_ratio"],
        "int8_max_abs_err": payload["int8_max_abs_err"],
        "native_backend": payload["native_backend"],
        "mesh_engine": payload.get("mesh_engine"),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
