"""Benchmark harness: one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only NAME]

Prints ``name,us_per_call,derived`` CSV rows (us_per_call = simulated or
measured microseconds of the benchmarked operation; derived = the headline
quantity the paper reports for that table).  Detailed tables are written to
benchmarks/results/*.json for EXPERIMENTS.md.
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

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "results")


def _emit(name: str, us_per_call: float, derived: str):
    print(f"{name},{us_per_call:.3f},{derived}")


def _save(name: str, payload):
    os.makedirs(RESULTS_DIR, exist_ok=True)
    with open(os.path.join(RESULTS_DIR, f"{name}.json"), "w") as f:
        json.dump(payload, f, indent=1)


# ---- Table 2: theoretical communication volume ------------------------------


def bench_table2_comm_volume():
    from repro.core.am import table2

    rows = {}
    for n in (32, 64, 128, 256, 1024):
        rows[n] = table2(n)
    _save("table2_comm_volume", rows)
    red = 1 - rows[256]["mesh"] / rows[256]["ring"]
    _emit("table2_comm_volume", 0.0, f"mesh_vs_ring_reduction_256gpu={red:.1%}")
    return rows


# ---- Table 3: fwd+bwd throughput (simulated, paper-calibrated cluster) -------


def bench_table3_throughput():
    from benchmarks.common import PAPER_HW, attention_time

    rows = []
    t0 = time.perf_counter()
    for causal in (True, False):
        for seq in (256 * 1024, 512 * 1024, 1024 * 1024):
            for n in (32, 64, 128, 256):
                ring = attention_time(n, seq, a=1, causal=causal)
                mesh = attention_time(n, seq, a=None, causal=causal)
                rows.append(
                    {
                        "causal": causal, "seq": seq, "n": n,
                        "ring_iters_per_s": ring["iters_per_s"],
                        "mesh_iters_per_s": mesh["iters_per_s"],
                        "mesh_a": mesh["a"],
                        "speedup": mesh["iters_per_s"] / ring["iters_per_s"],
                    }
                )
    wall = (time.perf_counter() - t0) * 1e6 / len(rows)
    _save("table3_throughput", rows)
    sp = [r["speedup"] for r in rows]
    avg, mx = sum(sp) / len(sp), max(sp)
    _emit("table3_throughput", wall, f"speedup_avg={avg:.2f}x_max={mx:.2f}x (paper: 2.9x/3.4x)")
    return rows


# ---- Table 4: MFU -------------------------------------------------------------


def bench_table4_mfu():
    from benchmarks.common import attention_time, mfu

    rows = []
    for causal in (True, False):
        for seq in (256 * 1024, 512 * 1024, 1024 * 1024):
            for n in (32, 64, 128, 256):
                ring = attention_time(n, seq, a=1, causal=causal)
                mesh = attention_time(n, seq, a=None, causal=causal)
                rows.append(
                    {
                        "causal": causal, "seq": seq, "n": n,
                        "ring_mfu": mfu(n, seq, ring["total_s"], causal),
                        "mesh_mfu": mfu(n, seq, mesh["total_s"], causal),
                    }
                )
    _save("table4_mfu", rows)
    ratio = sum(r["mesh_mfu"] / max(r["ring_mfu"], 1e-9) for r in rows) / len(rows)
    _emit("table4_mfu", 0.0, f"mfu_ratio_avg={ratio:.2f}x (paper: 2.5x avg)")
    return rows


# ---- Figure 8: strong / weak scaling -----------------------------------------


def bench_fig8_scaling():
    from benchmarks.common import attention_time

    strong = []
    for n in (32, 64, 128, 256):
        ring = attention_time(n, 1 << 20, a=1, causal=True)
        mesh = attention_time(n, 1 << 20, a=None, causal=True)
        strong.append({"n": n, "ring_s": ring["total_s"], "mesh_s": mesh["total_s"]})
    weak = []
    seq = 512 * 1024
    for n in (32, 64, 128, 256):
        ring = attention_time(n, seq, a=1, causal=True)
        mesh = attention_time(n, seq, a=None, causal=True)
        weak.append({"n": n, "seq": seq, "ring_s": ring["total_s"], "mesh_s": mesh["total_s"]})
        seq = int(seq * 1.41421356)
    _save("fig8_scaling", {"strong": strong, "weak": weak})
    ring_slow = weak[-1]["ring_s"] / weak[0]["ring_s"]
    mesh_slow = weak[-1]["mesh_s"] / weak[0]["mesh_s"]
    _emit(
        "fig8_scaling", 0.0,
        f"weak_scaling_slowdown ring={ring_slow:.2f}x mesh={mesh_slow:.2f}x (paper: 3.74x/2.83x)",
    )
    return strong, weak


# ---- Figure 9: runtime + communication breakdown ------------------------------


def bench_fig9_breakdown():
    from benchmarks.common import attention_time

    rows = []
    for n in (32, 64, 128, 256):
        ring = attention_time(n, 1 << 20, a=1, causal=True)
        mesh = attention_time(n, 1 << 20, a=None, causal=True)
        rows.append(
            {
                "n": n,
                "ring_compute_s": ring["compute_s"],
                "ring_wait_s": ring["exposed_comm_s"],
                "mesh_compute_s": mesh["compute_s"],
                "mesh_wait_s": mesh["exposed_comm_s"],
                "ring_comm_gb": ring["comm_bytes"] / 1e9,
                "mesh_comm_gb": mesh["comm_bytes"] / 1e9,
            }
        )
    _save("fig9_breakdown", rows)
    r = rows[-1]
    wait_red = 1 - r["mesh_wait_s"] / max(r["ring_wait_s"], 1e-12)
    vol_red = 1 - r["mesh_comm_gb"] / r["ring_comm_gb"]
    _emit(
        "fig9_breakdown", 0.0,
        f"wait_reduction_256={wait_red:.1%} comm_volume_reduction_256={vol_red:.1%} "
        f"(paper: ~74.9%/85.5%)",
    )
    return rows


# ---- Table 5: peak memory ------------------------------------------------------


def bench_table5_peak_memory():
    """Analytic attention-working-set model, same units as the paper:
    Ring holds <=2 KV chunks + 1 Q chunk; Mesh holds a Q chunks + b KV chunks
    + partial-O accumulators; backward adds the OdOQ/dQ/dKV buffers."""
    from repro.core.tiling import best_square_a

    bytes_per = 2  # bf16
    rows = []
    for causal in (True, False):
        for seq in (256 * 1024, 512 * 1024, 1024 * 1024):
            for n in (32, 64, 128, 256):
                chunk = seq * 4096 // n * bytes_per
                a = best_square_a(n)
                b = n // a
                ring_fwd = (1 + 2 * 2) * chunk
                ring_bwd = (1 + 2 * 2 + 3) * chunk
                mesh_fwd = (a + 2 * b + 2 * a) * chunk  # Q + KV + fp32 O acc
                mesh_bwd = (3 * a + 2 * b + 2 * a + 2 * b) * chunk
                rows.append(
                    {
                        "causal": causal, "seq": seq, "n": n,
                        "ring_fwd_gb": ring_fwd / 2**30,
                        "ring_bwd_gb": ring_bwd / 2**30,
                        "mesh_fwd_gb": mesh_fwd / 2**30,
                        "mesh_bwd_gb": mesh_bwd / 2**30,
                    }
                )
    _save("table5_peak_memory", rows)
    r = next(x for x in rows if x["causal"] and x["seq"] == 1 << 20 and x["n"] == 256)
    _emit(
        "table5_peak_memory", 0.0,
        f"1M_256gpu mesh_fwd={r['mesh_fwd_gb']:.1f}GB ring_fwd={r['ring_fwd_gb']:.2f}GB "
        f"(paper: 3.2/0.5)",
    )
    return rows


# ---- Figure 10: GQA sweep -------------------------------------------------------


def bench_fig10_gqa():
    from benchmarks.common import PAPER_HIDDEN, attention_time

    rows = []
    for g in (1, 2, 4, 8):
        kvh = PAPER_HIDDEN // g
        ring = attention_time(128, 1 << 20, a=1, causal=True, kv_hidden=kvh)
        mesh = attention_time(128, 1 << 20, a=None, causal=True, kv_hidden=kvh)
        rows.append(
            {
                "g": g,
                "ring_s": ring["total_s"], "mesh_s": mesh["total_s"],
                "mesh_a": mesh["a"],
                "speedup": ring["total_s"] / mesh["total_s"],
            }
        )
    _save("fig10_gqa", rows)
    _emit(
        "fig10_gqa", 0.0,
        "speedups_g1248=" + "/".join(f"{r['speedup']:.2f}x" for r in rows)
        + " (paper: gains shrink with g)",
    )
    return rows


# ---- Figure 5 / Algorithm 2: schedule quality -----------------------------------


def bench_schedule_quality():
    from benchmarks.common import PAPER_HW
    from repro.core import schedule as S
    from repro.core.am import CommModel
    from repro.core.simulator import make_cost_model, simulate

    comm = CommModel(seq=1 << 20, hidden=4096, n=64)
    cost = make_cost_model(comm, PAPER_HW, causal=True)
    rows = {}
    for name, sched in [
        ("greedy", S.greedy_forward_schedule(8, 8, cost.profile())),
        ("naive_rowfirst", S.naive_forward_schedule(8, 8)),
        ("ring", S.ring_forward_schedule(64)),
        (
            "greedy_concurrent",
            S.greedy_forward_schedule(8, 8, cost.profile(), allow_concurrent_rings=True),
        ),
    ]:
        sim = simulate(sched, cost, comm)
        rows[name] = {
            "total_s": sim.total,
            "exposed_comm_s": sim.exposed_comm,
            "overlap_efficiency": sim.overlap_efficiency,
            "steps": sim.steps,
        }
    _save("fig5_schedule_quality", rows)
    gain = rows["naive_rowfirst"]["total_s"] / rows["greedy"]["total_s"]
    _emit("fig5_schedule_quality", rows["greedy"]["total_s"] * 1e6, f"greedy_vs_naive={gain:.2f}x")
    return rows


# ---- Figure 6: autotuner choices -------------------------------------------------


def bench_fig6_autotune():
    from benchmarks.common import PAPER_HW, TPU_HW
    from repro.core.am import CommModel
    from repro.core.autotune import tune

    rows = []
    t0 = time.perf_counter()
    for hw_name, hw in (("paper", PAPER_HW), ("tpu_v5e", TPU_HW)):
        for n in (16, 64, 256):
            for seq in (1 << 18, 1 << 20):
                plan = tune(CommModel(seq=seq, hidden=4096, n=n), hw, causal=True)
                rows.append({"hw": hw_name, "n": n, "seq": seq, "a": plan.a, "b": plan.b,
                             "total_s": plan.total})
    us = (time.perf_counter() - t0) * 1e6 / len(rows)
    _save("fig6_autotune", rows)
    _emit("fig6_autotune", us, "chosen_a=" + "/".join(str(r["a"]) for r in rows))
    return rows


# ---- assigned architectures: tuned tile per arch -----------------------------------


def bench_arch_tiles():
    """The Fig-6 flow applied to every assigned arch's attention geometry on
    the production model axis (n=16): chosen tile + comm vs Ring-Attention."""
    from repro.configs import ALL_ARCHS, get_config
    from repro.core.am import CommModel

    rows = []
    for arch in ALL_ARCHS:
        cfg = get_config(arch)
        if cfg.attention_free:
            rows.append({"arch": arch, "a": None, "note": "attention-free (SSD)"})
            continue
        comm = CommModel(
            seq=32768, hidden=cfg.num_heads * cfg.hd, n=16,
            kv_hidden=cfg.num_kv_heads * cfg.hd,
        )
        a = comm.best_a()
        rows.append(
            {
                "arch": arch, "a": a, "b": 16 // a,
                "fwd_bytes_gb": comm.fwd_bytes(a) / 1e9,
                "ring_bytes_gb": comm.ring_fwd_bytes() / 1e9,
                "vs_ring": comm.fwd_bytes(a) / comm.ring_fwd_bytes(),
            }
        )
    _save("arch_tiles", rows)
    picks = "/".join(f"{r['arch'].split('-')[0]}:a{r['a']}" for r in rows if r["a"])
    _emit("arch_tiles", 0.0, picks)
    return rows


# ---- measured: mesh-attention wall time on fake devices ---------------------------


def bench_measured_mesh_attention():
    """CPU only: wall time of the distributed op on 8 fake CPU devices, in a
    child process — a smoke-level check that the machinery runs, not a
    device metric.  The parent has imported JAX, so on a TPU host the child
    could not take the chip; it is pinned to the CPU."""
    import subprocess

    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"
    )
    code = r"""
import time, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.compat import make_mesh
from repro.core.dispatch import AttentionPlanConfig, attention_in_shard_map
n=8
mesh = make_mesh((n,), ("sp",))
B,S,H,D = 1, 8*256, 4, 32
q,k,v = (jax.random.normal(kk,(B,S,H,D)) for kk in jax.random.split(jax.random.PRNGKey(0),3))
for a in (1, 2, 4):
    cfg = AttentionPlanConfig(backend="ring" if a == 1 else "mesh", axis_name="sp",
        n=n, a=a, causal=False, block_q=64, block_kv=64)
    f = jax.jit(shard_map(lambda q,k,v: attention_in_shard_map(q,k,v,cfg), mesh=mesh,
        in_specs=(P(None,"sp"),)*3, out_specs=P(None,"sp"), check_vma=False))
    f(q,k,v).block_until_ready()
    t0=time.perf_counter()
    for _ in range(3): o = f(q,k,v)
    o.block_until_ready()
    print(f"a={a}", (time.perf_counter()-t0)/3*1e6)
"""
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env, timeout=600
    )
    if proc.returncode != 0:
        _emit("measured_mesh_attention", 0.0, f"FAILED:{proc.stderr[-200:]}")
        return None
    lines = [l for l in proc.stdout.splitlines() if l.startswith("a=")]
    rows = {l.split()[0]: float(l.split()[1]) for l in lines}
    _save("measured_mesh_attention", rows)
    _emit(
        "measured_mesh_attention", min(rows.values()),
        " ".join(f"{k}:{v:.0f}us" for k, v in rows.items()),
    )
    return rows


# ---- mask pruning: comm volume with/without a document mask ------------------------


def bench_mesh_attention():
    """Segment-masked vs unmasked comm volume on a (2,4) fake-device mesh:
    simulated (event simulator over pruned schedules) AND measured (ppermute
    bytes in the compiled HLO), per commit."""
    from benchmarks.mesh_attention_bench import run_bench

    payload = run_bench()
    _save("mesh_attention_bench", payload)
    sim_red = payload.get("sim_comm_reduction", 0.0)
    meas_red = payload.get("measured_comm_reduction")
    meas = f"{meas_red:.1%}" if meas_red is not None else "n/a"
    _emit(
        "mesh_attention_bench",
        payload.get("measured", {}).get("pruned_wall_us", 0.0),
        f"mask_comm_reduction sim={sim_red:.1%} measured={meas}",
    )
    return payload


# ---- continuous-batching serve throughput/latency ---------------------------------


def bench_serve():
    """Mixed-length arrival trace through the slot-pool engine (reduced
    config): tokens/s + latency percentiles, accumulated per commit."""
    from benchmarks.serve_bench import run_bench

    payload = run_bench("granite-8b", slots=4, requests=8, new_tokens=6)
    _save("serve_bench", payload)
    lat = payload["latency_s"]
    ratio = payload["paged_prefix"]["bytes_per_request_ratio"]
    _emit(
        "serve_bench", payload["wall_s"] / max(payload["ticks"], 1) * 1e6,
        f"tok_per_s={payload['tokens_per_s']:.1f} "
        f"p50={lat['p50']:.3f}s p95={lat['p95']:.3f}s "
        f"paged_bytes_per_req={ratio:.2f}x_dense",
    )
    return payload


# ---- decode kernel: gather vs paged-native --------------------------------------


def bench_decode():
    """One decode tick over a paged KV pool, gather vs the native paged
    kernel, at several depth mixes and pool occupancies: measured tokens/s
    plus modeled HBM bytes/token (depth- vs capacity-proportional)."""
    from benchmarks.decode_bench import run_bench

    payload = run_bench()
    _save("decode_bench", payload)
    half = payload["hbm_bytes_ratio_at_half_occupancy"]
    mesh = payload.get("mesh_engine") or {}
    eq = mesh.get("native_equals_gather_equals_dense")
    rows = payload["op_level"]
    mixed = next(r for r in rows if r["scenario"] == "mixed_depth")
    _emit(
        "decode_bench", mixed["native"]["us_per_tick"],
        f"native_hbm_bytes={half:.2f}x_gather mesh_tokens_eq={eq} "
        f"native_backend={payload['native_backend']}",
    )
    return payload


# ---- roofline table from the dry-run ----------------------------------------------


def bench_roofline_table():
    ddir = os.path.join(RESULTS_DIR, "dryrun")
    if not os.path.isdir(ddir):
        _emit("roofline_table", 0.0, "no-dryrun-results-yet")
        return None
    rows = []
    for fn in sorted(os.listdir(ddir)):
        if not fn.endswith(".json"):
            continue
        with open(os.path.join(ddir, fn)) as f:
            rows.append(json.load(f))
    ok = sum(1 for r in rows if r.get("status") == "ok")
    skip = sum(1 for r in rows if r.get("status") == "skip")
    err = sum(1 for r in rows if r.get("status") == "error")
    _save("roofline_table", rows)
    _emit("roofline_table", 0.0, f"cells ok={ok} skip={skip} error={err}")
    return rows


BENCHES = {
    "table2_comm_volume": bench_table2_comm_volume,
    "table3_throughput": bench_table3_throughput,
    "table4_mfu": bench_table4_mfu,
    "fig8_scaling": bench_fig8_scaling,
    "fig9_breakdown": bench_fig9_breakdown,
    "table5_peak_memory": bench_table5_peak_memory,
    "fig10_gqa": bench_fig10_gqa,
    "fig5_schedule_quality": bench_schedule_quality,
    "fig6_autotune": bench_fig6_autotune,
    "arch_tiles": bench_arch_tiles,
    "measured_mesh_attention": bench_measured_mesh_attention,
    "mesh_attention_bench": bench_mesh_attention,
    "serve_bench": bench_serve,
    "decode_bench": bench_decode,
    "roofline_table": bench_roofline_table,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", default=None)
    args = ap.parse_args()
    names = args.only or list(BENCHES)
    print("name,us_per_call,derived")
    for name in names:
        BENCHES[name]()


if __name__ == "__main__":
    main()
