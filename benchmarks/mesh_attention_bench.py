"""Mesh-Attention comm-volume benchmark: mask pruning, simulated + measured.

    PYTHONPATH=src python -m benchmarks.mesh_attention_bench [--json-out PATH]

CPU only: the measured part runs in a child process pinned to
``JAX_PLATFORMS=cpu`` with 8 fake devices (its parent has imported JAX, so
on a TPU host the child could not take the chip).  Its wall times are CPU
times, never device metrics.

Runs a segment-masked (packed two-document) workload against the unmasked
causal baseline on a (2, 4) fake-device mesh and reports, per commit:

  * simulated per-device comm bytes (event simulator over the pruned vs
    unpruned greedy schedules),
  * MEASURED per-device collective-permute bytes parsed from the compiled
    HLO (``launch/hlo_analysis.collective_bytes``) — the wire truth,
  * measured wall time per call on the fake-device CPU mesh (smoke-level),
  * packed-output-vs-dense-oracle max abs error,
  * an ``overlap`` section comparing the serial | overlap | bidir transports:
    best-of-5 wall time, measured ppermute bytes (asserted IDENTICAL across
    modes — overlapping must never change wire volume), raw vs logical
    ppermute step counts (a bidir half-payload pair is one logical hop), and
    the simulator's per-mode total/exposed-comm estimates.

JSON lands in ``benchmarks/results/mesh_attention_bench.json`` and CI uploads
it as ``BENCH_mesh_attention_<sha>.json`` (same convention as serve_bench),
so the comm-volume trajectory accumulates per commit.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

try:
    import repro  # noqa: F401
except ImportError:  # running from a checkout without `pip install -e .`
    sys.path.insert(
        0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    )

RESULTS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "results")

_MEASURE_CODE = r"""
import json, time
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.compat import make_mesh
from repro.core.masking import MaskSpec
from repro.core.mesh_attention import MeshAttentionConfig, mesh_attention
from repro.core import schedule as Sch
from repro.kernels import ref
from repro.launch.hlo_analysis import collective_bytes
import dataclasses

n = 4
mesh = make_mesh((2, 4), ("data", "sp"))
B, S, H, Hkv, D = 2, 512, 4, 2, 32
kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
q = jax.random.normal(kq, (B, S, H, D))
k = jax.random.normal(kk, (B, S, Hkv, D))
v = jax.random.normal(kv, (B, S, Hkv, D))
doc_lens = (S // 2, S // 2)
spec = MaskSpec.document(doc_lens)
seg = jnp.asarray(spec.segment_array(S))

cfg = MeshAttentionConfig(axis_name="sp", n=n, a=2, mask=spec,
                          layout="contiguous", block_q=64, block_kv=64)
cfg_un = dataclasses.replace(
    cfg,
    fwd_schedule=Sch.greedy_forward_schedule(cfg.a, cfg.b),
    bwd_schedule=Sch.greedy_backward_schedule(cfg.a, cfg.b),
)

def build(c):
    return jax.jit(shard_map(
        lambda q, k, v, s: mesh_attention(q, k, v, c, seg=s),
        mesh=mesh, in_specs=(P("data", "sp"),) * 3 + (P("sp"),),
        out_specs=P("data", "sp"), check_vma=False,
    ))

out = {}
for name, c in (("pruned", cfg), ("unpruned", cfg_un)):
    f = build(c)
    hlo = f.lower(q, k, v, seg).compile().as_text()
    out[name + "_ppermute_bytes"] = collective_bytes(hlo)["collective-permute"]
    o = f(q, k, v, seg)
    o.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(3):
        o = f(q, k, v, seg)
    o.block_until_ready()
    out[name + "_wall_us"] = (time.perf_counter() - t0) / 3 * 1e6
    out[name + "_out"] = np.asarray(o)

o_ref, _ = ref.attention_ref(q, k, v, band=ref.causal_band(), seg_q=seg, seg_kv=seg)
out["packed_vs_oracle_err"] = float(jnp.max(jnp.abs(out["pruned_out"] - o_ref)))
out["pruned_bitwise_eq_unpruned"] = bool(
    (out["pruned_out"] == out["unpruned_out"]).all()
)
del out["pruned_out"], out["unpruned_out"]

# comm-overlap transport comparison on the same pruned workload: the three
# modes must move IDENTICAL ppermute byte volume (bidir just splits each hop
# into a half-payload pair) and produce bitwise-identical outputs; wall time
# is best-of-5 to keep the fake-device CPU measurement stable.
ov = {}
serial_out = None
for mode in Sch.COMM_OVERLAP_MODES:
    f = build(dataclasses.replace(cfg, comm_overlap=mode))
    hlo = f.lower(q, k, v, seg).compile().as_text()
    cb = collective_bytes(hlo)
    o = f(q, k, v, seg)
    o.block_until_ready()
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        o = f(q, k, v, seg)
        o.block_until_ready()
        best = min(best, time.perf_counter() - t0)
    o = np.asarray(o)
    if mode == "serial":
        serial_out = o
    else:
        assert (o == serial_out).all(), mode + " output != serial bitwise"
    ov[mode] = {
        "ppermute_bytes": cb["collective-permute"],
        "ppermute_ops": int(cb["collective-permute-count"]),
        "wall_us": best * 1e6,
    }
for mode in ("overlap", "bidir"):
    assert ov[mode]["ppermute_bytes"] == ov["serial"]["ppermute_bytes"], (
        mode, ov[mode]["ppermute_bytes"], ov["serial"]["ppermute_bytes"])
out["overlap"] = ov
print("RESULT " + json.dumps(out))
"""


def run_bench():
    from repro.core import schedule as Sch
    from repro.core.am import CommModel, ppermute_pair_factor
    from repro.core.autotune import plan_for
    from repro.core.masking import MaskSpec

    n, a, S = 4, 2, 512
    comm = CommModel(seq=S, hidden=4 * 32, n=n, kv_hidden=2 * 32,
                     bytes_per_elem=4, batch=2)
    mask = MaskSpec.document((S // 2, S // 2))
    sim_masked = plan_for(comm, a, mask=mask, layout="contiguous")
    sim_unmasked = plan_for(comm, a, causal=True, layout="contiguous")

    payload = {
        "mesh": [2, 4],
        "n": n,
        "a": a,
        "seq": S,
        "doc_lens": [S // 2, S // 2],
        "sim_comm_bytes_masked": sim_masked.comm_bytes,
        "sim_comm_bytes_unmasked": sim_unmasked.comm_bytes,
        "sim_comm_reduction": 1.0 - sim_masked.comm_bytes / max(sim_unmasked.comm_bytes, 1),
        "fwd_comms_masked": sim_masked.fwd.comm_ops(),
        "fwd_comms_unmasked": sim_unmasked.fwd.comm_ops(),
    }

    # simulated step cost per comm_overlap transport (same pruned workload):
    # serial fully exposes every transfer; overlap hides what compute covers;
    # bidir additionally moves each hop at per-direction bandwidth
    payload["sim_overlap"] = {
        mode: {
            "total_s": p.total,
            "exposed_comm_s": (p.fwd_sim.exposed_comm
                               + (p.bwd_sim.exposed_comm if p.bwd_sim else 0.0)),
            "comm_bytes": p.comm_bytes,
            "ppermute_pair_factor": ppermute_pair_factor(mode),
        }
        for mode, p in (
            (m, plan_for(comm, a, mask=mask, layout="contiguous", comm_overlap=m))
            for m in Sch.COMM_OVERLAP_MODES
        )
    }

    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"
    )
    proc = subprocess.run(
        [sys.executable, "-c", _MEASURE_CODE],
        capture_output=True, text=True, env=env, timeout=900,
    )
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT ")]
    if proc.returncode != 0 or not lines:
        payload["measured_error"] = proc.stderr[-500:]
        return payload
    measured = json.loads(lines[-1][len("RESULT "):])
    payload["measured"] = measured
    m, u = measured["pruned_ppermute_bytes"], measured["unpruned_ppermute_bytes"]
    payload["measured_comm_reduction"] = 1.0 - m / max(u, 1)
    ov = measured.get("overlap")
    if ov:
        from repro.core.am import logical_ppermute_steps

        # hard gate (bench smoke): overlapping may NOT change wire volume
        for mode in ("overlap", "bidir"):
            assert ov[mode]["ppermute_bytes"] == ov["serial"]["ppermute_bytes"], (
                f"{mode} moved different ppermute bytes than serial: {ov}"
            )
        for mode, rec in ov.items():
            rec["logical_steps"] = logical_ppermute_steps(rec["ppermute_ops"], mode)
        assert ov["bidir"]["logical_steps"] == ov["serial"]["logical_steps"], ov
        payload["measured_overlap_speedup"] = (
            ov["serial"]["wall_us"] / max(ov["overlap"]["wall_us"], 1e-9)
        )
    return payload


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--json-out", default=os.path.join(RESULTS_DIR, "mesh_attention_bench.json")
    )
    args = ap.parse_args(argv)
    payload = run_bench()
    os.makedirs(os.path.dirname(args.json_out), exist_ok=True)
    with open(args.json_out, "w") as f:
        json.dump(payload, f, indent=1)
    print(json.dumps({k: payload[k] for k in payload if not isinstance(payload[k], dict)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
