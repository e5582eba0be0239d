"""Quickstart: Mesh-Attention in 60 seconds.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/quickstart.py

1. builds the 2-D tiled assignment matrix and the greedy schedule (paper
   Algorithms 2/3),
2. runs the distributed op on 8 (fake) devices and checks it against the
   single-device oracle,
3. autotunes the tile shape for a communication-bound cluster.
"""

import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

try:
    import repro  # noqa: F401
except ImportError:  # running from a checkout without `pip install -e .`
    sys.path.insert(
        0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    )

import jax
import jax.numpy as jnp

from repro.compat import make_mesh
from repro.core.am import CommModel, table2
from repro.core.autotune import tune
from repro.core.dispatch import distributed_attention, plan_from_ctx
from repro.core.schedule import greedy_forward_schedule
from repro.core.simulator import HardwareModel
from repro.core.tiling import TileLayout, stripe_permutation, unstripe_permutation
from repro.kernels import ref
from repro.parallel.context import ParallelCtx


def main():
    n, a = 8, 2  # 8 devices, 2x4 tiles

    # --- 1. the assignment matrix & schedule --------------------------------
    lay = TileLayout(n, a)
    print("assignment matrix (AM[q_chunk][kv_chunk] = device):")
    print(lay.assignment_matrix())
    sched = greedy_forward_schedule(a, n // a)
    print(f"\ngreedy forward schedule ({sched.num_steps()} steps):")
    for i, step in enumerate(sched.steps):
        print(f"  step {i}: comm={list(step.comms)} compute={list(step.compute)}")

    # --- 2. distributed vs single-device (via the dispatch seam) ------------
    mesh = make_mesh((n,), ("sp",))
    B, S, H, D = 2, n * 32, 4, 16
    q, k, v = (
        jax.random.normal(kk, (B, S, H, D))
        for kk in jax.random.split(jax.random.PRNGKey(0), 3)
    )
    ctx = ParallelCtx(mesh=mesh, sp_axis="sp", mesh_a=a, block_q=32, block_kv=32)
    cfg = plan_from_ctx(ctx, causal=True)  # backend + tile as config
    f = jax.jit(lambda q, k, v: distributed_attention(q, k, v, cfg=cfg, ctx=ctx))
    perm = stripe_permutation(S, n)
    inv = unstripe_permutation(S, n)
    o = f(q[:, perm], k[:, perm], v[:, perm])[:, inv]
    o_ref, _ = ref.attention_ref(q, k, v, band=ref.causal_band())
    err = float(jnp.max(jnp.abs(o - o_ref)))
    print(f"\ndistributed vs oracle max |err| = {err:.2e}")
    assert err < 2e-5

    # --- 3. tile-shape autotuning (paper Figure 6) --------------------------
    hw = HardwareModel(peak_flops=989e12, link_bw=25e9, attn_efficiency=0.35)
    for nn in (64, 256):
        plan = tune(CommModel(seq=1 << 20, hidden=4096, n=nn), hw, causal=True)
        ring = table2(nn)["ring"]
        mesh_v = table2(nn)["mesh"]
        print(
            f"n={nn:4d}: best tile a x b = {plan.a} x {plan.b}, "
            f"simulated fwd+bwd {plan.total*1e3:.1f} ms, "
            f"theoretical comm {mesh_v:.3f} Nd vs ring {ring:.3f} Nd"
        )
    print("\nquickstart OK")


if __name__ == "__main__":
    main()
