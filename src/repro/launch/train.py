"""Training entry point.

    PYTHONPATH=src python -m repro.launch.train --arch granite-8b \
        [--reduced] [--steps 100] [--seq 128] [--batch 8] \
        [--ckpt-dir DIR] [--compress] [--multi-pod]

The mesh comes from ``launch.mesh.launch_context``: the sequence axis spans
every device below a pod (4-way on a 2x2 v5e host), the production mesh from
256 chips.  On a CPU, --fake-devices N emulates N devices (sets XLA_FLAGS
before jax is imported).
"""

import argparse
import os
import sys


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true", help="family-preserving small config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--compress", action="store_true", help="int8+EF cross-pod grad compression")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--fake-devices", type=int, default=0)
    ap.add_argument("--tile-a", type=int, default=None)
    ap.add_argument("--attn", default="mesh", choices=["mesh", "ring", "ulysses"])
    ap.add_argument("--docs", type=int, default=None,
                    help="pack N documents per row (segment-masked attention)")
    args = ap.parse_args()

    if args.fake_devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.fake_devices} "
            + os.environ.get("XLA_FLAGS", "")
        )

    import jax

    from repro.configs import get_config
    from repro.kernels import ops
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import launch_context
    from repro.optim.adamw import AdamWConfig
    from repro.parallel.compression import CompressionConfig
    from repro.train.loop import TrainConfig, fit

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()

    n = jax.device_count()
    enable_compile_cache()
    ctx = launch_context(n, multi_pod=args.multi_pod, mesh_a=args.tile_a,
                         attn_impl=args.attn)
    print(f"devices={n} mesh={'none' if ctx.mesh is None else dict(ctx.mesh.shape)} "
          f"attention={ops.attention_backend()}")

    tcfg = TrainConfig(
        steps=args.steps, seq=args.seq, batch=args.batch,
        ckpt_dir=args.ckpt_dir,
        compression=CompressionConfig(kind="int8") if args.compress else None,
        docs=args.docs,
    )
    out = fit(cfg, ctx, tcfg, AdamWConfig(total_steps=args.steps),
              hooks={"on_step": lambda s, m: (s % 10 == 0) and print(
                  f"step {s}: loss {float(m['loss']):.4f}")})
    print(f"done: step={out['step']} final_loss={out.get('final_loss')}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
