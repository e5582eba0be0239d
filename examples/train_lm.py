"""End-to-end training driver.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/train_lm.py --preset tiny --steps 50

Presets:
  tiny   — ~1M params, finishes on this CPU container in ~a minute
  100m   — ~100M-param llama-style model (the assignment's end-to-end size;
           run on real hardware or be patient)
  arch   — any assigned architecture's reduced config: --preset arch --arch ID

Demonstrates the full substrate: Mesh-Attention context parallelism over the
model axis, FSDP param sharding, AdamW, deterministic data, checkpointing
(resume with the same command), and the straggler monitor.
"""

import argparse
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax

from repro.compat import make_mesh
from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.optim.adamw import AdamWConfig
from repro.parallel.context import ParallelCtx
from repro.train.loop import TrainConfig, fit

PRESETS = {
    "tiny": ModelConfig(
        name="tiny", family="dense", num_layers=2, d_model=128, num_heads=4,
        num_kv_heads=2, d_ff=256, vocab_size=512,
    ),
    "100m": ModelConfig(
        name="llama-100m", family="dense", num_layers=12, d_model=768,
        num_heads=12, num_kv_heads=12, d_ff=2048, vocab_size=32000,
    ),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="tiny", choices=["tiny", "100m", "arch"])
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    ap.add_argument("--single-device", action="store_true")
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced() if args.preset == "arch" else PRESETS[args.preset]

    if args.single_device or jax.device_count() < 8:
        ctx = ParallelCtx()
    else:
        mesh = make_mesh((2, 4), ("data", "model"))
        ctx = ParallelCtx(mesh=mesh, batch_axes=("data",), sp_axis="model",
                          block_q=16, block_kv=16)
    print(f"devices={jax.device_count()} mesh={'none' if ctx.mesh is None else dict(ctx.mesh.shape)}")

    tcfg = TrainConfig(steps=args.steps, seq=args.seq, batch=args.batch,
                       ckpt_dir=args.ckpt_dir, ckpt_every=20)
    out = fit(cfg, ctx, tcfg, AdamWConfig(lr=3e-3, total_steps=args.steps, warmup_steps=10),
              hooks={"on_step": lambda s, m: (s % 10 == 0) and print(
                  f"step {s}: loss {float(m['loss']):.4f} gnorm {float(m['grad_norm']):.2f}")})
    hist = out["history"]
    print(f"\nloss {hist[0]:.4f} -> {hist[-1]:.4f} over {len(hist)} steps"
          f" (resumed from checkpoint)" if out["step"] != len(hist) else "")
    assert hist[-1] < hist[0], "training did not reduce loss"
    print("train_lm OK")


if __name__ == "__main__":
    main()
