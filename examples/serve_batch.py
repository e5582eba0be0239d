"""Batched serving over the distributed striped KV cache.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/serve_batch.py [--arch minicpm3-4b]

Prefills a batch of prompts with Mesh-Attention (the striped prefill chunks
land directly in the decode cache — the paper's locality property carried
into serving), then decodes greedily with per-token lse-combined partial
attention.  Verifies distributed generation equals single-device.
"""

import argparse
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import numpy as np

from repro.compat import make_mesh
from repro.configs import get_config
from repro.models import transformer as tfm
from repro.parallel.context import ParallelCtx
from repro.serve.engine import ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=8)
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced()
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len), dtype=np.int32)

    single = ServeEngine(cfg, params, max_seq=128)
    out_single = single.generate(prompts, max_new_tokens=args.new_tokens)

    if jax.device_count() >= 8:
        mesh = make_mesh((2, 4), ("data", "model"))
        ctx = ParallelCtx(mesh=mesh, batch_axes=("data",), sp_axis="model",
                          block_q=8, block_kv=8)
        dist = ServeEngine(cfg, params, ctx=ctx, max_seq=128)
        out_dist = dist.generate(prompts, max_new_tokens=args.new_tokens)
        assert (out_single == out_dist).all(), "distributed != single-device"
        print(f"distributed == single-device across {jax.device_count()} devices")

    for i, row in enumerate(out_single):
        print(f"request {i}: prompt {prompts[i][:6].tolist()}... -> {row.tolist()}")
    print("serve_batch OK")


if __name__ == "__main__":
    main()
