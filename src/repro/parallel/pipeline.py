"""GPipe-style pipeline parallelism over a ``pipe`` mesh axis.

The production dry-run mesh is DP x SP/TP (the paper's focus is
attention-level parallelism), but at >512-node scale depth must also shard.
This module provides a static fill-drain (GPipe) schedule as a composable
primitive:

  * the layer stack [L, ...] is reshaped to [n_stages, L/n_stages, ...] and
    sharded over "pipe" (each stage holds its contiguous layer slice),
  * the batch is split into M microbatches; activations flow stage->stage
    through ``ppermute`` once per tick; the loop runs M + n_stages - 1 ticks
    (bubble fraction = (S-1)/(M+S-1)),
  * everything is differentiable by plain autodiff (JAX transposes the
    ppermutes), so ``jax.grad`` through ``pipeline_apply`` trains.

The schedule is lock-step and static — every stage computes every tick
(garbage in the bubbles is masked at the edges), which is the standard
SPMD-friendly formulation.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P


__all__ = ["pipeline_apply", "pipeline_stages"]


def pipeline_stages(stacked_params, n_stages: int):
    """[L, ...] pytree -> [n_stages, L/n_stages, ...] (shard dim 0 on 'pipe')."""

    def f(x):
        L = x.shape[0]
        if L % n_stages:
            raise ValueError(f"{L} layers not divisible by {n_stages} stages")
        return x.reshape(n_stages, L // n_stages, *x.shape[1:])

    return jax.tree.map(f, stacked_params)


def _stage_perm(n_stages: int):
    return [(s, s + 1) for s in range(n_stages - 1)]


def pipeline_apply(
    layer_fn: Callable,  # (layer_params, x) -> x  (one layer)
    staged_params,  # pytree with leading [n_stages, L/S, ...] dims
    x: jnp.ndarray,  # [M, mb, ...] microbatched input (replicated over pipe)
    *,
    mesh,
    n_stages: int,
    axis: str = "pipe",
    extra_specs=P(),
) -> jnp.ndarray:
    """Run the microbatches through the pipeline; returns [M, mb, ...]
    outputs (replicated over the pipe axis for downstream use)."""
    M = x.shape[0]
    perm = _stage_perm(n_stages)

    def stage_fn(params_slice, x_in):
        def body(h, lp):
            return layer_fn(lp, h), None

        h, _ = lax.scan(body, x_in, params_slice)
        return h

    def inner(staged, xs):
        i = lax.axis_index(axis)
        my_params = jax.tree.map(lambda p: p[0], staged)  # [1, L/S, ...] -> [L/S, ...]
        buf = jnp.zeros_like(xs[0])
        n_ticks = M + n_stages - 1
        outs = jnp.zeros((M,) + xs.shape[1:], xs.dtype)

        def tick(carry, t):
            buf, outs = carry
            mb_idx = jnp.clip(t, 0, M - 1)
            inject = xs[mb_idx]
            x_in = jnp.where((i == 0) & (t < M), inject, buf)
            y = stage_fn(my_params, x_in)
            # stage s produced microbatch (t - s); valid on the LAST stage
            # when 0 <= t - (S-1) < M
            out_idx = jnp.clip(t - (n_stages - 1), 0, M - 1)
            is_valid = (i == n_stages - 1) & (t >= n_stages - 1)
            upd = jnp.where(is_valid, y, outs[out_idx])
            outs = lax.dynamic_update_index_in_dim(outs, upd, out_idx, 0)
            buf = lax.ppermute(y, axis, perm) if n_stages > 1 else y
            return (buf, outs), None

        (buf, outs), _ = lax.scan(tick, (buf, outs), jnp.arange(n_ticks))
        # broadcast the last stage's collected outputs to every stage
        stage_hot = (i == n_stages - 1).astype(outs.dtype)
        outs = lax.psum(outs * stage_hot, axis)
        return outs

    f = shard_map(
        inner,
        mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(axis), staged_params), extra_specs),
        out_specs=P(),
        check_vma=False,
    )
    return f(staged_params, x)
