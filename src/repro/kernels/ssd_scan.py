"""Pallas TPU kernel for the Mamba-2 SSD chunked scan (arXiv:2405.21060).

TARGET: TPU v5e.  Validated on CPU with interpret=True against
``ref.ssd_ref`` (sequential recurrence oracle) and the jnp chunked dual form.

TPU-native structure:
  * grid = (batch, heads, chunks); the chunk axis is sequential
    ("arbitrary"), carrying the [P, N] recurrence state in VMEM scratch —
    the cross-chunk linear recurrence never touches HBM.
  * within a chunk the dual quadratic form runs on the MXU:
    L ⊙ (C·Bᵀ) matmuls with the decay matrix built from a cumulative-sum
    expressed as a lower-triangular ones-matmul (MXU-friendly, no serial
    scan inside the kernel).
  * chunk length and head dims default to 64/128 lanes (hardware-aligned).

The kernel is forward-only; the model's SSM layers use the autodiff-able jnp
dual form in models/ssm.py and do not call it yet.  tests/test_tpu_compile.py
compiles it for a v5e.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

__all__ = ["ssd_scan_fwd"]


def _ssd_kernel(
    A_ref,  # [H] f32 in SMEM
    x_ref,  # [1, 1, c, P]
    dt_ref,  # [1, 1, c, 1]
    b_ref,  # [1, 1, c, N]
    c_ref,  # [1, 1, c, N]
    y_ref,  # [1, 1, c, P] out
    state_ref,  # [1, 1, P, N] out (final state)
    h_ref,  # scratch [P, N] f32
    *,
    nz: int,
):
    z = pl.program_id(2)
    head = pl.program_id(1)

    @pl.when(z == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, 0].astype(jnp.float32)  # [c, P]
    dt = dt_ref[0, 0][:, 0].astype(jnp.float32)  # [c]
    Bm = b_ref[0, 0].astype(jnp.float32)  # [c, N]
    Cm = c_ref[0, 0].astype(jnp.float32)  # [c, N]
    A = A_ref[head]
    c = x.shape[0]

    a = (dt * A)[:, None]  # [c, 1], negative
    # inclusive cumulative sum as a lower-triangular ones matmul (MXU)
    tril = jnp.tril(jnp.ones((c, c), jnp.float32))
    acum = jax.lax.dot(tril, a, preferred_element_type=jnp.float32)  # [c,1]

    Lmat = jnp.exp(acum - acum[:, 0][None, :]) * tril  # [c, c] decay, masked
    scores = jax.lax.dot_general(
        Cm, Bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [c, c]
    y = jax.lax.dot(
        (Lmat * scores) * dt[None, :], x, preferred_element_type=jnp.float32
    )  # [c, P] intra-chunk
    h = h_ref[...]
    y += jnp.exp(acum) * jax.lax.dot_general(
        Cm, h, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # inter-chunk: exp(acum) * C @ h^T -> [c, P]

    total = jnp.exp(acum[c - 1, 0])
    decay_end = jnp.exp(acum[c - 1, 0] - acum[:, 0])  # [c]
    h_new = total * h + jax.lax.dot_general(
        x * (decay_end * dt)[:, None], Bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [P, N]
    h_ref[...] = h_new
    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(z == nz - 1)
    def _final():
        state_ref[0, 0] = h_new.astype(state_ref.dtype)


def ssd_scan_fwd(
    x: jnp.ndarray,  # [B, S, H, P]
    dt: jnp.ndarray,  # [B, S, H] (softplus already applied)
    A: jnp.ndarray,  # [H] (negative)
    Bm: jnp.ndarray,  # [B, S, G, N]
    Cm: jnp.ndarray,  # [B, S, G, N]
    *,
    chunk: int = 64,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """-> (y [B,S,H,P], final_state [B,H,P,N]).  ``interpret=None``
    follows the platform (see ``resolve_interpret``)."""
    interpret = resolve_interpret(interpret)
    B, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    chunk = min(chunk, S)
    if S % chunk:
        raise ValueError(f"S={S} not divisible by chunk={chunk}")
    nz = S // chunk
    group = H // G

    xt = x.transpose(0, 2, 1, 3)  # [B, H, S, P]
    # a column [B, H, S, 1]: a (chunk, 1) block meets the TPU tiling rule,
    # where a (1, 1, chunk) block of [B, H, S] would not
    dtt = dt.transpose(0, 2, 1)[..., None]
    bt = Bm.transpose(0, 2, 1, 3)  # [B, G, S, N]
    ct = Cm.transpose(0, 2, 1, 3)

    kernel = functools.partial(_ssd_kernel, nz=nz)
    y, state = pl.pallas_call(
        kernel,
        grid=(B, H, nz),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, z: (b, h, z, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b, h, z: (b, h, z, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, z, g=group: (b, h // g, z, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, z, g=group: (b, h // g, z, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, z: (b, h, z, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, z: (b, h, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=None
        if interpret
        else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        name="ssd_scan_fwd",
    )(A.astype(jnp.float32), xt, dtt, bt, ct)
    return y.transpose(0, 2, 1, 3), state
