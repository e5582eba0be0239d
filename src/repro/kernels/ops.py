"""Jit'd public wrappers around the Pallas kernels with ref.py fallbacks.

Backend policy (``REPRO_KERNELS`` env var or ``set_backend()``):
  * "auto"   (default): compiled Pallas on TPU, pure-jnp ref elsewhere —
             the CPU container validates kernels with interpret=True in
             tests, but models/benchmarks run the fast XLA reference.
  * "pallas" : Pallas with interpret=True off-TPU (slow; correctness runs).
  * "ref"    : always the jnp oracle.
``attention_backend()`` names what the policy resolved to on this platform
(``pallas`` = compiled, ``pallas-interpret`` or ``ref``), so a run can
record it and a chip run can refuse anything but compiled kernels.

Two API layers:
  * ``flash_attention``  — differentiable (custom_vjp pairing the fwd kernel
    with the dq/dkv kernels); band must be static Python ints.  This is what
    the single-device model code uses.
  * ``block_attention`` / ``block_attention_bwd`` — non-differentiable
    building blocks taking a *dynamic* int32[4] band (offsets may come from
    ``jax.lax.axis_index`` inside shard_map).  ``core/mesh_attention.py``
    assembles the paper's distributed forward/backward out of these, defining
    its own custom_vjp at the distributed-op level (Algorithms 2/3).
"""

from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as fa
from repro.kernels import ref, resolve_interpret

Band = ref.Band

_BACKEND = os.environ.get("REPRO_KERNELS", "auto")


def set_backend(name: str) -> None:
    global _BACKEND
    if name not in ("auto", "pallas", "ref"):
        raise ValueError(name)
    _BACKEND = name


def current_backend() -> str:
    return _BACKEND


def pallas_enabled() -> bool:
    """Does the current backend policy run Pallas kernels (compiled on TPU,
    or interpret-mode under REPRO_KERNELS=pallas)?  "auto" off-TPU runs the
    fast XLA reference instead — perf-default code paths key off this."""
    if _BACKEND == "ref":
        return False
    return _BACKEND == "pallas" or jax.default_backend() == "tpu"


def attention_backend() -> str:
    """What attention runs as here: "pallas" (compiled), "pallas-interpret"
    or "ref"."""
    if not pallas_enabled():
        return "ref"
    return "pallas-interpret" if resolve_interpret() else "pallas"


def full_band() -> Tuple[int, int, int, int]:
    return (0, 0, -ref.BAND_INF, ref.BAND_INF)


def block_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    band,  # int32[4] array or 4-tuple (entries may be traced)
    *,
    scale: Optional[float] = None,
    stride_q: int = 1,
    stride_kv: int = 1,
    block_q: int = fa.DEFAULT_BLOCK_Q,
    block_kv: int = fa.DEFAULT_BLOCK_KV,
    seg_q: Optional[jnp.ndarray] = None,  # [Sq] int32 segment ids (documents)
    seg_kv: Optional[jnp.ndarray] = None,  # [Skv]
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One AM-block attention: (o, lse); no autodiff rule (see module doc)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    band = jnp.asarray(band, jnp.int32)
    if pallas_enabled():
        return fa.flash_attention_fwd(
            q, k, v, band,
            scale=scale, stride_q=stride_q, stride_kv=stride_kv,
            block_q=block_q, block_kv=block_kv,
            seg_q=seg_q, seg_kv=seg_kv,
        )
    return ref.attention_ref(
        q, k, v, scale=scale, band=tuple(band), stride_q=stride_q, stride_kv=stride_kv,
        seg_q=seg_q, seg_kv=seg_kv,
    )


def block_attention_bwd(
    q, k, v, o, lse, do, band,
    *,
    scale: Optional[float] = None,
    stride_q: int = 1,
    stride_kv: int = 1,
    block_q: int = fa.DEFAULT_BLOCK_Q,
    block_kv: int = fa.DEFAULT_BLOCK_KV,
    delta: Optional[jnp.ndarray] = None,
    seg_q: Optional[jnp.ndarray] = None,
    seg_kv: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One AM-block backward from saved (o, lse): (dq, dk, dv).

    Either ``o`` or ``delta`` (= rowsum(do*o), [B,Sq,H]) must be given.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    band = jnp.asarray(band, jnp.int32)
    if pallas_enabled():
        return fa.flash_attention_bwd(
            q, k, v, o, lse, do, band,
            scale=scale, stride_q=stride_q, stride_kv=stride_kv,
            block_q=block_q, block_kv=block_kv, delta=delta,
            seg_q=seg_q, seg_kv=seg_kv,
        )
    return ref.attention_bwd_ref(
        q, k, v, o, lse, do,
        scale=scale, band=tuple(band), stride_q=stride_q, stride_kv=stride_kv,
        delta=delta, seg_q=seg_q, seg_kv=seg_kv,
    )


# --------------------------------------------------------------------------
# differentiable single-device attention (static band)
# --------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, band, scale, stride_q, stride_kv):
    o, _ = block_attention(
        q, k, v, band, scale=scale, stride_q=stride_q, stride_kv=stride_kv
    )
    return o


def _flash_fwd(q, k, v, band, scale, stride_q, stride_kv):
    o, lse = block_attention(
        q, k, v, band, scale=scale, stride_q=stride_q, stride_kv=stride_kv
    )
    return o, (q, k, v, o, lse)


def _flash_bwd(band, scale, stride_q, stride_kv, res, do):
    q, k, v, o, lse = res
    dq, dk, dv = block_attention_bwd(
        q, k, v, o, lse, do, band,
        scale=scale, stride_q=stride_q, stride_kv=stride_kv,
    )
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


# segment-masked variant: the int32 seg operands are data (packed documents),
# so they ride as traced args with a None cotangent
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash_seg(q, k, v, seg_q, seg_kv, band, scale, stride_q, stride_kv):
    o, _ = block_attention(
        q, k, v, band, scale=scale, stride_q=stride_q, stride_kv=stride_kv,
        seg_q=seg_q, seg_kv=seg_kv,
    )
    return o


def _flash_seg_fwd(q, k, v, seg_q, seg_kv, band, scale, stride_q, stride_kv):
    o, lse = block_attention(
        q, k, v, band, scale=scale, stride_q=stride_q, stride_kv=stride_kv,
        seg_q=seg_q, seg_kv=seg_kv,
    )
    return o, (q, k, v, seg_q, seg_kv, o, lse)


def _flash_seg_bwd(band, scale, stride_q, stride_kv, res, do):
    q, k, v, seg_q, seg_kv, o, lse = res
    dq, dk, dv = block_attention_bwd(
        q, k, v, o, lse, do, band,
        scale=scale, stride_q=stride_q, stride_kv=stride_kv,
        seg_q=seg_q, seg_kv=seg_kv,
    )
    return dq, dk, dv, None, None


_flash_seg.defvjp(_flash_seg_fwd, _flash_seg_bwd)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = False,
    window: Optional[int] = None,
    band: Optional[Tuple[int, int, int, int]] = None,
    scale: Optional[float] = None,
    stride_q: int = 1,
    stride_kv: int = 1,
    seg_q: Optional[jnp.ndarray] = None,  # [Sq] int32 segment ids
    seg_kv: Optional[jnp.ndarray] = None,  # [Skv]
) -> jnp.ndarray:
    """Differentiable attention; the band is static (causal/window/custom),
    optionally composed with runtime segment ids (packed documents)."""
    if band is None:
        if causal:
            hi = (window - 1) if window else ref.BAND_INF
            band = (0, 0, 0, hi)
        elif window:
            band = (0, 0, -(window - 1), window - 1)
        else:
            band = full_band()
    if scale is None:
        scale = q.shape[-1] ** -0.5
    band = tuple(int(x) for x in band)
    if seg_q is not None:
        if seg_kv is None:
            seg_kv = seg_q
        return _flash_seg(
            q, k, v, jnp.asarray(seg_q, jnp.int32), jnp.asarray(seg_kv, jnp.int32),
            band, float(scale), stride_q, stride_kv,
        )
    return _flash(q, k, v, band, float(scale), stride_q, stride_kv)


combine_partials = ref.combine_partials
