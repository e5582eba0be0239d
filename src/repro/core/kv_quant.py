"""Quantized KV-pool storage: int8 / fp8-e4m3 pages with fp32 scale tables.

The paged pool stores K/V pages in a narrow dtype and keeps symmetric
scales in a side table that shares the pool's physical-page indexing
(``[L, num_pages, chunk, Hkv]`` next to pages ``[L, num_pages, chunk, Hkv,
D]``).  Scales are **per token per kv-head** within a page — amax over the
head dim only — so incremental appends (decode, chunked prefill,
speculative verify) never re-quantize previously written positions: each
position's ``(q, scale)`` pair is written exactly once and is final.  This
is the refinement of "per-page scales" that keeps the write paths
read-modify-write-free; the scale tile still rides the block table's page
indexing, so CoW/rollback/prefix-sharing move scales in lockstep with
pages.

Error model (documented bound, asserted in tests and dist_check
``quant_kv``):

- ``int8``: ``scale = amax / 127``, round-to-nearest →
  ``|x - deq(q)| <= scale/2 = amax/254`` per element, i.e. relative error
  ``<= 1/254`` of the per-(token, head) amax.
- ``fp8`` (e4m3fn, 3 mantissa bits): ``scale = amax / 448`` maps amax to
  the format's max normal; relative error ``<= 2**-4`` (half ulp).

``fp8`` is gated on the runtime exposing ``jnp.float8_e4m3fn``
(``fp8_supported()``); ``ServeConfig`` validation rejects it otherwise.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp

__all__ = [
    "KV_DTYPES",
    "QUANT_KV_DTYPES",
    "REL_ERROR_BOUND",
    "fp8_dtype",
    "fp8_supported",
    "storage_dtype",
    "storage_itemsize",
    "quantize",
    "dequantize",
    "kv_dtype_of",
    "store",
]

KV_DTYPES = ("fp", "int8", "fp8")
QUANT_KV_DTYPES = ("int8", "fp8")

# Max representable magnitude the amax is mapped onto.
_QMAX = {"int8": 127.0, "fp8": 448.0}

# Elementwise |x - dequant(quant(x))| <= REL_ERROR_BOUND * amax(token, head).
REL_ERROR_BOUND = {"fp": 0.0, "int8": 1.0 / 254.0, "fp8": 2.0 ** -4}

SCALE_DTYPE = jnp.float32


def fp8_dtype():
    """The fp8-e4m3 storage dtype, or None when this jax doesn't have it."""
    return getattr(jnp, "float8_e4m3fn", None)


def fp8_supported() -> bool:
    return fp8_dtype() is not None


def storage_dtype(kv_dtype: str, fp_dtype=jnp.float32):
    """Pool element dtype for a ``kv_dtype`` knob value."""
    if kv_dtype == "fp":
        return jnp.dtype(fp_dtype)
    if kv_dtype == "int8":
        return jnp.dtype(jnp.int8)
    if kv_dtype == "fp8":
        dt = fp8_dtype()
        if dt is None:
            raise ValueError("kv_dtype='fp8' requires jnp.float8_e4m3fn")
        return jnp.dtype(dt)
    raise ValueError(f"kv_dtype must be one of {KV_DTYPES}, got {kv_dtype!r}")


def storage_itemsize(kv_dtype: str, fp_dtype=jnp.float32) -> int:
    return storage_dtype(kv_dtype, fp_dtype).itemsize


def quantize(x: jnp.ndarray, kv_dtype: str) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric quantization over the last (head-dim) axis.

    Returns ``(q, scale)`` with ``q.shape == x.shape`` in the storage dtype
    and ``scale.shape == x.shape[:-1]`` in fp32.  ``dequantize(q, scale)``
    reconstructs within ``REL_ERROR_BOUND[kv_dtype] * amax``.  Zero rows
    get scale 0 (and quantize to 0), so zero-initialized pool positions and
    their zero-initialized scale entries agree by construction.
    """
    if kv_dtype not in QUANT_KV_DTYPES:
        raise ValueError(f"quantize expects one of {QUANT_KV_DTYPES}, got {kv_dtype!r}")
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = (amax / _QMAX[kv_dtype]).astype(SCALE_DTYPE)
    safe = jnp.where(scale > 0, scale, 1.0)[..., None]
    if kv_dtype == "int8":
        q = jnp.clip(jnp.round(xf / safe), -127.0, 127.0).astype(jnp.int8)
    else:
        q = (xf / safe).astype(fp8_dtype())
    return q, scale


def dequantize(q: jnp.ndarray, scale: Optional[jnp.ndarray]) -> jnp.ndarray:
    """Inverse of :func:`quantize`; ``scale`` broadcasts over the head dim.

    ``scale=None`` is the fp passthrough (cast to f32 only), so callers can
    route both modes through one expression.
    """
    if scale is None:
        return q.astype(jnp.float32)
    return q.astype(jnp.float32) * scale[..., None].astype(jnp.float32)


def kv_dtype_of(pool: jnp.ndarray) -> str:
    """The ``kv_dtype`` a pool array stores, read from its element dtype."""
    if pool.dtype == jnp.int8:
        return "int8"
    f8 = fp8_dtype()
    if f8 is not None and pool.dtype == jnp.dtype(f8):
        return "fp8"
    return "fp"


def store(kv: dict, index, k_new: jnp.ndarray, v_new: jnp.ndarray) -> dict:
    """Write ``k_new``/``v_new`` at ``index`` of the attention cache ``kv``
    (``{"k", "v"}``, plus ``"k_scale"``/``"v_scale"`` for a quantized pool,
    which stores them quantized with their per-(token, kv-head) scales at
    the same index).  Out-of-range indices are dropped.  Returns the updated
    dict; under jit each write is one scatter into the array it is given."""
    out = dict(kv)
    for name, new in (("k", k_new), ("v", v_new)):
        pool = kv[name]
        if name + "_scale" in kv:
            q, s = quantize(new, kv_dtype_of(pool))
            out[name] = pool.at[index].set(q, mode="drop")
            out[name + "_scale"] = kv[name + "_scale"].at[index].set(s, mode="drop")
        else:
            out[name] = pool.at[index].set(new.astype(pool.dtype), mode="drop")
    return out
