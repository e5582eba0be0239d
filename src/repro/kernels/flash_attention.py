"""Pallas TPU flash-attention kernels (forward, backward-dQ, backward-dKV).

TARGET: TPU v5e MXU/VMEM.  Validated on CPU with ``interpret=True`` against
``kernels/ref.py`` (see tests/test_kernels.py); compiled for a v5e by
tests/test_tpu_compile.py.

Design (TPU-native, not a CUDA port):
  * grid = (batch, q_heads, q_blocks, kv_blocks); the kv dimension is the
    innermost, sequential ("arbitrary") axis, carrying the online-softmax
    state (m, l, acc) in VMEM scratch across kv steps — HBM->VMEM streaming
    of K/V blocks is done by the Pallas pipeline via BlockSpec index maps.
  * block_q × block_kv default 128×128: MXU-aligned (128 lanes) and the
    working set (q, k, v, acc at fp32) stays well under VMEM (~16 MB).
  * the mask is a *band* in token space, parameterized by a dynamic int32[4]
    SMEM operand (q_offset, kv_offset, lo, hi) and static strides — one
    kernel covers full / causal / striped-causal (paper §3.7) / sliding
    window, and the offsets may depend on ``jax.lax.axis_index`` inside
    shard_map (they are *data*, not trace-time constants).
  * fully-masked blocks are skipped at runtime with ``pl.when`` predication
    (the striped-causal schedule makes whole blocks invisible ~half the
    time, recovering the causal FLOP saving block-wise).
  * GQA: K/V carry Hkv heads; index maps divide the query head index.

All softmax arithmetic is fp32 regardless of the input dtype; matmuls use
``preferred_element_type=float32`` so the MXU accumulates in fp32.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat import vma_struct
from repro.kernels import resolve_interpret
from repro.kernels.ref import BAND_INF, NEG_INF

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_KV = 128


def _struct(shape, dtype, *like):
    """ShapeDtypeStruct whose varying-manual-axes set is the union of the
    inputs' — required for pallas_call outputs under shard_map(check_vma)."""
    return vma_struct(shape, dtype, *like)


def _block_visible(band_ref, iq, ik, bq, bk, stride_q, stride_kv):
    """Any (row, col) in this (q-block, kv-block) pair inside the band?"""
    q0 = band_ref[0] + stride_q * (iq * bq)
    q1 = band_ref[0] + stride_q * (iq * bq + bq - 1)
    k0 = band_ref[1] + stride_kv * (ik * bk)
    k1 = band_ref[1] + stride_kv * (ik * bk + bk - 1)
    dmax = q1 - k0
    dmin = q0 - k1
    return (dmax >= band_ref[2]) & (dmin <= band_ref[3])


def _band_mask_block(band_ref, iq, ik, bq, bk, stride_q, stride_kv):
    rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    qpos = band_ref[0] + stride_q * (iq * bq + rows)
    kpos = band_ref[1] + stride_kv * (ik * bk + cols)
    diff = qpos - kpos
    return (diff >= band_ref[2]) & (diff <= band_ref[3])


def _mask_block(band_ref, segq_ref, segk_ref, iq, ik, bq, bk, stride_q, stride_kv):
    """Band mask, composed with the segment-id (packed-document) mask when
    the seg refs are present: (i, j) visible iff in-band AND same segment."""
    mask = _band_mask_block(band_ref, iq, ik, bq, bk, stride_q, stride_kv)
    if segq_ref is not None:
        segq = segq_ref[0, :]  # [bq]
        segk = segk_ref[0, :]  # [bk]
        mask &= segq[:, None] == segk[None, :]
    return mask


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------


def _fwd_kernel(
    band_ref,  # int32[4] in SMEM: (q_off, kv_off, lo, hi)
    q_ref,  # [1, 1, bq, D] VMEM
    k_ref,  # [1, 1, bk, D]
    v_ref,  # [1, 1, bk, D]
    *rest,  # [segq_ref [1, bq], segk_ref [1, bk],] o_ref, lse_ref, scratch...
    scale: float,
    stride_q: int,
    stride_kv: int,
    nk: int,
    has_seg: bool = False,
):
    if has_seg:
        segq_ref, segk_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
    else:
        segq_ref = segk_ref = None
        o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
    iq, ik = pl.program_id(2), pl.program_id(3)
    bq, d = q_ref.shape[2], q_ref.shape[3]
    bk = k_ref.shape[2]

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(_block_visible(band_ref, iq, ik, bq, bk, stride_q, stride_kv))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        mask = _mask_block(band_ref, segq_ref, segk_ref, iq, ik, bq, bk, stride_q, stride_kv)
        m_prev = m_ref[...]
        m_cur = jnp.max(jnp.where(mask, s, NEG_INF), axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_ref[...]
        l_safe = jnp.where(l > 0, l, 1.0)
        o_ref[0, 0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        lse = jnp.where(l > 0, m_ref[...] + jnp.log(l_safe), NEG_INF)
        lse_ref[0, 0, 0] = lse[:, 0].astype(lse_ref.dtype)


def _seg_operands(seg_q, seg_kv, block_q, block_kv):
    """Segment ids as [1, S] int32 pallas operands + their BlockSpecs."""
    sq = jnp.asarray(seg_q, jnp.int32)[None, :]
    sk = jnp.asarray(seg_kv, jnp.int32)[None, :]
    specs = [
        pl.BlockSpec((1, block_q), lambda b, h, iq, ik: (0, iq)),
        pl.BlockSpec((1, block_kv), lambda b, h, iq, ik: (0, ik)),
    ]
    return [sq, sk], specs


def flash_attention_fwd(
    q: jnp.ndarray,  # [B, Sq, H, D]
    k: jnp.ndarray,  # [B, Skv, Hkv, D]
    v: jnp.ndarray,
    band: jnp.ndarray,  # int32[4]; may be traced (e.g. from axis_index)
    *,
    scale: float,
    stride_q: int = 1,
    stride_kv: int = 1,
    block_q: int = DEFAULT_BLOCK_Q,
    block_kv: int = DEFAULT_BLOCK_KV,
    interpret: Optional[bool] = None,
    seg_q: Optional[jnp.ndarray] = None,  # [Sq] int32 segment ids
    seg_kv: Optional[jnp.ndarray] = None,  # [Skv]
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (o [B,Sq,H,D], lse [B,H,Sq]).  ``interpret=None`` follows
    the platform (see ``resolve_interpret``)."""
    interpret = resolve_interpret(interpret)
    B, Sq, H, D = q.shape
    _, Skv, Hkv, _ = k.shape
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Skv)
    if Sq % block_q or Skv % block_kv:
        raise ValueError(f"seq lengths ({Sq},{Skv}) not divisible by blocks ({block_q},{block_kv})")
    if H % Hkv:
        raise ValueError(f"H={H} not divisible by Hkv={Hkv}")
    group = H // Hkv
    nq, nk = Sq // block_q, Skv // block_kv
    has_seg = seg_q is not None

    qt = q.transpose(0, 2, 1, 3)  # [B, H, Sq, D]
    kt = k.transpose(0, 2, 1, 3)  # [B, Hkv, Skv, D]
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, stride_q=stride_q, stride_kv=stride_kv, nk=nk,
        has_seg=has_seg,
    )
    grid = (B, H, nq, nk)
    out_shape = [
        _struct((B, H, Sq, D), q.dtype, q, k, v, band),
        _struct((B, H, 1, Sq), jnp.float32, q, k, v, band),
    ]
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
        pl.BlockSpec((1, 1, block_kv, D), lambda b, h, iq, ik: (b, h // group, ik, 0)),
        pl.BlockSpec((1, 1, block_kv, D), lambda b, h, iq, ik: (b, h // group, ik, 0)),
    ]
    operands = [band.astype(jnp.int32), qt, kt, vt]
    if has_seg:
        seg_ops, seg_specs = _seg_operands(seg_q, seg_kv, block_q, block_kv)
        operands += seg_ops
        in_specs += seg_specs
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, 1, block_q), lambda b, h, iq, ik: (b, h, 0, iq)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=None
        if interpret
        else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
        name="mesh_flash_fwd",
    )(*operands)
    return o.transpose(0, 2, 1, 3), lse.reshape(B, H, Sq)


# --------------------------------------------------------------------------
# backward: dQ  (grid over q blocks, kv innermost)
# --------------------------------------------------------------------------


def _dq_kernel(
    band_ref,
    q_ref,  # [1,1,bq,D]
    k_ref,  # [1,1,bk,D]
    v_ref,
    do_ref,  # [1,1,bq,D]
    lse_ref,  # [1,1,bq]
    delta_ref,  # [1,1,bq]
    *rest,  # [segq_ref, segk_ref,] dq_ref, acc_ref
    scale: float,
    stride_q: int,
    stride_kv: int,
    nk: int,
    has_seg: bool = False,
):
    if has_seg:
        segq_ref, segk_ref, dq_ref, acc_ref = rest
    else:
        segq_ref = segk_ref = None
        dq_ref, acc_ref = rest
    iq, ik = pl.program_id(2), pl.program_id(3)
    bq, d = q_ref.shape[2], q_ref.shape[3]
    bk = k_ref.shape[2]

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(_block_visible(band_ref, iq, ik, bq, bk, stride_q, stride_kv))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0, 0].astype(jnp.float32)[:, None]
        delta = delta_ref[0, 0, 0].astype(jnp.float32)[:, None]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        mask = _mask_block(band_ref, segq_ref, segk_ref, iq, ik, bq, bk, stride_q, stride_kv)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta) * scale
        acc_ref[...] += jax.lax.dot(ds, k, preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _finalize():
        dq_ref[0, 0] = acc_ref[...].astype(dq_ref.dtype)


# --------------------------------------------------------------------------
# backward: dK/dV  (grid over kv blocks, q x head-group innermost)
# --------------------------------------------------------------------------


def _dkv_kernel(
    band_ref,
    q_ref,  # [1,1,bq,D]
    k_ref,  # [1,1,bk,D]
    v_ref,
    do_ref,  # [1,1,bq,D]
    lse_ref,  # [1,1,bq]
    delta_ref,  # [1,1,bq]
    *rest,  # [segq_ref, segk_ref,] dk_ref, dv_ref, dk_acc, dv_acc
    scale: float,
    stride_q: int,
    stride_kv: int,
    inner: int,  # = group * nq
    nq: int,
    has_seg: bool = False,
):
    if has_seg:
        segq_ref, segk_ref, dk_ref, dv_ref, dk_acc, dv_acc = rest
    else:
        segq_ref = segk_ref = None
        dk_ref, dv_ref, dk_acc, dv_acc = rest
    ik, it = pl.program_id(2), pl.program_id(3)
    iq = it % nq
    bq, d = q_ref.shape[2], q_ref.shape[3]
    bk = k_ref.shape[2]

    @pl.when(it == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(_block_visible(band_ref, iq, ik, bq, bk, stride_q, stride_kv))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0, 0].astype(jnp.float32)[:, None]
        delta = delta_ref[0, 0, 0].astype(jnp.float32)[:, None]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        mask = _mask_block(band_ref, segq_ref, segk_ref, iq, ik, bq, bk, stride_q, stride_kv)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)  # [bq, bk]
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta) * scale
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(it == inner - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def flash_attention_bwd(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    o: Optional[jnp.ndarray],
    lse: jnp.ndarray,  # [B, H, Sq]
    do: jnp.ndarray,
    band: jnp.ndarray,
    *,
    scale: float,
    stride_q: int = 1,
    stride_kv: int = 1,
    block_q: int = DEFAULT_BLOCK_Q,
    block_kv: int = DEFAULT_BLOCK_KV,
    interpret: Optional[bool] = None,
    delta: Optional[jnp.ndarray] = None,  # [B, Sq, H]
    seg_q: Optional[jnp.ndarray] = None,  # [Sq] int32 segment ids
    seg_kv: Optional[jnp.ndarray] = None,  # [Skv]
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """FlashAttention backward from saved (o, lse): (dq, dk, dv)."""
    interpret = resolve_interpret(interpret)
    B, Sq, H, D = q.shape
    _, Skv, Hkv, _ = k.shape
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Skv)
    group = H // Hkv
    nq, nk = Sq // block_q, Skv // block_kv
    band = band.astype(jnp.int32)
    has_seg = seg_q is not None

    if delta is None:
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    # row vectors [B, H, 1, Sq]: a (1, block_q) block meets the TPU tiling
    # rule, where a (1, 1, block_q) block of [B, H, Sq] would not
    delta = delta.astype(jnp.float32).transpose(0, 2, 1).reshape(B, H, 1, Sq)
    lse = lse.reshape(B, H, 1, Sq)

    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    dot = do.transpose(0, 2, 1, 3)

    interp_params = dict(interpret=interpret)

    dq_kernel = functools.partial(
        _dq_kernel, scale=scale, stride_q=stride_q, stride_kv=stride_kv, nk=nk,
        has_seg=has_seg,
    )
    dq_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
        pl.BlockSpec((1, 1, block_kv, D), lambda b, h, iq, ik: (b, h // group, ik, 0)),
        pl.BlockSpec((1, 1, block_kv, D), lambda b, h, iq, ik: (b, h // group, ik, 0)),
        pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
        pl.BlockSpec((1, 1, 1, block_q), lambda b, h, iq, ik: (b, h, 0, iq)),
        pl.BlockSpec((1, 1, 1, block_q), lambda b, h, iq, ik: (b, h, 0, iq)),
    ]
    dq_operands = [band, qt, kt, vt, dot, lse, delta]
    if has_seg:
        seg_ops, seg_specs = _seg_operands(seg_q, seg_kv, block_q, block_kv)
        dq_operands += seg_ops
        dq_specs += seg_specs
    dqt = pl.pallas_call(
        dq_kernel,
        grid=(B, H, nq, nk),
        in_specs=dq_specs,
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        out_shape=_struct((B, H, Sq, D), q.dtype, q, k, v, do, band),
        compiler_params=None
        if interpret
        else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
        name="mesh_flash_dq",
        **interp_params,
    )(*dq_operands)

    inner = group * nq
    dkv_kernel = functools.partial(
        _dkv_kernel, scale=scale, stride_q=stride_q, stride_kv=stride_kv, inner=inner, nq=nq,
        has_seg=has_seg,
    )
    dkv_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec(
            (1, 1, block_q, D),
            lambda b, hkv, ik, it, g=group, nq_=nq: (b, hkv * g + it // nq_, it % nq_, 0),
        ),
        pl.BlockSpec((1, 1, block_kv, D), lambda b, hkv, ik, it: (b, hkv, ik, 0)),
        pl.BlockSpec((1, 1, block_kv, D), lambda b, hkv, ik, it: (b, hkv, ik, 0)),
        pl.BlockSpec(
            (1, 1, block_q, D),
            lambda b, hkv, ik, it, g=group, nq_=nq: (b, hkv * g + it // nq_, it % nq_, 0),
        ),
        pl.BlockSpec(
            (1, 1, 1, block_q),
            lambda b, hkv, ik, it, g=group, nq_=nq: (b, hkv * g + it // nq_, 0, it % nq_),
        ),
        pl.BlockSpec(
            (1, 1, 1, block_q),
            lambda b, hkv, ik, it, g=group, nq_=nq: (b, hkv * g + it // nq_, 0, it % nq_),
        ),
    ]
    dkv_operands = [band, qt, kt, vt, dot, lse, delta]
    if has_seg:
        dkv_operands += [jnp.asarray(seg_q, jnp.int32)[None, :],
                         jnp.asarray(seg_kv, jnp.int32)[None, :]]
        dkv_specs += [
            pl.BlockSpec((1, block_q), lambda b, hkv, ik, it, nq_=nq: (0, it % nq_)),
            pl.BlockSpec((1, block_kv), lambda b, hkv, ik, it: (0, ik)),
        ]
    dkt, dvt = pl.pallas_call(
        dkv_kernel,
        grid=(B, Hkv, nk, inner),
        in_specs=dkv_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_kv, D), lambda b, hkv, ik, it: (b, hkv, ik, 0)),
            pl.BlockSpec((1, 1, block_kv, D), lambda b, hkv, ik, it: (b, hkv, ik, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_kv, D), jnp.float32),
            pltpu.VMEM((block_kv, D), jnp.float32),
        ],
        out_shape=[
            _struct((B, Hkv, Skv, D), k.dtype, q, k, v, do, band),
            _struct((B, Hkv, Skv, D), v.dtype, q, k, v, do, band),
        ],
        compiler_params=None
        if interpret
        else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
        name="mesh_flash_dkv",
        **interp_params,
    )(*dkv_operands)

    return (
        dqt.transpose(0, 2, 1, 3),
        dkt.transpose(0, 2, 1, 3),
        dvt.transpose(0, 2, 1, 3),
    )
