"""Paged-native split-K flash-decode Pallas kernel.

The gather-based paged decode (``core/decode_attention.py::paged_cache_gather``
+ the dense band kernel) materializes each slot's full ``[max_pages *
page_size]`` local view from the physical page pool every tick, so decode HBM
traffic scales with *virtual capacity*, not with how deep any request actually
is.  This kernel reads the page pool **in place**:

  * the int32 block table and the per-slot position vector are
    **scalar-prefetched** (``pltpu.PrefetchScalarGridSpec``) so the BlockSpec
    index maps resolve logical page -> physical page before each grid step's
    DMA — the pool is indexed directly, no gathered intermediate ever exists;
  * the grid is ``(batch, split, pages_per_split)`` — **split-K over pages**:
    each split owns a contiguous run of a slot's logical pages and produces a
    partial ``(o, lse)`` carried in VMEM scratch (online softmax over its
    pages); splits combine outside the kernel with a numerically-stable LSE
    reduce (:func:`combine_split_partials`).  Mixed-depth slot pools therefore
    fill the grid with many small independent partials instead of serializing
    every row behind the deepest one;
  * pages a slot never allocated (block table ``-1``), pages past the row's
    depth, and pages a sliding window provably hides are skipped with
    ``pl.when`` predication, and their index maps **clamp to the nearest
    visible page** so the Pallas pipeline re-fetches nothing (consecutive
    equal block indices elide the DMA): HBM bytes/token follow depth;
  * the **partial last page** of a depth not divisible by ``page_size`` is
    masked inside the page by the position band (global position ``<= pos``),
    so the split's lse counts exactly the live tail — the combine then weighs
    it correctly against full pages (asserted exact-vs-oracle in
    tests/test_paged_decode.py).

Geometry matches ``core/decode_attention.py`` verbatim: local slot ``j`` of a
shard holds global position ``kv_offset + stride_kv * j`` (striped:
``(i, n)``; contiguous: ``(i*m, 1)``), and slot ``j`` lives at offset
``j % page_size`` of logical page ``j // page_size``.  A dense ``[B, m]``
cache is the degenerate case: reshape to ``[B * (m/chunk), chunk]`` pages
with the identity block table ``bt[b, c] = b * chunks + c`` — one implicit
page run per row — and this same kernel serves the dense decode path too.

TARGET: TPU v5e.  Off-TPU the kernel runs with ``interpret=True`` (CPU CI);
``REPRO_KERNELS=ref`` callers fall back to the gather path at the
``core/decode_attention.py`` layer instead (the exact oracle).
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

__all__ = [
    "paged_flash_decode",
    "combine_split_partials",
    "default_num_splits",
    "dense_chunk_for",
]

# default logical pages each split-K partial covers; small enough that a few
# allocated pages already spread over several grid cells, big enough that the
# per-split finalize/combine overhead stays negligible
DEFAULT_PAGES_PER_SPLIT = 4

# candidate chunk sizes (local positions) for viewing a DENSE cache row as an
# implicit page run; the largest divisor of m wins, capped MXU-friendly
_DENSE_CHUNKS = (128, 64, 32, 16, 8, 4, 2, 1)


def default_num_splits(max_pages: int) -> int:
    return max(1, -(-max_pages // DEFAULT_PAGES_PER_SPLIT))


def dense_chunk_for(m: int) -> int:
    """Page size for the dense-cache-as-one-page-run view of a [B, m] slice:
    the largest candidate dividing m (always found — 1 divides everything),
    so the reshape in ``sharded_cache_decode`` is exact."""
    return next(c for c in _DENSE_CHUNKS if c <= m and m % c == 0)


def combine_split_partials(
    o_parts: jnp.ndarray,  # [B, S, H, D] fp32 per-split partial outputs
    lse_parts: jnp.ndarray,  # [B, S, H] fp32 per-split lse (NEG_INF = empty)
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Numerically-stable LSE reduce over the split axis -> ([B,1,H,D] fp32,
    [B,H,1] fp32), the same (o, lse) contract the banded partial returns.

    Empty splits (lse == NEG_INF) must contribute weight 0 even when EVERY
    split is empty (then m == NEG_INF and exp(lse - m) would be 1): the
    nonempty mask guards that, and a fully-hidden row combines to the exact
    empty-band result (o = 0, lse = NEG_INF) the psum combine expects.
    """
    m = jnp.maximum(jnp.max(lse_parts, axis=1), NEG_INF)  # [B, H]
    nonempty = lse_parts > NEG_INF / 2
    w = jnp.where(nonempty, jnp.exp(lse_parts - m[:, None]), 0.0)  # [B, S, H]
    den = jnp.sum(w, axis=1)  # [B, H]
    num = jnp.einsum("bsh,bshd->bhd", w, o_parts)
    den_safe = jnp.where(den > 0, den, 1.0)
    o = num / den_safe[..., None]
    lse = jnp.where(den > 0, m + jnp.log(den_safe), NEG_INF)
    return o[:, None], lse[..., None]  # [B,1,H,D], [B,H,1]


def _decode_kernel(
    # scalar prefetch (SMEM)
    bt_ref,  # [B, max_pages] int32 block table; -1 = unallocated
    pos_ref,  # [B] int32 per-slot positions
    off_ref,  # [1] int32 kv_offset (may be traced from axis_index)
    # blocks (VMEM)
    q_ref,  # [1, H, D]
    k_ref,  # [1, page_size, Hkv, D] one physical page
    v_ref,
    # quantized pools add two [1, page_size, Hkv] fp32 scale blocks here,
    # then outputs o [1,1,H,D] / lse [1,1,H], then scratch acc/m/l
    *rest,
    scale: float,
    stride_kv: int,
    page_size: int,
    max_pages: int,
    pages_per_split: int,
    hi: int,  # window - 1, or BAND_INF for no window
    group: int,  # H // Hkv (GQA)
    hkv: int,
    quantized: bool,
):
    if quantized:
        ks_ref, vs_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
    else:
        ks_ref = vs_ref = None
        o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
    b, s, p = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    lp = s * pages_per_split + p  # logical page this grid step covers
    pos_b = pos_ref[b]
    kv_off = off_ref[0]
    page_lo = kv_off + stride_kv * (lp * page_size)  # first global pos in page
    page_hi = kv_off + stride_kv * (lp * page_size + page_size - 1)
    win_lo = jnp.maximum(pos_b - hi, 0)  # oldest visible global position
    visible = (
        (lp < max_pages)
        & (bt_ref[b, jnp.minimum(lp, max_pages - 1)] >= 0)
        & (page_lo <= pos_b)  # page starts at or before the row's depth
        & (page_hi >= win_lo)  # page ends inside the sliding window
    )

    @pl.when(visible)
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # [H, D]
        k = k_ref[0].astype(jnp.float32)  # [page_size, Hkv, D]
        v = v_ref[0].astype(jnp.float32)
        if quantized:
            # dequantize IN VMEM, right after the page's DMA: the scale tile
            # rode along as an extra prefetched operand through the same
            # clamped index map, so HBM moved 1-byte elements + one fp32
            # scale per (token, kv-head) instead of fp32 K/V
            k = k * ks_ref[0][:, :, None].astype(jnp.float32)
            v = v * vs_ref[0][:, :, None].astype(jnp.float32)
        s_rows = []
        for hk in range(hkv):  # GQA: per-kv-head [group, page_size] scores
            s_rows.append(jax.lax.dot_general(
                q[hk * group : (hk + 1) * group], k[:, hk, :],
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            ))
        sc = jnp.concatenate(s_rows, axis=0) * scale  # [H, page_size]
        cols = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        gpos = page_lo + stride_kv * cols  # global position per column
        # the band masks the partial last page (columns past pos) AND any
        # in-page window tail — exactly the dense band kernel's predicate
        mask = (gpos <= pos_b) & (gpos >= win_lo)
        m_prev = m_ref[...]
        m_cur = jnp.max(jnp.where(mask, sc, NEG_INF), axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        pw = jnp.where(mask, jnp.exp(sc - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(pw, axis=1, keepdims=True)
        o_rows = []
        for hk in range(hkv):
            o_rows.append(jax.lax.dot(
                pw[hk * group : (hk + 1) * group], v[:, hk, :],
                preferred_element_type=jnp.float32,
            ))
        acc_ref[...] = acc_ref[...] * alpha + jnp.concatenate(o_rows, axis=0)
        m_ref[...] = m_new

    @pl.when(p == pages_per_split - 1)
    def _finalize():
        l = l_ref[...]
        l_safe = jnp.where(l > 0, l, 1.0)
        o_ref[0, 0] = acc_ref[...] / l_safe
        lse_ref[0, 0, 0] = jnp.where(
            l[:, 0] > 0, m_ref[:, 0] + jnp.log(l_safe[:, 0]), NEG_INF
        )


def paged_flash_decode(
    q: jnp.ndarray,  # [B, 1, H, D] the new token's queries
    k_pool: jnp.ndarray,  # [num_pages, page_size, Hkv, D] local page pool
    v_pool: jnp.ndarray,
    block_table: jnp.ndarray,  # [B, max_pages] int32; -1 = unallocated
    pos,  # int32 scalar or [B]: attends to global positions <= pos
    kv_offset,  # int32 (may be traced): global position of local slot 0
    *,
    stride_kv: int,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    num_splits: Optional[int] = None,
    interpret: Optional[bool] = None,
    k_scale: Optional[jnp.ndarray] = None,  # [num_pages, page_size, Hkv] f32
    v_scale: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """This shard's decode partial straight off the page pool: returns
    (o [B,1,H,D] in q.dtype, lse [B,H,1] fp32) — the same contract as the
    gather path's banded partial, ready for the cross-shard psum combine.

    ``k_scale``/``v_scale`` mark a quantized pool (int8 / fp8 elements):
    each page's scale tile is fetched through the same clamped index map and
    K/V are dequantized in VMEM right after the DMA."""
    B, _, H, D = q.shape
    num_pages, page_size, hkv, _ = k_pool.shape
    max_pages = block_table.shape[1]
    if H % hkv:
        raise ValueError(f"H={H} not divisible by Hkv={hkv}")
    group = H // hkv
    if scale is None:
        scale = D**-0.5
    hi = (window - 1) if window else BAND_INF
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    off = jnp.reshape(jnp.asarray(kv_offset, jnp.int32), (1,))
    bt = jnp.asarray(block_table, jnp.int32)
    if num_splits is None:
        num_splits = default_num_splits(max_pages)
    num_splits = max(1, min(int(num_splits), max_pages))
    pages_per_split = -(-max_pages // num_splits)
    interpret = resolve_interpret(interpret)

    def kv_index_map(b, s, p, bt_ref, pos_ref, off_ref):
        # clamp invisible steps to the nearest VISIBLE logical page so runs of
        # skipped steps keep the block index constant and the pipeline elides
        # their DMAs (depth-proportional HBM traffic, not capacity)
        lp = s * pages_per_split + p
        pos_b, kv_off = pos_ref[b], off_ref[0]
        lp_hi = (pos_b - kv_off) // (stride_kv * page_size)  # last visible
        win_lo = jnp.maximum(pos_b - hi, 0)
        j_lo = (win_lo - kv_off + stride_kv - 1) // stride_kv
        lp_lo = jnp.maximum(j_lo, 0) // page_size  # first visible
        lp_hi = jnp.clip(lp_hi, 0, max_pages - 1)
        lp_lo = jnp.clip(lp_lo, 0, lp_hi)
        lp_eff = jnp.clip(lp, lp_lo, lp_hi)
        return (jnp.maximum(bt_ref[b, lp_eff], 0), 0, 0, 0)

    def scale_index_map(b, s, p, bt_ref, pos_ref, off_ref):
        # the scale tile rides the pool's physical-page resolution verbatim
        return kv_index_map(b, s, p, bt_ref, pos_ref, off_ref)[:3]

    quantized = k_scale is not None
    in_specs = [
        pl.BlockSpec((1, H, D), lambda b, s, p, *_: (b, 0, 0)),
        pl.BlockSpec((1, page_size, hkv, D), kv_index_map),
        pl.BlockSpec((1, page_size, hkv, D), kv_index_map),
    ]
    operands = [bt, pos, off, q[:, 0], k_pool, v_pool]
    if quantized:
        in_specs += [
            pl.BlockSpec((1, page_size, hkv), scale_index_map),
            pl.BlockSpec((1, page_size, hkv), scale_index_map),
        ]
        operands += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, num_splits, pages_per_split),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, H, D), lambda b, s, p, *_: (b, s, 0, 0)),
            pl.BlockSpec((1, 1, 1, H), lambda b, s, p, *_: (b, s, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((H, D), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _decode_kernel,
        scale=float(scale), stride_kv=stride_kv, page_size=page_size,
        max_pages=max_pages, pages_per_split=pages_per_split, hi=hi,
        group=group, hkv=hkv, quantized=quantized,
    )
    like = tuple(operands)
    o_parts, lse_parts = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            vma_struct((B, num_splits, H, D), jnp.float32, *like),
            vma_struct((B, num_splits, 1, H), jnp.float32, *like),
        ],
        interpret=interpret,
        compiler_params=None
        if interpret
        else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        name="paged_flash_decode",
    )(*operands)
    o, lse = combine_split_partials(o_parts, lse_parts[:, :, 0])
    return o.astype(q.dtype), lse
