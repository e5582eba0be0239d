"""Paged-native flash-decode Pallas kernel.

The gather-based paged decode (``core/decode_attention.py::paged_cache_gather``
+ the dense band kernel) materializes each slot's full ``[max_pages *
page_size]`` local view from the physical page pool every tick, so decode HBM
traffic scales with *virtual capacity*, not with how deep any request actually
is.  This kernel reads the page pool **in place**:

  * the grid is **one step per slot**, ``(batch,)``.  The int32 block table,
    the per-slot positions, ``kv_offset`` and the layer index are
    **scalar-prefetched** into SMEM; from them the step works out the slot's
    visible logical pages ``[lp_lo, lp_hi]`` (depth, shard stride and
    sliding window) and runs a ``lax.fori_loop`` over exactly those pages,
    nothing past them;
  * the pool is the model's whole stacked ``[L, num_pages, page_size, Hkv,
    D]`` pool and stays in HBM (``memory_space=pltpu.HBM``); every page copy
    reads ``pool.at[layer, page]``, so no layer's slice of the pool is ever
    materialized around the call.  The loop walks the visible pages in
    **blocks of P pages** (``P = max(1, 256 // page_size)``,
    about 256 tokens per block: 16 pages of the 16-token serving pool, 2 of
    the dense 128-token view).  Each page of a block is fetched with its own
    ``make_async_copy`` into a double-buffered VMEM block ``[2, P*page_size,
    Hkv, D]``; block j+1's copies are started before block j is computed,
    and the last step of a slot starts the next slot's first block, so the
    DMA engine is busy across slot boundaries too (grid axis ``arbitrary``).
    Quantized pools fetch each page's ``[page_size, Hkv]`` scale tile of the
    layer through the same page id into their own buffers and dequantize in
    VMEM;
  * pages past the depth, pages the window hides and unallocated pages
    (block table ``-1``) are neither copied nor computed: a free slot costs
    one grid step and no DMA, and HBM bytes follow depth;
  * online softmax in f32 over the blocks, per-kv-head GQA dots with f32
    accumulation; the band predicate (global position ``<= pos`` and ``>=``
    the window's start) on every column masks the partial last page and the
    window tail, and a column mask drops the pages of a block that were not
    copied.  The step writes the final ``(o, lse)``; a row with nothing
    visible gets ``(0, NEG_INF)`` exactly, as the cross-shard psum combine
    expects.

There is no split axis over a slot's pages: the TPU v5e has one TensorCore
and runs the grid in sequence, so splitting a slot buys no parallelism and
only adds grid steps, f32 partials written to HBM and a combine read back.
Cost per call is one step per slot plus one loop iteration per visible block.

Geometry matches ``core/decode_attention.py`` verbatim: local slot ``j`` of a
shard holds global position ``kv_offset + stride_kv * j`` (striped:
``(i, n)``; contiguous: ``(i*m, 1)``), and slot ``j`` lives at offset
``j % page_size`` of logical page ``j // page_size``.  A dense ``[B, m]``
cache is the degenerate case: reshape ``[L, B, m]`` to ``[L, B * (m/chunk),
chunk]`` pages with the identity block table ``bt[b, c] = b * chunks + c`` —
one implicit page run per row — and this same kernel serves the dense decode
path too.

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
    "pages_per_block",
    "dense_chunk_for",
]

# tokens one DMA block covers: enough pages per block that the copies stream
# and the loop's fixed cost is spread, small enough that two K and two V
# blocks (plus their f32 working copies) sit well inside scoped VMEM
BLOCK_TOKENS = 256

# candidate chunk sizes (local positions) for viewing a DENSE cache row as an
# implicit page run; the largest divisor of m wins, capped MXU-friendly
_DENSE_CHUNKS = (128, 64, 32, 16, 8, 4, 2, 1)


def pages_per_block(page_size: int) -> int:
    """Pages the kernel fetches and computes per loop iteration."""
    return max(1, BLOCK_TOKENS // page_size)


def dense_chunk_for(m: int) -> int:
    """Page size for the dense-cache-as-one-page-run view of a [B, m] slice:
    the largest candidate dividing m (always found — 1 divides everything),
    so the reshape in ``sharded_cache_decode`` is exact."""
    return next(c for c in _DENSE_CHUNKS if c <= m and m % c == 0)


def _visible_pages(pos_b, kv_off, *, stride_kv, page_size, max_pages, hi):
    """(first, last, window start) of a slot's visible logical pages; the
    range is empty (last < first) when no local slot is visible."""
    win_lo = jnp.maximum(pos_b - hi, 0)  # oldest visible global position
    j_hi = (pos_b - kv_off) // stride_kv  # last local slot at or before pos
    j_lo = jnp.maximum((win_lo - kv_off + stride_kv - 1) // stride_kv, 0)
    lp_hi = jnp.minimum(j_hi // page_size, max_pages - 1)
    lp_lo = j_lo // page_size
    return lp_lo, lp_hi, win_lo


def _decode_kernel(
    # scalar prefetch (SMEM)
    bt_ref,  # [B, max_pages] int32 block table; -1 = unallocated
    pos_ref,  # [B] int32 per-slot positions
    off_ref,  # [1] int32 kv_offset (may be traced from axis_index)
    layer_ref,  # [1] int32 layer of the stacked pools this call reads
    # q block (VMEM) and the pools (HBM)
    q_ref,  # [1, H, D]
    k_hbm,  # [L, num_pages, page_size, Hkv, D]
    v_hbm,
    # quantized pools add the layer's two [num_pages, 1, lanes] fp32 scale
    # rows here; then outputs o [1,H,D] / lse [1,1,H]; then scratch: the
    # K/V blocks [2, P*page_size, Hkv, D] (+ scale blocks), the DMA sems
    *rest,
    scale: float,
    stride_kv: int,
    page_size: int,
    max_pages: int,
    block_pages: int,
    hi: int,  # window - 1, or BAND_INF for no window
    group: int,  # H // Hkv (GQA)
    hkv: int,
    quantized: bool,
):
    if quantized:
        (ks_hbm, vs_hbm, o_ref, lse_ref,
         k_buf, v_buf, ks_buf, vs_buf, sems) = rest
    else:
        o_ref, lse_ref, k_buf, v_buf, sems = rest
        ks_hbm = vs_hbm = ks_buf = vs_buf = None
    b, nb = pl.program_id(0), pl.num_programs(0)
    kv_off = off_ref[0]
    layer = layer_ref[0]
    P, T = block_pages, block_pages * page_size
    vis = functools.partial(
        _visible_pages, stride_kv=stride_kv, page_size=page_size,
        max_pages=max_pages, hi=hi,
    )

    def n_blocks(lp_lo, lp_hi):
        return jnp.maximum(lp_hi - lp_lo + P, 0) // P

    def page_copies(slot_b, lp, lp_hi, buf, i):
        """(predicate, copies) of page i of a block: logical page lp of
        slot_b into rows [i*page_size, (i+1)*page_size) of buffer buf."""
        pid = bt_ref[slot_b, jnp.minimum(lp, max_pages - 1)]
        ok = (lp <= lp_hi) & (pid >= 0)
        pid = jnp.maximum(pid, 0)
        rows = pl.ds(pl.multiple_of(i * page_size, page_size), page_size)
        cps = [
            pltpu.make_async_copy(k_hbm.at[layer, pid], k_buf.at[buf, rows], sems.at[0, buf]),
            pltpu.make_async_copy(v_hbm.at[layer, pid], v_buf.at[buf, rows], sems.at[1, buf]),
        ]
        if quantized:
            cps += [
                pltpu.make_async_copy(ks_hbm.at[pid], ks_buf.at[buf, i], sems.at[0, buf]),
                pltpu.make_async_copy(vs_hbm.at[pid], vs_buf.at[buf, i], sems.at[1, buf]),
            ]
        return ok, rows, cps

    def start_block(slot_b, lp0, lp_hi, buf):
        def one(i, _):
            ok, _, cps = page_copies(slot_b, lp0 + i, lp_hi, buf, i)

            @pl.when(ok)
            def _():
                for cp in cps:
                    cp.start()

            return 0

        jax.lax.fori_loop(0, P, one, 0)

    def wait_block(lp0, lp_hi, buf):
        """Wait for the block's copies; returns the [1, T] column mask of the
        pages that were copied.  A page not copied has its V rows (and V
        scales) zeroed: its columns carry weight 0, and 0 * stale VMEM must
        not turn into NaN."""
        cols = jax.lax.broadcasted_iota(jnp.int32, (1, T), 1) // page_size

        def one(i, copied):
            ok, rows, cps = page_copies(b, lp0 + i, lp_hi, buf, i)

            @pl.when(ok)
            def _():
                for cp in cps:
                    cp.wait()

            @pl.when(jnp.logical_not(ok))
            def _():
                v_buf[buf, rows] = jnp.zeros((page_size,) + v_buf.shape[2:], v_buf.dtype)
                if quantized:
                    vs_buf[buf, i] = jnp.zeros(vs_buf.shape[2:], vs_buf.dtype)

            return jnp.where(cols == i, ok.astype(jnp.int32), copied)

        return jax.lax.fori_loop(0, P, one, jnp.zeros((1, T), jnp.int32)) > 0

    pos_b = pos_ref[b]
    lp_lo, lp_hi, win_lo = vis(pos_b, kv_off)
    nblk = n_blocks(lp_lo, lp_hi)

    # the first block of slot 0 is started here; every later slot's first
    # block was started by the step before it (below)
    @pl.when((b == 0) & (nblk > 0))
    def _():
        start_block(b, lp_lo, lp_hi, 0)

    q = q_ref[0].astype(jnp.float32)  # [H, D]
    H, D = q.shape

    def token_scales(rows):
        """[P, 1, L] page rows of (token, kv-head) scales -> [T, Hkv]: each
        page row repeated over its tokens, then lane (r*Hkv + h) of token r
        picked into column h by a 0/1 matmul (exact at HIGHEST)."""
        L = rows.shape[-1]
        rep = jnp.broadcast_to(rows, (P, page_size, L)).reshape(T, L)
        r = jax.lax.broadcasted_iota(jnp.int32, (T, L), 0) % page_size
        lane = jax.lax.broadcasted_iota(jnp.int32, (T, L), 1)
        own = jnp.where(lane // hkv == r, rep, 0.0)  # token r's Hkv lanes
        pick = (jax.lax.broadcasted_iota(jnp.int32, (L, hkv), 0) % hkv
                == jax.lax.broadcasted_iota(jnp.int32, (L, hkv), 1))
        return jax.lax.dot(own, pick.astype(jnp.float32),
                           precision=jax.lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)

    def body(j, carry):
        m_prev, l_prev, acc_prev = carry
        buf = j % 2
        lp0 = lp_lo + j * P

        @pl.when(j + 1 < nblk)
        def _():
            start_block(b, lp0 + P, lp_hi, 1 - buf)

        copied = wait_block(lp0, lp_hi, buf)
        k = k_buf[buf].astype(jnp.float32)  # [T, Hkv, D]
        v = v_buf[buf].astype(jnp.float32)
        if quantized:
            # dequantize IN VMEM, right after the block's DMAs: the scale
            # tiles rode the same page ids, so HBM moved 1-byte elements + one
            # fp32 scale per (token, kv-head) instead of fp32 K/V
            k = k * token_scales(ks_buf[buf])[:, :, None]
            v = v * token_scales(vs_buf[buf])[:, :, None]
        s_rows = []
        for hk in range(hkv):  # GQA: per-kv-head [group, T] scores
            s_rows.append(jax.lax.dot_general(
                q[hk * group : (hk + 1) * group], k[:, hk, :],
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            ))
        sc = jnp.concatenate(s_rows, axis=0) * scale  # [H, T]
        cols = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        gpos = kv_off + stride_kv * (lp0 * page_size + cols)  # global position
        # the band masks the partial last page (columns past pos) AND any
        # in-block window tail — exactly the dense band kernel's predicate;
        # ``copied`` drops pages past the range or never allocated
        mask = (gpos <= pos_b) & (gpos >= win_lo) & copied
        m_cur = jnp.max(jnp.where(mask, sc, NEG_INF), axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        pw = jnp.where(mask, jnp.exp(sc - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(pw, axis=1, keepdims=True)
        o_rows = []
        for hk in range(hkv):
            o_rows.append(jax.lax.dot(
                pw[hk * group : (hk + 1) * group], v[:, hk, :],
                preferred_element_type=jnp.float32,
            ))
        acc_new = acc_prev * alpha + jnp.concatenate(o_rows, axis=0)
        return m_new, l_new, acc_new

    init = (
        jnp.full((H, 1), NEG_INF, jnp.float32),
        jnp.zeros((H, 1), jnp.float32),
        jnp.zeros((H, D), jnp.float32),
    )
    m, l, acc = jax.lax.fori_loop(0, nblk, body, init)

    # every copy of this slot has been waited for: start the next slot's
    # first block so its DMAs overlap this step's epilogue and the next
    # step's prologue
    @pl.when(b + 1 < nb)
    def _():
        lo1, hi1, _ = vis(pos_ref[b + 1], kv_off)

        @pl.when(n_blocks(lo1, hi1) > 0)
        def _():
            start_block(b + 1, lo1, hi1, 0)

    l_safe = jnp.where(l > 0, l, 1.0)
    o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
    lse_ref[0, 0] = jnp.where(l[:, 0] > 0, m[:, 0] + jnp.log(l_safe[:, 0]), NEG_INF)


def paged_flash_decode(
    q: jnp.ndarray,  # [B, 1, H, D] the new token's queries
    k_pool: jnp.ndarray,  # [L, num_pages, page_size, Hkv, D] local page pools
    v_pool: jnp.ndarray,
    block_table: jnp.ndarray,  # [B, max_pages] int32; -1 = unallocated
    pos,  # int32 scalar or [B]: attends to global positions <= pos
    kv_offset,  # int32 (may be traced): global position of local slot 0
    layer,  # int32 (may be traced): the layer of the stacked pools to read
    *,
    stride_kv: int,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    interpret: Optional[bool] = None,
    k_scale: Optional[jnp.ndarray] = None,  # [L, num_pages, page_size, Hkv] f32
    v_scale: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Layer ``layer``'s decode partial for this shard, straight off the
    stacked page pool: returns (o [B,1,H,D] in q.dtype, lse [B,H,1] fp32) —
    the same contract as the gather path's banded partial, ready for the
    cross-shard psum combine.  The pools are read in place, so a caller that
    carries them through its layer loop never copies a layer out.

    ``k_scale``/``v_scale`` mark a quantized pool (int8 / fp8 elements):
    each page's scale tile is fetched through the same page id and K/V are
    dequantized in VMEM right after the DMA."""
    B, _, H, D = q.shape
    _, num_pages, page_size, hkv, _ = k_pool.shape
    max_pages = block_table.shape[1]
    if H % hkv:
        raise ValueError(f"H={H} not divisible by Hkv={hkv}")
    group = H // hkv
    if scale is None:
        scale = D**-0.5
    hi = (window - 1) if window else BAND_INF
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    off = jnp.reshape(jnp.asarray(kv_offset, jnp.int32), (1,))
    lyr = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    bt = jnp.asarray(block_table, jnp.int32)
    P = pages_per_block(page_size)
    T = P * page_size
    interpret = resolve_interpret(interpret)

    quantized = k_scale is not None
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    in_specs = [pl.BlockSpec((1, H, D), lambda b, *_: (b, 0, 0)), hbm, hbm]
    operands = [bt, pos, off, lyr, q[:, 0], k_pool, v_pool]
    scratch = [
        pltpu.VMEM((2, T, hkv, D), k_pool.dtype),
        pltpu.VMEM((2, T, hkv, v_pool.shape[-1]), v_pool.dtype),
    ]
    if quantized:
        # a DMA may only slice whole 128-lane rows, so each page's
        # [page_size, Hkv] scale tile of the layer travels as one lane-dense
        # row (a relayout of the layer's scales: 4 bytes per token and head)
        lanes = -(-page_size * hkv // 128) * 128

        def rows(s):
            s = jax.lax.dynamic_index_in_dim(s, lyr[0], keepdims=False)
            s = s.astype(jnp.float32).reshape(num_pages, page_size * hkv)
            s = jnp.pad(s, ((0, 0), (0, lanes - page_size * hkv)))
            return s.reshape(num_pages, 1, lanes)

        in_specs += [hbm, hbm]
        operands += [rows(k_scale), rows(v_scale)]
        scratch += [pltpu.VMEM((2, P, 1, lanes), jnp.float32)] * 2
    scratch.append(pltpu.SemaphoreType.DMA((2, 2)))  # [K|V, buffer]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, H, D), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec((1, 1, H), lambda b, *_: (b, 0, 0)),
        ],
        scratch_shapes=scratch,
    )
    kernel = functools.partial(
        _decode_kernel,
        scale=float(scale), stride_kv=stride_kv, page_size=page_size,
        max_pages=max_pages, block_pages=P, hi=hi,
        group=group, hkv=hkv, quantized=quantized,
    )
    like = tuple(operands)
    o, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            vma_struct((B, H, D), q.dtype, *like),
            vma_struct((B, 1, H), jnp.float32, *like),
        ],
        interpret=interpret,
        compiler_params=None
        if interpret
        # the next slot's first block is started inside this step: the
        # steps must run in order on one core
        else pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        name="paged_flash_decode",
    )(*operands)
    return o[:, None], jnp.swapaxes(lse, 1, 2)
