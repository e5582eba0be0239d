"""Distributed flash-decode over a sequence-sharded KV cache.

The paper's locality idea applied to inference: the KV cache is sharded over
the sequence-parallel axis — by *absolute position modulo n* ("striped", the
same striping the causal mask uses for training, §3.7) or contiguously (for
SSM/hybrid archs whose train layout is contiguous).  Each decode step:

  1. the new token's Q is replicated across the axis (it is tiny),
  2. every device computes a partial flash-decode over its local cache slice,
  3. partials are combined with an lse-weighted ``psum`` — per-token
     communication is O(B·H·D), independent of context length.

This replaces head-parallel (Ulysses-style) decode, which is capped at Hkv
devices — with GQA (e.g. kv=8 on a 16-wide model axis) that cap binds, the
sequence-sharded cache does not.  Striping additionally balances appends
(shard t mod n) no matter how long generation runs.

``pos`` may be a scalar (every batch row at the same depth — the static-batch
case) or an int32 ``[B]`` vector of per-slot positions.  The vector form is
what makes continuous batching cheap here: each slot's owner/band math is
independent, so one step serves slots at arbitrary mixed depths with the same
O(B·H·D) per-token combine.

Two cache layouts share the band math:

  * **dense** (``sharded_cache_*``) — each batch row owns a ``[cap/n]``
    local slice; owner shard -> slot row.
  * **paged** (``paged_cache_*``) — rows share one physical page pool
    ``[num_pages, page_size, Hkv, D]`` per device, addressed through an int32
    block table ``[B, max_pages]`` (``serve/kv_pool.py`` owns the allocator);
    owner shard -> (page, offset).  The decode band gathers the row's pages
    into the same local-position order the dense slice has, so the kernel
    call — and therefore the numerics — are identical to the dense path.

Every entry takes the caches STACKED over the model's layers (``[L, B, m,
Hkv, D]`` / ``[L, num_pages, page_size, Hkv, D]``, scale tables likewise)
plus the ``layer`` it serves: the model carries the whole stack through its
layer loop, appends scatter at ``[layer, ...]`` in place and reads index
``[layer, ...]`` directly, so no layer's slice of the cache is copied out
and written back per step.

Under a sliding window, shards whose whole local slice provably falls outside
every row's window skip the kernel call entirely (``lax.cond``): the skip
branch returns the exact empty-band result (o = 0, lse = NEG_INF), so the
psum combine is bitwise-unchanged.  The bound is shard-uniform — one window
start per shard, rounded down over the batch (min over rows, floored to a
stripe multiple) — so pruning never depends on a single row's depth.

Both decode entries take a ``kernel`` selector:

  * ``"gather"`` / ``"band"`` (the defaults) — the original paths: paged
    gathers the row's pages into a dense local view, then both run the band
    kernel (one vmapped call per row under vector pos).
  * ``"native"`` — the paged Pallas kernel (``kernels/paged_decode.py``)
    reads the block table in-kernel and indexes the page pool directly; the
    dense cache routes through the SAME kernel by viewing each ``[m]`` row as
    one implicit page run (reshape + identity block table).  Falls back to
    the gather/band oracle under ``REPRO_KERNELS=ref``.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import kv_quant
from repro.kernels import ops
from repro.kernels import paged_decode as pk
from repro.kernels.ref import BAND_INF, NEG_INF

__all__ = [
    "sharded_cache_decode",
    "sharded_cache_update",
    "sharded_cache_chunk_update",
    "sharded_cache_chunk_decode",
    "paged_cache_decode",
    "paged_cache_update",
    "paged_cache_chunk_update",
    "paged_cache_chunk_decode",
]


def _owner_slot(pos, i, n: int, m: int, layout: str):
    """(is_owner, slot) for writing global position ``pos``; m = local slots."""
    if layout == "striped":
        return (pos % n) == i, pos // n
    return (pos // m) == i, pos % m


def sharded_cache_update(
    k_cache: jnp.ndarray,  # [L, B, m, Hkv, D] local slices, stacked over layers
    v_cache: jnp.ndarray,
    k_new: jnp.ndarray,  # [B, 1, Hkv, D] replicated across the axis
    v_new: jnp.ndarray,
    pos,  # int32 scalar or [B] vector: global position(s) being written
    layer,  # int32: the layer written
    axis_name: Optional[str],
    n: int,
    layout: str = "striped",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Each batch row scatters its new token into its own slot of layer
    ``layer``; rows this shard does not own, and rows past capacity
    (retired slots still ticking), are dropped by the scatter."""
    i = lax.axis_index(axis_name) if axis_name is not None else 0
    m = k_cache.shape[2]
    B = k_new.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    is_owner, slot = _owner_slot(pos, i, n, m, layout)
    write = is_owner & (pos < n * m)
    slot = jnp.clip(slot, 0, m - 1)
    # out-of-range batch index -> scatter drops the row entirely
    b_idx = jnp.where(write, jnp.arange(B), B)
    out = []
    for cache, new in ((k_cache, k_new), (v_cache, v_new)):
        out.append(cache.at[layer, b_idx, slot].set(new[:, 0].astype(cache.dtype), mode="drop"))
    return out[0], out[1]


def _shard_geometry(i, n: int, m: int, layout: str):
    """(kv_offset, stride) of local slot s -> global position for the band."""
    if layout == "striped":
        return i, n
    return i * m, 1


def _window_nonempty(pos, i, n: int, m: int, layout: str, window: int):
    """Shard-uniform visibility: can ANY local slot of this shard fall inside
    ANY row's window [pos - window + 1, pos]?  The window start is rounded
    DOWN over the batch (min over rows, then floored to a multiple of n) so
    the bound is uniform per shard — conservative: errs toward computing."""
    pos = jnp.asarray(pos, jnp.int32)
    hi_pos = jnp.max(pos)  # newest visible position over the batch
    lo_pos = jnp.maximum(jnp.min(pos) - (window - 1), 0)
    lo_pos = (lo_pos // n) * n  # shard-uniform round-down
    if layout == "striped":
        # shard i holds positions i, i+n, ...: visible iff some j >= 0 with
        # i + n*j in [lo_pos, hi_pos] and j < m
        lo_j = (lo_pos - i + n - 1) // n
        hi_j = (hi_pos - i) // n
        lo_j = jnp.maximum(lo_j, 0)
        return (hi_j >= lo_j) & (lo_j < m) & (hi_pos >= i)
    # contiguous: shard i holds [i*m, (i+1)*m)
    return (i * m <= hi_pos) & ((i + 1) * m - 1 >= lo_pos)


def _psum_combine(o, lse, axis_name: Optional[str], q_dtype):
    """lse-weighted psum of per-shard partials (softmax over disjoint KV)."""
    if axis_name is None:
        return o.astype(q_dtype)
    mx = lax.pmax(lse, axis_name)  # [B, H, 1]
    mx = jnp.maximum(mx, NEG_INF)
    w = jnp.exp(lse - mx)  # zero for empty shards
    num = lax.psum(o.astype(jnp.float32) * w.swapaxes(1, 2)[..., None], axis_name)
    den = lax.psum(w, axis_name)
    den_safe = jnp.where(den > 0, den, 1.0)
    out = num / den_safe.swapaxes(1, 2)[..., None]
    return out.astype(q_dtype)


def _banded_partial(q, k_loc, v_loc, pos, kv_off, stride_kv, hi, scale):
    """Per-shard partial flash-decode; scalar pos batches the kernel call,
    vector pos maps it over rows (the band's q offset differs per row)."""
    if pos.ndim == 0:
        band = jnp.stack(
            [pos, jnp.asarray(kv_off, jnp.int32), jnp.int32(0), jnp.int32(hi)]
        )
        return ops.block_attention(
            q, k_loc, v_loc, band, scale=scale, stride_q=1, stride_kv=stride_kv
        )

    def one(qb, kb, vb, pb):
        band = jnp.stack(
            [pb, jnp.asarray(kv_off, jnp.int32), jnp.int32(0), jnp.int32(hi)]
        )
        ob, lb = ops.block_attention(
            qb[None], kb[None], vb[None], band,
            scale=scale, stride_q=1, stride_kv=stride_kv,
        )
        return ob[0], lb[0]

    return jax.vmap(one)(q, k_loc, v_loc, pos)


def _maybe_pruned(run, q, pos, i, n, m, layout, window, prune):
    """Wrap a shard-partial thunk in the window-prune ``lax.cond``: the kernel
    call is skipped when a sliding window provably hides every local slot.
    The skip branch returns the EXACT empty-band kernel result (o = 0,
    lse = NEG_INF), so downstream combines are bitwise-identical to the
    unpruned program."""
    if not (prune and window):
        return run(None)

    B, S, H = q.shape[0], q.shape[1], q.shape[2]

    def skip(_):
        return (
            jnp.zeros(q.shape, q.dtype),
            jnp.full((B, H, S), NEG_INF, jnp.float32),
        )

    return lax.cond(_window_nonempty(pos, i, n, m, layout, window), run, skip, None)


def _maybe_pruned_partial(
    q, k_loc, v_loc, pos, i, n, m, layout, window, scale, prune,
):
    kv_off, stride_kv = _shard_geometry(i, n, m, layout)
    hi = (window - 1) if window else BAND_INF

    def run(_):
        return _banded_partial(q, k_loc, v_loc, pos, kv_off, stride_kv, hi, scale)

    return _maybe_pruned(run, q, pos, i, n, m, layout, window, prune)


def _native_enabled(kernel: str) -> bool:
    """The paged kernel serves ``kernel="native"`` except under the pure-jnp
    oracle backend, where the gather/band path (the exact reference the kernel
    is validated against) stands in."""
    if kernel in ("gather", "band"):
        return False
    if kernel != "native":
        raise ValueError(f"unknown decode kernel {kernel!r}")
    return ops.current_backend() != "ref"


def sharded_cache_decode(
    q: jnp.ndarray,  # [B, 1, H, D] new token's query, replicated over the axis
    k_cache: jnp.ndarray,  # [L, B, m, Hkv, D] local slices, stacked over layers
    v_cache: jnp.ndarray,
    pos,  # int32 scalar or [B] vector: current position(s); attends to <= pos
    layer,  # int32: the layer read
    axis_name: Optional[str],
    n: int,
    *,
    layout: str = "striped",
    window: Optional[int] = None,
    scale: Optional[float] = None,
    prune: bool = True,
    kernel: str = "band",  # band | native (paged kernel over implicit page runs)
) -> jnp.ndarray:
    """One decode step: partial attention per shard + lse-weighted psum.

    ``kernel="native"`` views each row's dense slice as ONE implicit page run
    (a free reshape of the stack + identity block table) and runs the paged
    kernel at ``layer`` — same band math, no per-row vmap, each row walks
    only its visible pages.
    """
    i = lax.axis_index(axis_name) if axis_name is not None else 0
    L, B, m, hkv, d = k_cache.shape
    pos = jnp.asarray(pos, jnp.int32)
    if _native_enabled(kernel):
        chunk = pk.dense_chunk_for(m)
        chunks = m // chunk
        kv_off, stride_kv = _shard_geometry(i, n, m, layout)
        k_pool = k_cache.reshape(L, B * chunks, chunk, hkv, d)
        v_pool = v_cache.reshape(L, B * chunks, chunk, hkv, v_cache.shape[-1])
        bt = jnp.arange(B * chunks, dtype=jnp.int32).reshape(B, chunks)

        def run(_):
            return pk.paged_flash_decode(
                q, k_pool, v_pool, bt, pos, kv_off, layer,
                stride_kv=stride_kv, window=window, scale=scale,
            )

        o, lse = _maybe_pruned(run, q, pos, i, n, m, layout, window, prune)
    else:
        o, lse = _maybe_pruned_partial(
            q, k_cache[layer], v_cache[layer], pos, i, n, m, layout, window, scale, prune
        )
    return _psum_combine(o, lse, axis_name, q.dtype)


# --------------------------------------------------------------------------
# paged cache: physical page pool + block table (serve/kv_pool.py allocator)
# --------------------------------------------------------------------------
#
# Quantized pools: when the pool dtype is int8 / fp8-e4m3 a fp32 scale table
# [L, num_pages, page_size, Hkv] rides next to each pool, indexed by the SAME
# (page, offset) the pool scatter/gather uses.  Scales are per token per
# kv-head (amax over D only — see core/kv_quant.py), so every write path
# (decode append, chunk prefill, speculative verify) quantizes its new
# tokens independently and never re-quantizes resident positions.  The
# update entries quantize when handed scale tables; the gather oracle
# dequantizes; the native kernel dequantizes in VMEM after each page's DMA.


def _page_coords(pos, i, n: int, page_size: int, max_pages: int, layout: str):
    """Owner shard -> (logical page, offset) for global position ``pos``.
    The paged analogue of ``_owner_slot``: the dense local slot j just splits
    into (j // page_size, j % page_size)."""
    m = max_pages * page_size  # virtual local capacity
    is_owner, j = _owner_slot(pos, i, n, m, layout)
    return is_owner & (pos < n * m), j // page_size, j % page_size


def paged_cache_update(
    k_pool: jnp.ndarray,  # [L, num_pages, page_size, Hkv, D] local page pools
    v_pool: jnp.ndarray,
    k_new: jnp.ndarray,  # [B, 1, Hkv, D] replicated across the axis
    v_new: jnp.ndarray,
    block_table: jnp.ndarray,  # [B, max_pages] int32; -1 = unallocated
    pos,  # int32 scalar or [B] vector
    layer,  # int32: the layer written
    axis_name: Optional[str],
    n: int,
    layout: str = "striped",
    k_scale: Optional[jnp.ndarray] = None,  # [L, num_pages, page_size, Hkv] f32
    v_scale: Optional[jnp.ndarray] = None,
):
    """Scatter-by-block-table append: owner shard -> (page, offset) of layer
    ``layer``, one scatter into the stacked pool.  Rows past virtual
    capacity or pointing at unallocated pages are dropped (the allocator
    only hands live slots a writable tail page).  With scale tables the new
    token is quantized to the pool dtype and its per-(token, head) scales
    scatter through the SAME coordinates; returns a 4-tuple ``(k_pool,
    v_pool, k_scale, v_scale)`` in that case."""
    i = lax.axis_index(axis_name) if axis_name is not None else 0
    num_pages, page_size = k_pool.shape[1], k_pool.shape[2]
    max_pages = block_table.shape[1]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (k_new.shape[0],))
    write, lp, off = _page_coords(pos, i, n, page_size, max_pages, layout)
    lp = jnp.clip(lp, 0, max_pages - 1)
    b = jnp.arange(k_new.shape[0])
    phys = block_table[b, lp]
    write = write & (phys >= 0)
    # out-of-range page index -> scatter drops the row entirely
    page_idx = jnp.where(write, phys, num_pages)
    return _store_pages(
        k_pool, v_pool, k_scale, v_scale, (layer, page_idx, off), k_new[:, 0], v_new[:, 0]
    )


def _store_pages(k_pool, v_pool, k_scale, v_scale, index, k_new, v_new):
    """``kv_quant.store`` on the pools (and a quantized pool's scales):
    returns ``(k_pool, v_pool)`` or ``(k_pool, v_pool, k_scale, v_scale)``."""
    kv = {"k": k_pool, "v": v_pool}
    if k_scale is not None:
        kv.update(k_scale=k_scale, v_scale=v_scale)
    kv = kv_quant.store(kv, index, k_new, v_new)
    return tuple(kv[name] for name in ("k", "v", "k_scale", "v_scale") if name in kv)


def paged_cache_gather(k_pool, v_pool, block_table, layer, k_scale=None, v_scale=None):
    """Materialize each row's dense local view of layer ``layer`` from its
    pages: [B, m, Hkv, D] with m = max_pages * page_size, in the SAME
    local-position order as the dense cache slice (so the band math is
    shared verbatim); one gather at ``[layer, pages]`` of the stacked pool.
    Unallocated pages clamp to page 0 — whatever is there is hidden behind
    the band.  Quantized pools (scale tables passed) gather scales through
    the same index and dequantize to fp32 — the reference path for
    REPRO_KERNELS=ref and non-Pallas platforms."""
    num_pages = k_pool.shape[1]
    idx = jnp.clip(block_table, 0, num_pages - 1)  # [B, max_pages]
    out = []
    for pool, scales in ((k_pool, k_scale), (v_pool, v_scale)):
        pages = pool[layer, idx]  # [B, max_pages, page_size, Hkv, D]
        if scales is not None:
            pages = kv_quant.dequantize(pages, scales[layer, idx])
        out.append(pages.reshape((idx.shape[0], -1) + pool.shape[3:]))
    return out[0], out[1]


def paged_cache_decode(
    q: jnp.ndarray,  # [B, 1, H, D] replicated over the axis
    k_pool: jnp.ndarray,  # [L, num_pages, page_size, Hkv, D] local page pools
    v_pool: jnp.ndarray,
    block_table: jnp.ndarray,  # [B, max_pages] int32
    pos,  # int32 scalar or [B] vector
    layer,  # int32: the layer read
    axis_name: Optional[str],
    n: int,
    *,
    layout: str = "striped",
    window: Optional[int] = None,
    scale: Optional[float] = None,
    prune: bool = True,
    kernel: str = "gather",  # gather | native (block table read in-kernel)
    k_scale: Optional[jnp.ndarray] = None,  # [L, num_pages, page_size, Hkv] f32
    v_scale: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Paged decode partial + psum combine.  ``kernel="gather"`` materializes
    each row's dense local view from its pages and runs the identical banded
    partial the dense path uses (the correctness oracle); ``"native"`` hands
    the stacked pool, the layer and the block table straight to the paged
    Pallas kernel — no gathered intermediate, HBM traffic follows allocated
    depth.  Quantized pools hand their scale tables along: the native kernel
    dequantizes in VMEM after each page's DMA, the gather path dequantizes
    in the gather."""
    i = lax.axis_index(axis_name) if axis_name is not None else 0
    page_size, max_pages = k_pool.shape[2], block_table.shape[1]
    m = max_pages * page_size
    pos = jnp.asarray(pos, jnp.int32)
    if _native_enabled(kernel):
        kv_off, stride_kv = _shard_geometry(i, n, m, layout)

        def run(_):
            return pk.paged_flash_decode(
                q, k_pool, v_pool, block_table, pos, kv_off, layer,
                stride_kv=stride_kv, window=window, scale=scale,
                k_scale=k_scale, v_scale=v_scale,
            )

        o, lse = _maybe_pruned(run, q, pos, i, n, m, layout, window, prune)
    else:
        k_loc, v_loc = paged_cache_gather(
            k_pool, v_pool, block_table, layer, k_scale, v_scale
        )
        o, lse = _maybe_pruned_partial(
            q, k_loc, v_loc, pos, i, n, m, layout, window, scale, prune
        )
    return _psum_combine(o, lse, axis_name, q.dtype)


# --------------------------------------------------------------------------
# chunked prefill: multi-token append + prefix-causal chunk attention
# --------------------------------------------------------------------------
#
# Continuous prefill feeds a prompt into a live slot C tokens at a time.  A
# chunk is just C consecutive decode writes batched into one launch: row b
# scatters positions starts[b] .. starts[b]+lens[b]-1 through the SAME
# owner/stripe math the single-token path uses, and the chunk's attention is
# the same banded partial with a multi-row q — row i of the chunk sits at
# global position starts[b]+i, so band = (starts[b], kv_off, 0, hi) with
# stride_q=1 is exactly prefix-causal over resident positions.  Pad rows
# (i >= lens[b]) compute garbage but never write; softmax is per-row so they
# cannot contaminate real rows.


def sharded_cache_chunk_update(
    k_cache: jnp.ndarray,  # [L, B, m, Hkv, D] local slices, stacked over layers
    v_cache: jnp.ndarray,
    k_new: jnp.ndarray,  # [B, C, Hkv, D] replicated across the axis
    v_new: jnp.ndarray,
    starts: jnp.ndarray,  # [B] int32: global position of each row's chunk base
    lens: jnp.ndarray,  # [B] int32: valid tokens per row (0 = inactive row)
    write_starts: jnp.ndarray,  # [B] int32: skip writes below this position
    layer,  # int32: the layer written
    axis_name: Optional[str],
    n: int,
    layout: str = "striped",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Scatter a C-token chunk per row into layer ``layer``'s local cache
    slice.  Positions below ``write_starts`` (a shared prefix already
    resident) and at/after ``starts + lens`` are dropped; distinct owned
    positions of one row map to distinct local slots, so the scatter has no
    duplicate coordinates."""
    i = lax.axis_index(axis_name) if axis_name is not None else 0
    B, C = k_new.shape[0], k_new.shape[1]
    m = k_cache.shape[2]
    starts = jnp.asarray(starts, jnp.int32)
    c = jnp.arange(C, dtype=jnp.int32)
    pos = starts[:, None] + c[None, :]  # [B, C]
    is_owner, slot = _owner_slot(pos, i, n, m, layout)
    write = (
        is_owner
        & (c[None, :] < lens[:, None])
        & (pos >= write_starts[:, None])
        & (pos < n * m)
    )
    slot = jnp.clip(slot, 0, m - 1)
    b = jnp.broadcast_to(jnp.arange(B)[:, None], (B, C))
    # out-of-range batch index -> scatter drops the element entirely
    b_idx = jnp.where(write, b, B)
    out = []
    for cache, new in ((k_cache, k_new), (v_cache, v_new)):
        out.append(
            cache.at[layer, b_idx, slot].set(new.astype(cache.dtype), mode="drop")
        )
    return out[0], out[1]


def _chunk_banded_partial(q, k_loc, v_loc, starts, kv_off, stride_kv, hi, scale):
    """Per-shard partial for a [B, C, H, D] chunk: one banded kernel call per
    row, with the band's q offset at that row's chunk base."""

    def one(qb, kb, vb, sb):
        band = jnp.stack(
            [sb, jnp.asarray(kv_off, jnp.int32), jnp.int32(0), jnp.int32(hi)]
        )
        ob, lb = ops.block_attention(
            qb[None], kb[None], vb[None], band,
            scale=scale, stride_q=1, stride_kv=stride_kv,
        )
        return ob[0], lb[0]

    return jax.vmap(one)(q, k_loc, v_loc, starts)


def sharded_cache_chunk_decode(
    q: jnp.ndarray,  # [B, C, H, D] chunk queries, replicated over the axis
    k_cache: jnp.ndarray,  # [L, B, m, Hkv, D] local slices (chunk already written)
    v_cache: jnp.ndarray,
    starts,  # int32 [B]: global position of each row's chunk base
    layer,  # int32: the layer read
    axis_name: Optional[str],
    n: int,
    *,
    layout: str = "striped",
    window: Optional[int] = None,
    scale: Optional[float] = None,
    prune: bool = True,
) -> jnp.ndarray:
    """Prefix-causal chunk attention: row i of the chunk attends to global
    positions <= starts + i (within the window).  Same partial + psum combine
    as single-token decode; the window-prune bound widens by C - 1 because the
    oldest row's window starts C - 1 earlier than the newest's."""
    i = lax.axis_index(axis_name) if axis_name is not None else 0
    m = k_cache.shape[2]
    C = q.shape[1]
    starts = jnp.asarray(starts, jnp.int32)
    kv_off, stride_kv = _shard_geometry(i, n, m, layout)
    hi = (window - 1) if window else BAND_INF

    def run(_):
        return _chunk_banded_partial(
            q, k_cache[layer], v_cache[layer], starts, kv_off, stride_kv, hi, scale
        )

    win_eff = (window + C - 1) if window else None
    o, lse = _maybe_pruned(run, q, starts + (C - 1), i, n, m, layout, win_eff, prune)
    return _psum_combine(o, lse, axis_name, q.dtype)


def paged_cache_chunk_update(
    k_pool: jnp.ndarray,  # [L, num_pages, page_size, Hkv, D] local page pools
    v_pool: jnp.ndarray,
    k_new: jnp.ndarray,  # [B, C, Hkv, D] replicated across the axis
    v_new: jnp.ndarray,
    block_table: jnp.ndarray,  # [B, max_pages] int32; -1 = unallocated
    starts: jnp.ndarray,  # [B] int32
    lens: jnp.ndarray,  # [B] int32 (0 = inactive row)
    write_starts: jnp.ndarray,  # [B] int32: skip writes below this position
    layer,  # int32: the layer written
    axis_name: Optional[str],
    n: int,
    layout: str = "striped",
    k_scale: Optional[jnp.ndarray] = None,  # [L, num_pages, page_size, Hkv] f32
    v_scale: Optional[jnp.ndarray] = None,
):
    """Chunk append through the block table into layer ``layer``: the
    allocator pre-books every prompt page at admission, so a chunk never
    lands on an unallocated page; shared-prefix positions (below
    ``write_starts``) are skipped so CoW pages are never touched
    mid-prefill.  With scale tables (quantized pool) each chunk token
    quantizes independently — per-(token, head) scales mean resident
    positions are never re-quantized — and continuous prefill + speculative
    verify write quantized exactly like decode.  Returns a 4-tuple
    ``(k_pool, v_pool, k_scale, v_scale)`` in that case."""
    i = lax.axis_index(axis_name) if axis_name is not None else 0
    num_pages, page_size = k_pool.shape[1], k_pool.shape[2]
    max_pages = block_table.shape[1]
    B, C = k_new.shape[0], k_new.shape[1]
    starts = jnp.asarray(starts, jnp.int32)
    c = jnp.arange(C, dtype=jnp.int32)
    pos = starts[:, None] + c[None, :]  # [B, C]
    write, lp, off = _page_coords(pos, i, n, page_size, max_pages, layout)
    write = write & (c[None, :] < lens[:, None]) & (pos >= write_starts[:, None])
    lp = jnp.clip(lp, 0, max_pages - 1)
    b = jnp.broadcast_to(jnp.arange(B)[:, None], (B, C))
    phys = block_table[b, lp]
    write = write & (phys >= 0)
    page_idx = jnp.where(write, phys, num_pages)
    return _store_pages(k_pool, v_pool, k_scale, v_scale, (layer, page_idx, off), k_new, v_new)


def paged_cache_chunk_decode(
    q: jnp.ndarray,  # [B, C, H, D] replicated over the axis
    k_pool: jnp.ndarray,  # [L, num_pages, page_size, Hkv, D] local page pools
    v_pool: jnp.ndarray,
    block_table: jnp.ndarray,  # [B, max_pages] int32
    starts,  # int32 [B]
    layer,  # int32: the layer read
    axis_name: Optional[str],
    n: int,
    *,
    layout: str = "striped",
    window: Optional[int] = None,
    scale: Optional[float] = None,
    prune: bool = True,
    k_scale: Optional[jnp.ndarray] = None,  # [L, num_pages, page_size, Hkv] f32
    v_scale: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Paged chunk attention: gather the row's pages of layer ``layer`` (one
    gather at ``[layer, pages]``) into the dense local view
    and run the identical banded chunk partial (chunks are a prefill-side
    path — the paged decode kernel stays single-token).  Quantized pools
    dequantize in the gather."""
    i = lax.axis_index(axis_name) if axis_name is not None else 0
    page_size, max_pages = k_pool.shape[2], block_table.shape[1]
    m = max_pages * page_size
    C = q.shape[1]
    starts = jnp.asarray(starts, jnp.int32)
    k_loc, v_loc = paged_cache_gather(
        k_pool, v_pool, block_table, layer, k_scale, v_scale
    )
    kv_off, stride_kv = _shard_geometry(i, n, m, layout)
    hi = (window - 1) if window else BAND_INF

    def run(_):
        return _chunk_banded_partial(q, k_loc, v_loc, starts, kv_off, stride_kv, hi, scale)

    win_eff = (window + C - 1) if window else None
    o, lse = _maybe_pruned(run, q, starts + (C - 1), i, n, m, layout, win_eff, prune)
    return _psum_combine(o, lse, axis_name, q.dtype)


# backwards-compatible aliases (striped is the default layout)
striped_cache_update = sharded_cache_update
striped_cache_decode = sharded_cache_decode
