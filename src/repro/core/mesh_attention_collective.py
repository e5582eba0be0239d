"""Algorithm-1 collective mode: Mesh-Attention with XLA-native collectives.

The paper's Algorithm 1 states the functional flow as whole-group
collectives (all-gather Q in the Q group, all-gather KV in the KV group,
blockwise compute, reduce-scatter O with online-softmax as the reduce
operator) and §3.4 then *decomposes* them into ring P2P steps for
overlapping.  On meshes that expose the tile factors as REAL axes
(e.g. ``(data, aq, akv)``), this module implements Algorithm 1 directly with
``lax.all_gather`` / ``lax.psum_scatter`` — XLA's async collectives then do
their own overlapping.  It serves as:

  * a cross-check of the ring decomposition (same math, different comm),
  * an alternative production configuration for §Perf comparisons (XLA can
    sometimes schedule few large collectives better than many small ones),
  * the natural expression of the paper's "wrap-around mesh" on a physical
    2-D TPU slice.

Chunk layout: the sequence is sharded over the combined ("aq","akv") axes in
row-major order, so device (x, y) holds global chunk c = x·b + y.  Its
gathered Q set is the column-residue class {x'·b + y} and its KV set the row
band {x·b + y'} — each AM block is computed exactly once and the local Q-KV
property holds by construction (c is in both sets).  The lse-weighted
reduce-scatter over "aq" returns each device exactly its own chunk's output.

Differentiable by plain autodiff (XLA transposes the collectives); the
ring-mode custom_vjp remains the paper-faithful backward.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
from jax import lax

from repro.core import schedule as S
from repro.core.mesh_attention import join_halves
from repro.kernels import ops
from repro.kernels.ref import BAND_INF, NEG_INF

__all__ = ["mesh_attention_collective"]


def mesh_attention_collective(
    q: jnp.ndarray,  # [B, m, H, D] local chunk
    k: jnp.ndarray,  # [B, m, Hkv, D]
    v: jnp.ndarray,
    q_axis: str,  # mesh axis carrying the tile height a
    kv_axis: str,  # mesh axis carrying the tile width b
    *,
    causal: bool = False,
    window: Optional[int] = None,
    layout: str = "striped",
    scale: Optional[float] = None,
    block_q: int = 128,
    block_kv: int = 128,
    mask=None,  # Optional[MaskSpec]; supersedes causal/window
    seg: Optional[jnp.ndarray] = None,  # [m] int32 local segment-id chunk
    comm_overlap: str = "overlap",  # schedule.COMM_OVERLAP_MODES; collective
    # mode has no step pipeline, so the knob maps onto the gathers: serial
    # barriers compute on every gather, bidir splits each all-gather into a
    # half-payload pair (both ring directions of the axis).  Reductions
    # (psum_scatter, the lse all-gather feeding one) are never split — only
    # pure transport is, which keeps all three modes bitwise-equal.
) -> jnp.ndarray:
    S.validate_comm_overlap(comm_overlap)
    a = lax.psum(1, q_axis)
    b = lax.psum(1, kv_axis)
    n = a * b
    x = lax.axis_index(q_axis)
    y = lax.axis_index(kv_axis)
    m = q.shape[1]
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if mask is not None:
        causal = mask.is_causal
        window = mask.window
        if mask.needs_segments and seg is None:
            raise ValueError(f"mask kind {mask.kind!r} needs a segment-id operand")

    def gather(x, axis):
        if comm_overlap != "bidir" or x.ndim == 0 or x.shape[-1] < 2:
            return lax.all_gather(x, axis)
        h = x.shape[-1] // 2
        lo = lax.all_gather(x[..., :h], axis)
        hi_half = lax.all_gather(x[..., h:], axis)
        return join_halves(lo, hi_half)

    # Algorithm 1 lines 1-2: group all-gathers
    qs = gather(q, q_axis)  # [a, B, m, H, D]
    ks = gather(k, kv_axis)  # [b, B, m, Hkv, D]
    vs = gather(v, kv_axis)
    seg_qs = seg_ks = None
    if seg is not None:
        seg = jnp.asarray(seg, jnp.int32)
        seg_qs = gather(seg, q_axis)  # [a, m]
        seg_ks = gather(seg, kv_axis)  # [b, m]
    if comm_overlap == "serial":
        # pin the gathers ahead of the blockwise compute (identity on values)
        gathered = (qs, ks, vs) + ((seg_qs, seg_ks) if seg is not None else ())
        barr = lax.optimization_barrier(gathered)
        qs, ks, vs = barr[0], barr[1], barr[2]
        if seg is not None:
            seg_qs, seg_ks = barr[3], barr[4]

    hi = (window - 1) if (causal and window) else BAND_INF

    def band_for(u, w_):
        if not causal:
            return jnp.asarray([0, 0, -BAND_INF, BAND_INF], jnp.int32), 1, 1
        qc = u * b + y  # global chunk ids under the row-major layout
        kc = x * b + w_
        if layout == "striped":
            off_q, off_kv, s = qc, kc, n
        else:
            off_q, off_kv, s = qc * m, kc * m, 1
        return (
            jnp.stack([off_q.astype(jnp.int32), off_kv.astype(jnp.int32),
                       jnp.int32(0), jnp.int32(hi)]),
            s, s,
        )

    # Algorithm 1 line 3: blockwise compute with online-softmax accumulation
    o_rows = []
    lse_rows = []
    for u in range(a):
        acc_o = None
        acc_l = None
        for w_ in range(b):
            band, sq, skv = band_for(jnp.asarray(u), jnp.asarray(w_))
            o_b, l_b = ops.block_attention(
                qs[u], ks[w_], vs[w_], band,
                scale=scale, stride_q=sq, stride_kv=skv,
                block_q=block_q, block_kv=block_kv,
                seg_q=None if seg_qs is None else seg_qs[u],
                seg_kv=None if seg_ks is None else seg_ks[w_],
            )
            o_b = o_b.astype(jnp.float32)
            l_b = l_b.astype(jnp.float32)
            if acc_o is None:
                acc_o, acc_l = o_b, l_b
            else:
                mx = jnp.maximum(jnp.maximum(acc_l, l_b), NEG_INF)
                w1 = jnp.exp(acc_l - mx)
                w2 = jnp.exp(l_b - mx)
                tot = jnp.where(w1 + w2 > 0, w1 + w2, 1.0)
                acc_o = (acc_o * (w1 / tot).swapaxes(1, 2)[..., None]
                         + o_b * (w2 / tot).swapaxes(1, 2)[..., None])
                acc_l = jnp.where(w1 + w2 > 0, mx + jnp.log(tot), NEG_INF)
        o_rows.append(acc_o)
        lse_rows.append(acc_l)

    o_stack = jnp.stack(o_rows)  # [a, B, m, H, D] partials for my Q set
    lse_stack = jnp.stack(lse_rows)  # [a, B, H, m]

    # Algorithm 1 line 4: reduce-scatter with online softmax as the reducer.
    # Combine lse across the Q group first (tiny), then psum_scatter the
    # rescaled partials so device x receives exactly its own chunk (slot x).
    lse_all = lax.all_gather(lse_stack, q_axis)  # [a(dev), a(slot), B, H, m]
    mx = jnp.maximum(jnp.max(lse_all, axis=0), NEG_INF)  # [a, B, H, m]
    den = jnp.sum(jnp.exp(lse_all - mx[None]), axis=0)
    den = jnp.where(den > 0, den, 1.0)
    w = jnp.exp(lse_stack - mx) / den  # my weight for each slot
    o_weighted = o_stack * w.swapaxes(2, 3)[..., None]  # [a, B, m, H, D]
    # untiled: slot dim removed; device x receives the reduced slot x = its chunk
    o_mine = lax.psum_scatter(o_weighted, q_axis, scatter_dimension=0, tiled=False)
    return o_mine.astype(q.dtype)
