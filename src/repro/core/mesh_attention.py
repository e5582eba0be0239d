"""Mesh-Attention: the distributed attention op (paper §3).

Runs INSIDE ``shard_map``: every array argument is the device-local chunk
(sequence sharded n ways over ``cfg.axis_name``; causal inputs must be in the
*striped* layout of ``core.tiling.stripe_permutation``).  The op executes the
greedy step program from ``core/schedule.py`` verbatim:

  * ``Recv Q`` / ``Recv KV``  -> one ``jax.lax.ppermute`` per step on the
    Q-ring / KV-ring neighbour shifts (``TileLayout.q_shift_perm`` /
    ``kv_shift_perm``).  Chunk u arrives after u hops (Table 1).
  * compute block (u, v)      -> one Pallas flash block between Q slot u and
    KV slot v, accumulated into the row's (o, lse) with the online-softmax
    combine.  Striped-causal masking uses the *global* chunk indices, which
    depend on ``axis_index`` — they enter the kernel as dynamic SMEM scalars.
  * ``Send O``  (step t)      -> ppermute the completed row t+1 partial to
    the lower Q-ring neighbour; fold the received row (t+2 mod a) partial in
    (online softmax as the reduce operator, Alg. 1 line 4).

Backward (Alg. 3) is a custom_vjp at this level — the paper's communication
pattern (circulate OdOQ + KV, reduce dQ along the Q ring and dKV along the
KV ring with plain sums) — so JAX never auto-differentiates the ring code.

``a = 1`` degenerates to Ring-Attention (no Q ring, no O sends): the baseline
is literally a config choice, as in the paper ("covers Ring-Attention as a
special case").

Both executors run each step as an issue/compute/commit pipeline governed by
``cfg.comm_overlap`` (see ``schedule.COMM_OVERLAP_MODES``): the step's ring
permutes are emitted ahead of its flash blocks and only land in their slots
at step end, so in ``overlap`` mode (default) the transfer is in flight while
the blocks run; ``serial`` barriers the blocks on the transfers (the naive
baseline the cost model prices as comm+compute); ``bidir`` splits every hop
into a half-payload ppermute pair over both ring directions (TokenRing,
PAPERS.md).  All three modes are BITWISE-equal — only transport routing and
HLO ordering differ (dist_check ``overlap_exact``).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import schedule as S
from repro.core.masking import MaskSpec
from repro.core.tiling import TileLayout
from repro.kernels import ops
from repro.kernels.ref import BAND_INF, NEG_INF

__all__ = ["MeshAttentionConfig", "mesh_attention", "mesh_attention_with_lse"]


@dataclasses.dataclass(frozen=True)
class MeshAttentionConfig:
    """Static configuration (hashable: it is a nondiff custom_vjp argument).

    The mask is a first-class :class:`MaskSpec`; the legacy ``causal`` /
    ``window`` booleans remain as a back-compat construction shim and are
    normalized through :meth:`mask_spec`.
    """

    axis_name: str
    n: int  # devices on the sequence-parallel axis
    a: int  # tile height; b = n // a; a=1 == Ring-Attention
    causal: bool = False
    window: Optional[int] = None  # sliding-window width (causal only)
    layout: str = "striped"  # striped (paper §3.7) | contiguous (SSM/hybrid)
    scale: Optional[float] = None
    fwd_schedule: Optional[S.Schedule] = None
    bwd_schedule: Optional[S.Schedule] = None
    bwd_wire: str = "qdod"  # "odoq" = paper wire (circulates O); "qdod" = Δ-trick
    block_q: int = 128
    block_kv: int = 128
    allow_concurrent_rings: bool = False
    mask: Optional[MaskSpec] = None  # takes precedence over causal/window
    # how each step's ring permutes are ordered against its compute blocks
    # (schedule.COMM_OVERLAP_MODES): serial barriers them onto the critical
    # path, overlap leaves them in flight during the blocks (double-buffered
    # slots), bidir additionally splits each hop into a half-payload pair on
    # both ring directions.  All three are bitwise-equal.
    comm_overlap: str = "overlap"

    def __post_init__(self):
        S.validate_comm_overlap(self.comm_overlap)
        if self.n % self.a:
            raise ValueError(f"a={self.a} must divide n={self.n}")
        if self.mask is not None and (self.causal or self.window is not None):
            raise ValueError("pass either mask= or the legacy causal/window flags, not both")
        if self.window is not None and not self.causal:
            raise ValueError("sliding window requires causal=True")
        if self.bwd_wire not in ("odoq", "qdod"):
            raise ValueError(self.bwd_wire)
        if self.layout not in ("striped", "contiguous"):
            raise ValueError(self.layout)

    @property
    def b(self) -> int:
        return self.n // self.a

    def mask_spec(self) -> MaskSpec:
        if self.mask is not None:
            return self.mask
        return MaskSpec.from_flags(self.causal, self.window)

    def schedules(self, seq: Optional[int] = None) -> Tuple[S.Schedule, S.Schedule]:
        """(fwd, bwd) schedules, mask-pruned when the mask proves slot blocks
        empty on every device.  ``seq`` is the GLOBAL sequence length (needed
        to classify window/document blocks; None skips pruning)."""
        skip: frozenset = frozenset()
        if seq is not None:
            skip = self.mask_spec().empty_blocks(
                self.a, self.b, layout=self.layout, n=self.n, seq=seq
            )
        fwd = self.fwd_schedule or S.greedy_forward_schedule(
            self.a, self.b, allow_concurrent_rings=self.allow_concurrent_rings,
            skip_blocks=skip,
        )
        bwd = self.bwd_schedule or S.greedy_backward_schedule(
            self.a, self.b, allow_concurrent_rings=self.allow_concurrent_rings,
            skip_blocks=skip,
        )
        if (fwd.a, fwd.b) != (self.a, self.b) or (bwd.a, bwd.b) != (self.a, self.b):
            raise ValueError("schedule shape mismatch with (a, b)")
        for sched in (fwd, bwd):
            # a provided schedule may skip fewer blocks (e.g. an unpruned
            # baseline) but never blocks the mask cannot prove empty
            extra = set(sched.skip) - set(skip) if seq is not None else None
            if extra:
                raise ValueError(f"schedule skips non-empty blocks: {sorted(extra)}")
        S.validate_schedule(fwd)
        S.validate_schedule(bwd)
        return fwd, bwd


# --------------------------------------------------------------------------
# helpers
# --------------------------------------------------------------------------


def _band_for_block(cfg: MeshAttentionConfig, i, u: int, v: int, m_q: int, m_kv: int):
    """Dynamic (axis_index-dependent) band + strides for AM block (u, v).

    striped layout: token t of global chunk c has position c + n*t  (stride n)
    contiguous layout: position c*m + t                              (stride 1)

    The band carries the positional part of the mask (causal / window /
    block-sparse bitmap); segment-id masking composes inside the kernel via
    the seg operands the rings circulate alongside Q and KV.
    """
    spec = cfg.mask_spec()
    if spec.kind == "full":
        band = jnp.asarray([0, 0, -BAND_INF, BAND_INF], jnp.int32)
        return band, 1, 1
    qc = cfg.a * (i // cfg.a) + (i + u) % cfg.a  # global Q chunk (Table 1)
    kc = (i + cfg.a * v) % cfg.n  # global KV chunk (Table 1)
    if spec.kind == "block_sparse":
        # chunk-level bitmap: a visible block is unmasked, an invisible one
        # (kept lock-step because some OTHER device needs it) gets an
        # impossible band (lo > hi) so its partial is exactly empty
        vis = jnp.asarray(spec.bitmap, bool)[qc, kc]
        full = jnp.asarray([0, 0, -BAND_INF, BAND_INF], jnp.int32)
        none = jnp.asarray([0, 0, 1, 0], jnp.int32)
        return jnp.where(vis, full, none), 1, 1
    lo, hi = spec.band()  # causal kinds: 0 <= q_pos - kv_pos (<= window-1)
    if cfg.layout == "striped":
        q_off, kv_off, sq, skv = qc, kc, cfg.n, cfg.n
    else:
        q_off, kv_off, sq, skv = qc * m_q, kc * m_kv, 1, 1
    band = jnp.stack(
        [q_off.astype(jnp.int32), kv_off.astype(jnp.int32), jnp.int32(lo), jnp.int32(hi)]
    )
    return band, sq, skv


def _combine_f32(o1, lse1, o2, lse2):
    """Online-softmax combine with fp32 output accumulators.

    o: [B, S, H, D] fp32; lse: [B, H, S] fp32.
    """
    m = jnp.maximum(jnp.maximum(lse1, lse2), NEG_INF)
    w1 = jnp.exp(lse1 - m)
    w2 = jnp.exp(lse2 - m)
    tot = w1 + w2
    tot_safe = jnp.where(tot > 0, tot, 1.0)
    c1 = (w1 / tot_safe).swapaxes(1, 2)[..., None]
    c2 = (w2 / tot_safe).swapaxes(1, 2)[..., None]
    o = o1 * c1 + o2 * c2
    lse = jnp.where(tot > 0, m + jnp.log(tot_safe), NEG_INF)
    return o, lse


def _merge(acc: Optional[tuple], o, lse):
    o = o.astype(jnp.float32)
    lse = lse.astype(jnp.float32)
    if acc is None:
        return o, lse
    return _combine_f32(acc[0], acc[1], o, lse)


# --------------------------------------------------------------------------
# ring transport (comm_overlap modes)
# --------------------------------------------------------------------------


def _ring_hop(buf, axis_name: str, perm, mode: str):
    """One ring hop of a pytree payload under the comm_overlap mode.

    ``serial``/``overlap``: one ppermute per leaf.  ``bidir``: every leaf is
    split into two half-payloads shipped as a concurrent ppermute pair — the
    TokenRing move (PAPERS.md): two independent transfers the runtime can
    route over both directions of the torus link, so each half moves at full
    per-direction bandwidth.  Reassembly is pure transport
    (``concat(x[..., :h], x[..., h:]) == x``), so downstream compute sees
    bitwise the single-permute payload and total wire bytes are unchanged.
    """
    if mode != "bidir":
        return jax.tree.map(lambda x: lax.ppermute(x, axis_name, perm), buf)

    def hop(x):
        if x.ndim == 0 or x.shape[-1] < 2:  # nothing to split (tiny payload)
            return lax.ppermute(x, axis_name, perm)
        h = x.shape[-1] // 2
        cw = lax.ppermute(x[..., :h], axis_name, perm)
        ccw = lax.ppermute(x[..., h:], axis_name, perm)
        return join_halves(cw, ccw)

    return jax.tree.map(hop, buf)


def join_halves(lo, hi):
    """``concatenate([lo, hi], axis=-1)`` written as a pad and a slice
    update.  XLA compiles the elementwise consumers of a concatenate
    differently from those of one array, and the online-softmax combine
    then rounds differently (1 ulp on the CPU); after a slice update they
    compile as on the single-permute payload, so bidir stays bitwise."""
    axis = lo.ndim - 1
    out = jnp.pad(lo, [(0, 0)] * axis + [(0, hi.shape[-1])])
    return lax.dynamic_update_slice_in_dim(out, hi, lo.shape[-1], axis=axis)


def _after_comms(issued, *operands):
    """``serial`` mode: thread compute operands through an optimization
    barrier with the step's in-flight permute results, so XLA must complete
    the transfers before any of the step's blocks run (the naive
    ppermute-then-compute ordering the serial cost model prices).  Identity
    on values — bitwise-neutral by construction."""
    if not issued:
        return operands
    out = lax.optimization_barrier(tuple(operands) + tuple(issued))
    return out[: len(operands)]


# --------------------------------------------------------------------------
# forward program (Algorithm 2 structure)
# --------------------------------------------------------------------------


def _fwd_program(q, k, v, cfg: MeshAttentionConfig, kv_transform=None, seg=None):
    """kv_transform (beyond-paper, §Perf 'latent wire'): when given, ``k`` is
    an opaque wire buffer (e.g. MLA's compressed latent) circulated on the KV
    ring; it is expanded to per-head (k, v) ONCE per received chunk, at first
    use.  Wire bytes drop from 2·Hkv·dk to the latent width.

    ``seg`` (int32 [S/n], the local chunk of the segment-id array) rides the
    rings alongside Q and KV for document/segment masks; mask-pruned blocks
    are simply absent from the (possibly shorter) schedule, with the send
    counters re-based so the surviving ring reduce stays aligned."""
    n, a, b = cfg.n, cfg.a, cfg.b
    lay = TileLayout(n, a)
    i = lax.axis_index(cfg.axis_name)
    scale = cfg.scale if cfg.scale is not None else q.shape[-1] ** -0.5
    sched, _ = cfg.schedules(n * q.shape[1])

    q_perm = lay.q_shift_perm()
    kv_perm = lay.kv_shift_perm()

    # each slot buffer is (payload, seg-or-None): jax.tree.map ppermutes both
    qs: Dict[int, tuple] = {0: (q, seg)}
    kvs: Dict[int, tuple] = {
        0: (k if kv_transform is not None else jnp.stack([k, v]), seg)
    }
    kv_used: Dict[int, tuple] = {}

    def kv_at(slot: int):
        if slot not in kv_used:
            buf, s_kv = kvs[slot]
            if kv_transform is not None:
                kk, vv = kv_transform(buf)
            else:
                kk, vv = buf[0], buf[1]
            kv_used[slot] = (kk, vv, s_kv)
        return kv_used[slot]

    o_acc: Dict[int, Optional[tuple]] = {u: None for u in range(a)}
    nq = nkv = 0
    # leading sends over fully-pruned rows are absent; re-base the counter
    nsend = (a - 1) - sum(1 for c in sched.comm_ops() if c == S.SEND_O)

    mode = cfg.comm_overlap
    for step in sched.steps:
        # phase 1 — ISSUE: emit this step's ring permutes ahead of its
        # blocks.  Under the schedule semantics a transfer issued at step t
        # delivers at the END of t and feeds compute at t+1+ (double-buffered
        # slots), so in overlap/bidir mode the permute pair below rides the
        # wire WHILE the blocks of phase 2 run — XLA's async collectives see
        # no data dependency between them.
        recv_updates = []
        issued: list = []
        for comm in step.comms:
            if comm == S.RECV_Q:
                nxt = _ring_hop(qs[nq], cfg.axis_name, q_perm, mode)
                recv_updates.append(("q", nxt))
                issued += [x for x in jax.tree.leaves(nxt)]
            elif comm == S.RECV_KV:
                nxt = _ring_hop(kvs[nkv], cfg.axis_name, kv_perm, mode)
                recv_updates.append(("kv", nxt))
                issued += [x for x in jax.tree.leaves(nxt)]
            elif comm == S.SEND_O:
                src = nsend + 1  # completed row being forwarded
                dst = (nsend + 2) % a  # row whose partial arrives (Table 1)
                o_r, l_r = _ring_hop(o_acc[src], cfg.axis_name, q_perm, mode)
                o_acc[dst] = _merge(o_acc[dst], o_r, l_r)
                issued += [o_r, l_r]
                nsend += 1
            else:  # pragma: no cover
                raise ValueError(comm)
        # phase 2 — COMPUTE this step's blocks from previously-delivered
        # slots.  serial mode barriers each block's operands on the issued
        # transfers, pinning comm ahead of compute on the critical path.
        for (u, vv) in step.compute:
            band, sq, skv = _band_for_block(cfg, i, u, vv, q.shape[1], k.shape[1])
            q_u, s_q = qs[u]
            kk, vv_t, s_kv = kv_at(vv)
            if mode == "serial":
                q_u, kk, vv_t = _after_comms(issued, q_u, kk, vv_t)
            o_b, l_b = ops.block_attention(
                q_u, kk, vv_t, band,
                scale=scale, stride_q=sq, stride_kv=skv,
                block_q=cfg.block_q, block_kv=cfg.block_kv,
                seg_q=s_q, seg_kv=s_kv,
            )
            o_acc[u] = _merge(o_acc[u], o_b, l_b)
        # phase 3 — COMMIT: the in-flight transfers land in the next slots
        # (the buffer swap of the double buffer), visible from step t+1 on.
        for kind, buf in recv_updates:
            if kind == "q":
                nq += 1
                qs[nq] = buf
            else:
                nkv += 1
                kvs[nkv] = buf

    if o_acc[0] is None:  # every local-row block mask-pruned
        B, m, H = q.shape[0], q.shape[1], q.shape[2]
        return jnp.zeros_like(q), jnp.full((B, H, m), NEG_INF, jnp.float32)
    o_f, lse_f = o_acc[0]
    return o_f.astype(q.dtype), lse_f


# --------------------------------------------------------------------------
# backward program (Algorithm 3 structure)
# --------------------------------------------------------------------------


def _bwd_program(cfg: MeshAttentionConfig, q, k, v, o, lse, do, seg=None):
    n, a, b = cfg.n, cfg.a, cfg.b
    lay = TileLayout(n, a)
    i = lax.axis_index(cfg.axis_name)
    scale = cfg.scale if cfg.scale is not None else q.shape[-1] ** -0.5
    _, sched = cfg.schedules(n * q.shape[1])

    q_perm = lay.q_shift_perm()
    kv_perm = lay.kv_shift_perm()

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)  # [B,S,H]
    # the Q ring circulates the "OdOQ" bundle (paper wire) or the Δ-trick
    # bundle (beyond-paper: rowsum(dO·O) replaces the full O chunk — 2Nd/n+ε
    # bytes per hop instead of 3Nd/n)
    bundle0 = {"q": q, "do": do, "lse": lse, "delta": delta}
    if cfg.bwd_wire == "odoq":
        bundle0["o"] = o
    if seg is not None:
        bundle0["seg"] = seg

    qb: Dict[int, dict] = {0: bundle0}
    kvs: Dict[int, tuple] = {0: (jnp.stack([k, v]), seg)}
    dq_acc: Dict[int, Optional[jnp.ndarray]] = {u: None for u in range(a)}
    dkv_acc: Dict[int, Optional[jnp.ndarray]] = {u: None for u in range(b)}
    nq = nkv = 0
    # leading sends over fully-pruned rows/cols are absent; re-base counters
    ndq = (a - 1) - sum(1 for c in sched.comm_ops() if c == S.SEND_DQ)
    ndkv = (b - 1) - sum(1 for c in sched.comm_ops() if c == S.SEND_DKV)

    def _add(cur, new):
        new = new.astype(jnp.float32)
        return new if cur is None else cur + new

    mode = cfg.comm_overlap
    for step in sched.steps:
        # same issue/compute/commit pipeline as the forward executor; the
        # dq/dkv accumulation chains are plain float sums whose association
        # order is fixed by the schedule, so the bidir half-payload pairs
        # (each half summed element-wise on the same route) stay bitwise
        recv_updates = []
        issued: list = []
        for comm in step.comms:
            if comm == S.RECV_ODOQ:
                nxt = _ring_hop(qb[nq], cfg.axis_name, q_perm, mode)
                recv_updates.append(("q", nxt))
                issued += [x for x in jax.tree.leaves(nxt)]
            elif comm == S.RECV_KV:
                nxt = _ring_hop(kvs[nkv], cfg.axis_name, kv_perm, mode)
                recv_updates.append(("kv", nxt))
                issued += [x for x in jax.tree.leaves(nxt)]
            elif comm == S.SEND_DQ:
                src, dst = ndq + 1, (ndq + 2) % a
                got = _ring_hop(dq_acc[src], cfg.axis_name, q_perm, mode)
                dq_acc[dst] = _add(dq_acc[dst], got)
                issued.append(got)
                ndq += 1
            elif comm == S.SEND_DKV:
                src, dst = ndkv + 1, (ndkv + 2) % b
                got = _ring_hop(dkv_acc[src], cfg.axis_name, kv_perm, mode)
                dkv_acc[dst] = _add(dkv_acc[dst], got)
                issued.append(got)
                ndkv += 1
            else:  # pragma: no cover
                raise ValueError(comm)
        for (u, vv) in step.compute:
            band, sq, skv = _band_for_block(cfg, i, u, vv, q.shape[1], k.shape[1])
            bu = qb[u]
            kv_buf, s_kv = kvs[vv]
            q_u, do_u, kv_u = bu["q"], bu["do"], kv_buf
            if mode == "serial":
                q_u, do_u, kv_u = _after_comms(issued, q_u, do_u, kv_u)
            dq_b, dk_b, dv_b = ops.block_attention_bwd(
                q_u, kv_u[0], kv_u[1], bu.get("o"), bu["lse"], do_u, band,
                scale=scale, stride_q=sq, stride_kv=skv,
                block_q=cfg.block_q, block_kv=cfg.block_kv, delta=bu["delta"],
                seg_q=bu.get("seg"), seg_kv=s_kv,
            )
            dq_acc[u] = _add(dq_acc[u], dq_b)
            dkv_acc[vv] = _add(dkv_acc[vv], jnp.stack([dk_b, dv_b]))
        for kind, buf in recv_updates:
            if kind == "q":
                nq += 1
                qb[nq] = buf
            else:
                nkv += 1
                kvs[nkv] = buf

    dq = jnp.zeros_like(q) if dq_acc[0] is None else dq_acc[0].astype(q.dtype)
    dkv = dkv_acc[0]
    if dkv is None:
        return dq, jnp.zeros_like(k), jnp.zeros_like(v)
    return dq, dkv[0].astype(k.dtype), dkv[1].astype(v.dtype)


# --------------------------------------------------------------------------
# public op
# --------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _mesh_attention(q, k, v, cfg: MeshAttentionConfig):
    o, _ = _fwd_program(q, k, v, cfg)
    return o


def _mesh_attention_fwd(q, k, v, cfg):
    o, lse = _fwd_program(q, k, v, cfg)
    return o, (q, k, v, o, lse)


def _mesh_attention_bwd(cfg, res, do):
    q, k, v, o, lse = res
    return _bwd_program(cfg, q, k, v, o, lse, do)


_mesh_attention.defvjp(_mesh_attention_fwd, _mesh_attention_bwd)


# variant with a segment-id operand (packed documents): the int32 chunk is a
# traced argument whose cotangent is None
@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _mesh_attention_seg(q, k, v, seg, cfg: MeshAttentionConfig):
    o, _ = _fwd_program(q, k, v, cfg, seg=seg)
    return o


def _mesh_attention_seg_fwd(q, k, v, seg, cfg):
    o, lse = _fwd_program(q, k, v, cfg, seg=seg)
    return o, (q, k, v, seg, o, lse)


def _mesh_attention_seg_bwd(cfg, res, do):
    q, k, v, seg, o, lse = res
    dq, dk, dv = _bwd_program(cfg, q, k, v, o, lse, do, seg=seg)
    return dq, dk, dv, None


_mesh_attention_seg.defvjp(_mesh_attention_seg_fwd, _mesh_attention_seg_bwd)


def _local_band(cfg: MeshAttentionConfig):
    """Static band for the n == 1 degenerate path."""
    spec = cfg.mask_spec()
    if spec.kind == "block_sparse":
        if len(spec.bitmap) != cfg.n:
            raise ValueError(
                f"block_sparse bitmap is {len(spec.bitmap)}x{len(spec.bitmap)}, "
                f"but the sequence is split n={cfg.n} ways"
            )
        return (0, 0, -BAND_INF, BAND_INF) if spec.bitmap[0][0] else (0, 0, 1, 0)
    lo, hi = spec.band()
    return (0, 0, lo, hi)


def mesh_attention(q, k, v, cfg: MeshAttentionConfig, seg=None):
    """Distributed attention over the local chunks (call inside shard_map).

    q: [B, S/n, H, D]; k, v: [B, S/n, Hkv, D] -> o: [B, S/n, H, D].
    Causal inputs must be striped (token t on chunk t mod n).  ``seg`` is the
    local [S/n] int32 segment-id chunk for document/segment masks.
    """
    spec = cfg.mask_spec()
    if spec.needs_segments and seg is None:
        raise ValueError(f"mask kind {spec.kind!r} needs a segment-id operand")
    if cfg.n == 1:
        return ops.flash_attention(
            q, k, v, band=_local_band(cfg), scale=cfg.scale,
            seg_q=seg, seg_kv=seg,
        )
    if seg is not None:
        return _mesh_attention_seg(q, k, v, jnp.asarray(seg, jnp.int32), cfg)
    return _mesh_attention(q, k, v, cfg)


def mesh_attention_with_lse(q, k, v, cfg: MeshAttentionConfig, seg=None):
    """Forward-only variant exposing the log-sum-exp (tests, serving)."""
    return _fwd_program(q, k, v, cfg, seg=seg)


def mesh_attention_wire(q, wire, cfg: MeshAttentionConfig, kv_transform, seg=None):
    """Mesh-Attention with a compressed KV wire (beyond-paper, §Perf).

    ``wire``: the per-device chunk of whatever representation should
    circulate on the KV ring (e.g. MLA latent [B, S/n, 1, kvr+rope]);
    ``kv_transform(chunk) -> (k, v)`` expands it per-head at first use.
    Differentiable by plain autodiff (no custom Alg-3 rule on this path);
    intended for forward-only prefill/serving.
    """
    o, _ = _fwd_program(q, wire, None, cfg, kv_transform=kv_transform, seg=seg)
    return o
