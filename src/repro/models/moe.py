"""Mixture-of-Experts layer: top-k routing with sort-based capacity dispatch.

Dispatch is MegaBlocks/GShard-style but gather/scatter based (no [S,E,C]
one-hot blow-up): per sample, the S·K (token, expert) assignments are sorted
by expert id, ranked within expert, and tokens beyond the per-expert capacity
C = ceil(S·K·cf / E) are dropped.  Everything is static-shaped (jit/pjit
friendly).

Distribution modes (cfg.moe.mode):
  * "tp": expert d_ff sharded over the model axis (works for any expert
    count, e.g. Mixtral's 8 experts on a 16-wide axis).  The second expert
    matmul produces partials that GSPMD psums/reduce-scatters.
  * "ep": expert dim sharded over the model axis (experts padded up to a
    multiple of the axis; padding experts get -inf router logits).  GSPMD
    inserts the dispatch all-to-all when resharding xe from token- to
    expert-major.

Both modes first gather the sequence dimension over the model axis
(Megatron SP<->TP transition) because routing needs token-local decisions
while the sequence is context-parallel for attention.
"""

from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import dense_init, rms_norm
from repro.parallel.context import ParallelCtx

__all__ = ["init_moe_params", "moe_block", "padded_experts"]

_ACT = {"silu": jax.nn.silu, "gelu": lambda x: jax.nn.gelu(x, approximate=True)}


def padded_experts(cfg: ModelConfig, ctx: ParallelCtx) -> int:
    e = cfg.moe.num_experts
    if cfg.moe.mode == "ep" and ctx.sp_size > 1:
        return int(math.ceil(e / ctx.sp_size) * ctx.sp_size)
    return e


def init_moe_params(key, cfg: ModelConfig, L: int, dtype, ctx: ParallelCtx) -> dict:
    m = cfg.moe
    D, Fe = cfg.d_model, m.d_ff_expert
    E = padded_experts(cfg, ctx)
    ks = jax.random.split(key, 8)
    p = {
        "ln": jnp.zeros((L, D), dtype),
        "router": dense_init(ks[0], (L, D, E), dtype=jnp.float32),
        "we1": dense_init(ks[1], (L, E, D, Fe), in_axis=-2, dtype=dtype),
        "we3": dense_init(ks[2], (L, E, D, Fe), in_axis=-2, dtype=dtype),
        "we2": dense_init(ks[3], (L, E, Fe, D), in_axis=-2, dtype=dtype),
    }
    if m.num_shared:
        Fs = m.d_ff_shared
        p.update(
            ws1=dense_init(ks[4], (L, D, Fs), dtype=dtype),
            ws3=dense_init(ks[5], (L, D, Fs), dtype=dtype),
            ws2=dense_init(ks[6], (L, Fs, D), dtype=dtype),
            shared_gate=dense_init(ks[7], (L, D, 1), dtype=dtype),
        )
    return p


def _dispatch_indices(idx: jnp.ndarray, E: int, C: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """idx: [T, K] expert choice per (token, k) -> (slot [T,K], valid [T,K]).

    slot = expert*C + rank-within-expert (capacity-dropped entries invalid).
    """
    T, K = idx.shape
    flat = idx.reshape(-1)  # [T*K]
    order = jnp.argsort(flat, stable=True)
    sorted_e = flat[order]
    # rank of each sorted entry within its expert: position - first occurrence
    first = jnp.searchsorted(sorted_e, sorted_e, side="left")
    ranks = jnp.arange(T * K) - first
    valid_sorted = ranks < C
    slot_sorted = sorted_e * C + jnp.minimum(ranks, C - 1)
    # scatter back to (token, k) order
    slot = jnp.zeros((T * K,), jnp.int32).at[order].set(slot_sorted.astype(jnp.int32))
    valid = jnp.zeros((T * K,), bool).at[order].set(valid_sorted)
    return slot.reshape(T, K), valid.reshape(T, K)


def _route(h, router_w, cfg: ModelConfig, E_pad: int):
    """h [B,S,D] -> (idx [B,S,K], weights [B,S,K], aux_loss scalar)."""
    m = cfg.moe
    logits = (h.astype(jnp.float32) @ router_w).astype(jnp.float32)  # [B,S,E_pad]
    if E_pad > m.num_experts:  # mask padding experts
        neg = jnp.full((E_pad - m.num_experts,), -1e30, jnp.float32)
        logits = logits.at[..., m.num_experts :].add(neg)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, m.top_k)
    if m.router_norm_topk:
        w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    # load-balancing auxiliary loss (Switch-style): E * sum(f_e * p_e)
    T = h.shape[0] * h.shape[1]
    sel = jax.nn.one_hot(idx[..., 0], E_pad, dtype=jnp.float32)
    f = sel.reshape(T, E_pad).mean(0)
    pm = probs.reshape(T, E_pad).mean(0)
    aux = m.num_experts * jnp.sum(f * pm)
    return idx, w.astype(h.dtype), aux


def _moe_ep_segmented(x, p, cfg: ModelConfig, ctx: ParallelCtx):
    """Expert parallelism in pure GSPMD via an explicit segment dim.

    Beyond-paper §Perf: the naive global-view dispatch makes GSPMD gather the
    whole sequence (plus a top_k-duplicated [B,S·K,D] buffer).  Exposing the
    sequence shards as a leading segment dim [B, n, S/n, ...] (a free reshape
    of the sharded layout) keeps routing/dispatch LOCAL per shard; the only
    cross-device movement is resharding the capacity buffer
    [B, n, E, C_loc, D] from segment-major to expert-major and back — which
    GSPMD emits as all-to-alls.  Per-shard capacity C_loc =
    ceil(S_loc·K·cf/E) (the standard EP formulation).
    """
    m = cfg.moe
    act = _ACT[cfg.mlp_act]
    B, S, D = x.shape
    E = p["router"].shape[-1]
    n = ctx.sp_size
    S_loc = S // n
    C = int(math.ceil(S_loc * m.top_k * m.capacity_factor / E))
    bs = ctx.eff_batch_spec(B)
    P_ = jax.sharding.PartitionSpec

    def seg(spec_tail):
        return jax.sharding.NamedSharding(ctx.mesh, P_(bs, ctx.sp_axis, *spec_tail))

    def exp(spec_tail):
        return jax.sharding.NamedSharding(ctx.mesh, P_(bs, None, ctx.sp_axis, *spec_tail))

    h = rms_norm(x, p["ln"])
    idx, w, aux = _route(h, p["router"], cfg, E)
    hseg = jax.lax.with_sharding_constraint(h.reshape(B, n, S_loc, D), seg([None]))
    idxseg = idx.reshape(B, n, S_loc, m.top_k)
    wseg = w.reshape(B, n, S_loc, m.top_k)

    def one(h_s, idx_s, w_s):  # per (batch, segment)
        slot, valid = _dispatch_indices(idx_s, E, C)
        contrib = jnp.where(valid[..., None], w_s[..., None], 0.0)
        xe = jnp.zeros((E * C, D), h_s.dtype)
        src = jnp.repeat(h_s, m.top_k, axis=0)
        xe = xe.at[slot.reshape(-1)].add(jnp.where(valid.reshape(-1, 1), src, 0.0))
        return xe, slot, contrib

    xe, slot, contrib = jax.vmap(jax.vmap(one))(hseg, idxseg, wseg)
    xe = jax.lax.with_sharding_constraint(xe.reshape(B, n, E, C, D), seg([None, None, None]))
    # segment-major -> expert-major: the dispatch all-to-all
    xe = jax.lax.with_sharding_constraint(xe, exp([None, None]))
    up = jnp.einsum("bnecd,edf->bnecf", xe, p["we1"])
    gate = jnp.einsum("bnecd,edf->bnecf", xe, p["we3"])
    ye = jnp.einsum("bnecf,efd->bnecd", act(up) * gate, p["we2"])
    # expert-major -> segment-major: the return all-to-all
    ye = jax.lax.with_sharding_constraint(ye, seg([None, None, None]))

    def combine_one(ye_s, slot_s, contrib_s):
        got = ye_s.reshape(E * C, D)[slot_s.reshape(-1)].reshape(S_loc, m.top_k, D)
        return jnp.sum(got * contrib_s.astype(got.dtype), axis=1)

    out = jax.vmap(jax.vmap(combine_one))(ye, slot, contrib)  # [B, n, S_loc, D]
    out = out.reshape(B, S, D)
    if m.num_shared:
        g = jax.nn.sigmoid((h @ p["shared_gate"]).astype(jnp.float32)).astype(h.dtype)
        out = out + g * ((act(h @ p["ws1"]) * (h @ p["ws3"])) @ p["ws2"])
    out = ctx.constrain(out, "seq", None)
    return x + out.astype(x.dtype), aux


def _moe_ep_manual(x, p, cfg: ModelConfig, ctx: ParallelCtx):
    """Expert parallelism with explicit dispatch all-to-alls inside a
    partial-manual shard_map (GShard-style).  NOTE: functionally validated on
    fake-device meshes (tests), but the 256-device CPU dry-run compile hits
    an XLA host-backend bug ("Invalid binary instruction opcode copy"), so
    the production EP path is the segmented pure-GSPMD variant above.
    """
    import jax
    from jax import lax

    from jax import shard_map

    m = cfg.moe
    act = _ACT[cfg.mlp_act]
    B, S, D = x.shape
    E = p["router"].shape[-1]
    n = ctx.sp_size
    E_loc = E // n
    S_loc = S // n
    C = int(math.ceil(S_loc * m.top_k * m.capacity_factor / E))

    def inner(h, ln, router, we1, we3, we2, *shared):
        hn = rms_norm(h, ln)
        idx, w, aux = _route(hn, router, cfg, E)

        def one_sample(h_s, idx_s, w_s):
            slot, valid = _dispatch_indices(idx_s, E, C)
            contrib = jnp.where(valid[..., None], w_s[..., None], 0.0)
            xe = jnp.zeros((E * C, D), h_s.dtype)
            src = jnp.repeat(h_s, m.top_k, axis=0)
            xe = xe.at[slot.reshape(-1)].add(jnp.where(valid.reshape(-1, 1), src, 0.0))
            return xe, slot, contrib

        xe, slot, contrib = jax.vmap(one_sample)(hn, idx, w)
        xe = xe.reshape(B, E, C, D)
        # dispatch: expert-major exchange (tokens travel to their experts)
        xe = lax.all_to_all(xe, ctx.sp_axis, split_axis=1, concat_axis=2, tiled=True)
        up = jnp.einsum("becd,edf->becf", xe, we1)
        gate = jnp.einsum("becd,edf->becf", xe, we3)
        ye = jnp.einsum("becf,efd->becd", act(up) * gate, we2)
        # return: tokens travel home
        ye = lax.all_to_all(ye, ctx.sp_axis, split_axis=2, concat_axis=1, tiled=True)
        ye = ye.reshape(B, E * C, D)

        def combine_one(ye_s, slot_s, contrib_s):
            got = ye_s[slot_s.reshape(-1)].reshape(S_loc, m.top_k, D)
            return jnp.sum(got * contrib_s.astype(got.dtype), axis=1)

        out = jax.vmap(combine_one)(ye, slot, contrib)
        if m.num_shared:
            ws1, ws3, ws2, sg = shared
            g = jax.nn.sigmoid((hn @ sg).astype(jnp.float32)).astype(hn.dtype)
            out = out + g * ((act(hn @ ws1) * (hn @ ws3)) @ ws2)
        return out, lax.pmean(aux, ctx.sp_axis)

    P_ = jax.sharding.PartitionSpec
    seq_spec = P_(None, "model", None)
    args = [p["ln"], p["router"], p["we1"], p["we3"], p["we2"]]
    in_specs = [seq_spec, P_(), P_(), P_("model"), P_("model"), P_("model")]
    if m.num_shared:
        args += [p["ws1"], p["ws3"], p["ws2"], p["shared_gate"]]
        in_specs += [P_(), P_(), P_(), P_()]
    f = shard_map(
        inner,
        mesh=ctx.shard_map_mesh(),
        in_specs=tuple(in_specs),
        out_specs=(seq_spec, P_()),
        axis_names={"model"},
        check_vma=False,
    )
    out, aux = f(x, *args)
    return x + out.astype(x.dtype), aux


def moe_block(
    x: jnp.ndarray,  # [B, S, D]
    p: dict,  # one layer's params
    cfg: ModelConfig,
    ctx: ParallelCtx,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (x + moe(x), aux_loss)."""
    m = cfg.moe
    if (
        m.mode == "ep"
        and ctx.mesh is not None
        and ctx.sp_size > 1
        and padded_experts(cfg, ctx) % ctx.sp_size == 0
        and x.shape[1] % ctx.sp_size == 0
    ):
        return _moe_ep_segmented(x, p, cfg, ctx)
    act = _ACT[cfg.mlp_act]
    B, S, D = x.shape
    E = p["router"].shape[-1]
    C = int(math.ceil(S * m.top_k * m.capacity_factor / E))

    h = rms_norm(x, p["ln"])
    # SP -> token-local: gather the sequence over the model axis
    h = ctx.constrain(h, None, None)
    idx, w, aux = _route(h, p["router"], cfg, E)

    def one_sample(h_s, idx_s, w_s):
        slot, valid = _dispatch_indices(idx_s, E, C)  # [S,K]
        contrib = jnp.where(valid[..., None], w_s[..., None], 0.0)
        # xe[e*C + c] = token routed there (dropped -> zeros via scatter mask)
        xe = jnp.zeros((E * C, D), h_s.dtype)
        src = jnp.repeat(h_s, m.top_k, axis=0)  # [S*K, D] token per assignment
        xe = xe.at[slot.reshape(-1)].add(
            jnp.where(valid.reshape(-1, 1), src, 0.0)
        )
        return xe, slot, contrib

    xe, slot, contrib = jax.vmap(one_sample)(h, idx, w)  # xe [B, E*C, D]
    xe = xe.reshape(B, E, C, D)
    if m.mode == "ep" and ctx.mesh is not None and ctx.sp_size > 1:
        # token-major -> expert-major resharding = the EP all-to-all
        xe = jax.lax.with_sharding_constraint(
            xe,
            jax.sharding.NamedSharding(
                ctx.mesh,
                jax.sharding.PartitionSpec(ctx.eff_batch_spec(B), ctx.sp_axis, None, None),
            ),
        )
    up = jnp.einsum("becd,edf->becf", xe, p["we1"])
    gate = jnp.einsum("becd,edf->becf", xe, p["we3"])
    ye = jnp.einsum("becf,efd->becd", act(up) * gate, p["we2"])
    if m.mode == "ep" and ctx.mesh is not None and ctx.sp_size > 1:
        ye = jax.lax.with_sharding_constraint(
            ye,
            jax.sharding.NamedSharding(
                ctx.mesh,
                jax.sharding.PartitionSpec(ctx.eff_batch_spec(B), None, None, None),
            ),
        )
    ye = ye.reshape(B, E * C, D)

    def combine_one(ye_s, slot_s, contrib_s):
        got = ye_s[slot_s.reshape(-1)].reshape(S, m.top_k, D)
        return jnp.sum(got * contrib_s.astype(got.dtype), axis=1)

    out = jax.vmap(combine_one)(ye, slot, contrib)  # [B, S, D]

    if m.num_shared:
        g = jax.nn.sigmoid((h @ p["shared_gate"]).astype(jnp.float32)).astype(h.dtype)
        shared = (act(h @ p["ws1"]) * (h @ p["ws3"])) @ p["ws2"]
        out = out + g * shared

    # back to the sequence-parallel layout
    out = ctx.constrain(out, "seq", None)
    return x + out.astype(x.dtype), aux
