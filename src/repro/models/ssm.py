"""Mamba-2 SSD block (state-space duality, arXiv:2405.21060).

The SSD scan is computed in chunked dual form: quadratic attention-like
matmuls within chunks + a linear state recurrence across chunks — the same
structure the Pallas kernel (kernels/ssd_scan.py) tiles for VMEM.

Sequence parallelism: Mesh-Attention does not apply (no Q·Kᵀ — see DESIGN.md
§Arch-applicability); instead the sequence is sharded *contiguously* over the
model axis and the recurrence crosses devices through its (tiny) state:

  1. each device runs the chunked scan with h0 = 0, producing its final
     state S_i and total decay T_i (both O(H·P·N) — KBs, not chunks),
  2. one all-gather of {(S_i, T_i)} and a closed-form prefix combine give the
     true incoming state h0_i = sum_{j<i} (prod_{j<k<i} T_k) S_j,
  3. outputs are corrected in closed form: y_t += C_t · (cumdecay_t · h0_i);
     the causal depthwise conv exchanges a (width-1)-token halo by ppermute.

Communication per layer is O(n · H·P·N) bytes — negligible next to attention
— which is why the roofline for mamba2/hymba cells is compute/memory-bound.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.layers import dense_init, rms_norm
from repro.parallel.context import ParallelCtx

__all__ = ["init_ssm_params", "ssm_block", "ssm_dims", "init_ssm_cache", "ssm_decode_step"]


def ssm_dims(cfg: ModelConfig):
    s = cfg.ssm
    d_inner = s.expand * cfg.d_model
    H = d_inner // s.head_dim
    return d_inner, H, s.n_groups, s.state_dim, s.head_dim


def init_ssm_params(key, cfg: ModelConfig, L: int, dtype) -> dict:
    s = cfg.ssm
    D = cfg.d_model
    d_inner, H, G, N, Pd = ssm_dims(cfg)
    conv_dim = d_inner + 2 * G * N
    ks = jax.random.split(key, 4)
    return {
        "ln": jnp.zeros((L, D), dtype),
        # fused input projection -> (z, x, B, C, dt)
        "in_proj": dense_init(ks[0], (L, D, 2 * d_inner + 2 * G * N + H), dtype=dtype),
        "conv_w": dense_init(ks[1], (L, s.conv_width, conv_dim), in_axis=-2, dtype=dtype),
        "conv_b": jnp.zeros((L, conv_dim), dtype),
        "A_log": jnp.zeros((L, H), jnp.float32),  # A = -exp(A_log) = -1 init
        "D_skip": jnp.ones((L, H), jnp.float32),
        "dt_bias": jnp.zeros((L, H), jnp.float32),
        "out_ln": jnp.zeros((L, d_inner), dtype),
        "out_proj": dense_init(ks[2], (L, d_inner, D), dtype=dtype),
    }


# --------------------------------------------------------------------------
# chunked SSD (local sequence)
# --------------------------------------------------------------------------


def _ssd_chunked(
    x: jnp.ndarray,  # [B, S, H, P] (fp32)
    dt: jnp.ndarray,  # [B, S, H]  (fp32, softplus applied)
    A: jnp.ndarray,  # [H] (negative, fp32)
    Bm: jnp.ndarray,  # [B, S, H, N] (groups already broadcast)
    Cm: jnp.ndarray,  # [B, S, H, N]
    chunk: int,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """-> (y_zero [B,S,H,P], h_in_chunks [B,nc,H,P,N], cumT [B,nc,H], extras)

    y_zero is the output with zero initial state; h_in_chunks are the
    incoming states per chunk under h0=0; cumT[z] = decay from sequence start
    to the start of chunk z.  The device-level correction only needs:
        y = y_zero + einsum(C_t, exp(Acum_t) * cumT[z] * h0)
    Also returns (final_state, total_decay) for the cross-device combine.
    """
    Bb, S, H, Pd = x.shape
    N = Bm.shape[-1]
    nc = S // chunk
    c = chunk
    xr = x.reshape(Bb, nc, c, H, Pd)
    dtr = dt.reshape(Bb, nc, c, H)
    Br = Bm.reshape(Bb, nc, c, H, N)
    Cr = Cm.reshape(Bb, nc, c, H, N)
    a = dtr * A  # [B,nc,c,H] negative
    Acum = jnp.cumsum(a, axis=2)  # inclusive

    # intra-chunk (dual quadratic form): y[t] = sum_{s<=t} L[t,s] (C_t.B_s) dt_s x_s
    Ldec = jnp.exp(Acum[:, :, :, None, :] - Acum[:, :, None, :, :])  # [B,nc,t,s,H]
    mask = jnp.tril(jnp.ones((c, c), bool))
    L = jnp.where(mask[None, None, :, :, None], Ldec, 0.0)
    scores = jnp.einsum("bzthn,bzshn->bztsh", Cr, Br)
    y_intra = jnp.einsum("bztsh,bzsh,bzshp->bzthp", L * scores, dtr, xr)

    # chunk summary states: contribution of chunk z to its end-state
    decay_to_end = jnp.exp(Acum[:, :, -1:, :] - Acum)  # [B,nc,c,H]
    chunk_state = jnp.einsum("bzch,bzch,bzchn,bzchp->bzhpn", decay_to_end, dtr, Br, xr)
    T = jnp.exp(Acum[:, :, -1, :])  # total decay per chunk [B,nc,H]

    # inter-chunk prefix (h0 = 0)
    def step(h, inp):
        cs, t = inp
        h_in = h
        h = t[:, :, None, None] * h + cs
        return h, h_in

    hT, h_in_chunks = lax.scan(
        step, jnp.zeros((Bb, H, Pd, N), jnp.float32),
        (chunk_state.transpose(1, 0, 2, 3, 4), T.transpose(1, 0, 2)),
    )
    h_in_chunks = h_in_chunks.transpose(1, 0, 2, 3, 4)  # [B,nc,H,P,N]

    y_inter = jnp.einsum("bzthn,bzth,bzhpn->bzthp", Cr, jnp.exp(Acum), h_in_chunks)
    y_zero = (y_intra + y_inter).reshape(Bb, S, H, Pd)

    cumT = jnp.exp(jnp.cumsum(jnp.sum(a, axis=2), axis=1) - jnp.sum(a, axis=2))  # decay to chunk start
    total_decay = jnp.exp(jnp.sum(a, axis=(1, 2)))  # [B,H]
    return y_zero, (Cr, Acum, cumT), hT, total_decay


def _apply_initial_state(y_zero, extras, h0):
    """Closed-form correction for a nonzero initial state."""
    Cr, Acum, cumT = extras
    Bb, nc, c, H, N = Cr.shape
    corr = jnp.einsum(
        "bzthn,bzth,bzh,bhpn->bzthp", Cr, jnp.exp(Acum), cumT, h0
    )
    return y_zero + corr.reshape(y_zero.shape)


def ssd_scan(x, dt, A, Bm, Cm, chunk, h0=None):
    """Single-device SSD: returns (y, final_state)."""
    y_zero, extras, hT, total = _ssd_chunked(x, dt, A, Bm, Cm, chunk)
    if h0 is not None:
        y_zero = _apply_initial_state(y_zero, extras, h0)
        hT = hT + total[:, :, None, None] * h0
    return y_zero, hT


# --------------------------------------------------------------------------
# distributed core (conv halo + state passing) — runs inside shard_map
# --------------------------------------------------------------------------


def _conv1d_causal(xin, w, b, halo):
    """Depthwise causal conv. xin [B,S,C], w [width,C], halo [B,width-1,C]."""
    width = w.shape[0]
    xp = jnp.concatenate([halo, xin], axis=1)
    out = sum(
        xp[:, i : i + xin.shape[1]] * w[i][None, None, :] for i in range(width)
    )
    return out + b[None, None, :]


def _ssm_core(zxbcdt, p, cfg: ModelConfig, axis_name: Optional[str], n: int):
    """From fused projection to gated SSD output (pre out_proj).

    Returns (y, hT_global [B,H,P,N] fp32, conv_tail [B,w-1,conv_dim]) — the
    final recurrence state and conv window, identical on every device (needed
    for prefill -> decode continuity).
    """
    s = cfg.ssm
    d_inner, H, G, N, Pd = ssm_dims(cfg)
    Bb, S, _ = zxbcdt.shape
    z, xc, Bc, Cc, dt = jnp.split(
        zxbcdt, [d_inner, 2 * d_inner, 2 * d_inner + G * N, 2 * d_inner + 2 * G * N], axis=-1
    )
    conv_in = jnp.concatenate([xc, Bc, Cc], axis=-1)
    width = s.conv_width
    if axis_name is not None and n > 1:
        # halo exchange: last width-1 tokens from the left neighbour
        # (device 0 has no source pair -> ppermute fills zeros = causal pad)
        tail = conv_in[:, -(width - 1) :, :]
        halo = lax.ppermute(tail, axis_name, [(i, i + 1) for i in range(n - 1)])
    else:
        halo = jnp.zeros((Bb, width - 1, conv_in.shape[-1]), conv_in.dtype)
    conv_out = jax.nn.silu(_conv1d_causal(conv_in, p["conv_w"], p["conv_b"], halo))
    xc, Bc, Cc = jnp.split(conv_out, [d_inner, d_inner + G * N], axis=-1)

    xh = xc.reshape(Bb, S, H, Pd).astype(jnp.float32)
    Bh = jnp.repeat(Bc.reshape(Bb, S, G, N), H // G, axis=2).astype(jnp.float32)
    Ch = jnp.repeat(Cc.reshape(Bb, S, G, N), H // G, axis=2).astype(jnp.float32)
    dtf = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"][None, None, :])
    A = -jnp.exp(p["A_log"].astype(jnp.float32))

    y_zero, extras, hT, total = _ssd_chunked(xh, dtf, A, Bh, Ch, min(s.chunk, S))
    conv_tail = conv_in[:, -(width - 1) :, :]
    if axis_name is not None and n > 1:
        i = lax.axis_index(axis_name)
        # gather every device's (zero-init final state, total decay) — a few
        # KB per device; this is the entire cross-device cost of the SSD scan
        allS = lax.all_gather(hT, axis_name)  # [n,B,H,P,N]
        allT = lax.all_gather(total, axis_name)  # [n,B,H]
        # h0_i = sum_{j<i} (prod_{j<k<i} T_k) S_j   (static unroll over n)
        h0 = jnp.zeros_like(hT)
        for j in range(n):
            contrib = allS[j]
            decay = jnp.ones_like(total)
            for k in range(j + 1, n):
                decay = jnp.where(k < i, decay * allT[k], decay)
            h0 = h0 + jnp.where(j < i, (decay[:, :, None, None] * contrib), 0.0)
        y_zero = _apply_initial_state(y_zero, extras, h0)
        hT = hT + total[:, :, None, None] * h0
        # global final state (same value on every device): prefix over ALL j
        hT_global = jnp.zeros_like(hT)
        for j in range(n):
            dacc = jnp.ones_like(total)
            for k in range(j + 1, n):
                dacc = dacc * allT[k]
            hT_global = hT_global + dacc[:, :, None, None] * allS[j]
        # global conv tail = last device's tail
        all_tails = lax.all_gather(conv_tail, axis_name)
        conv_tail = all_tails[n - 1]
        hT = hT_global

    y = y_zero + p["D_skip"][None, None, :, None].astype(jnp.float32) * xh
    y = y.reshape(Bb, S, d_inner).astype(z.dtype)
    y = rms_norm(y * jax.nn.silu(z), p["out_ln"])
    return y, hT, conv_tail


def ssm_block(
    x: jnp.ndarray, p: dict, cfg: ModelConfig, ctx: ParallelCtx, *, return_state: bool = False
):
    h = rms_norm(x, p["ln"])
    zxbcdt = h @ p["in_proj"]
    n = ctx.sp_size
    if n > 1:
        bs = ctx.eff_batch_spec(x.shape[0])
        spec = P(bs, ctx.sp_axis, None)
        rep3 = P(bs, None, None)
        rep4 = P(bs, None, None, None)
        core = shard_map(
            functools.partial(_ssm_core, cfg=cfg, axis_name=ctx.sp_axis, n=n),
            mesh=ctx.shard_map_mesh(),
            in_specs=(spec, P()),
            out_specs=(spec, rep4, rep3),
            check_vma=False,
        )
        y, hT, conv_tail = core(zxbcdt, p)
    else:
        y, hT, conv_tail = _ssm_core(zxbcdt, p, cfg, None, 1)
    out = x + y @ p["out_proj"]
    if return_state:
        return out, {"state": hT, "conv": conv_tail.astype(x.dtype)}
    return out


# --------------------------------------------------------------------------
# decode (O(1) per token; states replicated over the model axis)
# --------------------------------------------------------------------------


def init_ssm_cache(cfg: ModelConfig, L: int, batch: int, dtype) -> dict:
    s = cfg.ssm
    d_inner, H, G, N, Pd = ssm_dims(cfg)
    conv_dim = d_inner + 2 * G * N
    return {
        "conv": jnp.zeros((L, batch, s.conv_width - 1, conv_dim), dtype),
        "state": jnp.zeros((L, batch, H, Pd, N), jnp.float32),
    }


def ssm_decode_step(x, p, cache_l, cfg: ModelConfig):
    """x [B, 1, D]; cache_l = {conv [B,w-1,C], state [B,H,P,N]} (one layer).

    Returns (y [B,1,D] residual-added, new cache_l).
    """
    s = cfg.ssm
    d_inner, H, G, N, Pd = ssm_dims(cfg)
    Bb = x.shape[0]
    h = rms_norm(x, p["ln"])
    zxbcdt = h @ p["in_proj"]
    z, xc, Bc, Cc, dt = jnp.split(
        zxbcdt, [d_inner, 2 * d_inner, 2 * d_inner + G * N, 2 * d_inner + 2 * G * N], axis=-1
    )
    conv_in = jnp.concatenate([xc, Bc, Cc], axis=-1)  # [B,1,C]
    window = jnp.concatenate([cache_l["conv"], conv_in], axis=1)  # [B,w,C]
    conv_out = jax.nn.silu(
        jnp.einsum("bwc,wc->bc", window, p["conv_w"]) + p["conv_b"][None, :]
    )[:, None, :]
    new_conv = window[:, 1:, :]
    xc, Bc, Cc = jnp.split(conv_out, [d_inner, d_inner + G * N], axis=-1)
    xh = xc.reshape(Bb, H, Pd).astype(jnp.float32)
    Bh = jnp.repeat(Bc.reshape(Bb, G, N), H // G, axis=1).astype(jnp.float32)
    Ch = jnp.repeat(Cc.reshape(Bb, G, N), H // G, axis=1).astype(jnp.float32)
    dtf = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + p["dt_bias"][None, :])  # [B,H]
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    decay = jnp.exp(dtf * A)[..., None, None]
    hstate = decay * cache_l["state"] + jnp.einsum("bh,bhp,bhn->bhpn", dtf, xh, Bh)
    y = jnp.einsum("bhpn,bhn->bhp", hstate, Ch) + p["D_skip"][None, :, None] * xh
    y = y.reshape(Bb, 1, d_inner).astype(z.dtype)
    y = rms_norm(y * jax.nn.silu(z), p["out_ln"])
    return x + y @ p["out_proj"], {"conv": new_conv, "state": hstate}
