"""Attention layer: GQA/MHA/MLA projections over the unified dispatch seam.

The projection math runs under pjit (GSPMD shards the weights); the attention
itself goes through ``repro.core.dispatch`` — the backend (mesh | ring |
ulysses | decode | local-flash) is a registry lookup driven by the
``ParallelCtx``, and the tile/schedule may come from the autotuner's plan
cache.  No backend module is imported here.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import dispatch
from repro.models.layers import dense_init, rms_norm, rope
from repro.parallel.context import ParallelCtx

__all__ = [
    "init_attention_params",
    "init_cross_attention_params",
    "attention_block",
    "cross_attention_block",
    "distributed_attention",
    "decode_attention_step",
    "chunk_attention_step",
]


# --------------------------------------------------------------------------
# distributed dispatch (thin adapters over repro.core.dispatch)
# --------------------------------------------------------------------------


def distributed_attention(
    q: jnp.ndarray,  # [B, S(/n), H, D] local-logical global view under pjit
    k: jnp.ndarray,
    v: jnp.ndarray,
    ctx: ParallelCtx,
    *,
    causal: bool,
    window: Optional[int] = None,
    layout: str = "striped",
    segments: Optional[jnp.ndarray] = None,  # [S] int32, same order as tokens
) -> jnp.ndarray:
    """``segments`` switches the mask to causal-within-document (packed
    multi-document rows); it must be permuted exactly like the tokens."""
    if segments is not None:
        from repro.core.masking import MaskSpec

        cfg = dispatch.plan_from_ctx(
            ctx, mask=MaskSpec.segment(window=window), layout=layout
        )
        return dispatch.distributed_attention(q, k, v, cfg=cfg, ctx=ctx, segments=segments)
    cfg = dispatch.plan_from_ctx(ctx, causal=causal, window=window, layout=layout)
    return dispatch.distributed_attention(q, k, v, cfg=cfg, ctx=ctx)


def decode_attention_step(
    q: jnp.ndarray,  # [B, 1, H, D]
    k_new: jnp.ndarray,  # [B, 1, Hkv, D]
    v_new: jnp.ndarray,
    k_cache: jnp.ndarray,  # [L, B, cap(/n), Hkv, D] (paged: the page pools)
    v_cache: jnp.ndarray,
    pos,  # int32 scalar or [B] per-slot position vector
    ctx: ParallelCtx,
    *,
    layer,  # int32: the layer of the stacked caches this step serves
    window: Optional[int] = None,
    layout: str = "striped",
    scale: Optional[float] = None,
    block_table: Optional[jnp.ndarray] = None,  # [B, max_pages]: paged cache
    decode_kernel: Optional[str] = None,  # None -> ctx.decode_kernel
    k_scale: Optional[jnp.ndarray] = None,  # f32 scale tables: quantized pool
    v_scale: Optional[jnp.ndarray] = None,
):
    """Returns (o, new_k_cache, new_v_cache), the caches updated in layer
    ``layer`` only.  ``block_table`` is handed to
    the decode backend verbatim; with the native kernel variant it is read
    in-kernel (scalar-prefetched), never gathered into a dense view.  With
    ``k_scale``/``v_scale`` (quantized paged pool) the return extends to
    ``(o, k_cache, v_cache, k_scale, v_scale)``."""
    return dispatch.decode_attention_step(
        q, k_new, v_new, k_cache, v_cache, pos, ctx, layer=layer,
        window=window, layout=layout, scale=scale, block_table=block_table,
        decode_kernel=decode_kernel, k_scale=k_scale, v_scale=v_scale,
    )


def chunk_attention_step(
    q: jnp.ndarray,  # [B, C, H, D] chunk queries
    k_new: jnp.ndarray,  # [B, C, Hkv, D]
    v_new: jnp.ndarray,
    k_cache: jnp.ndarray,  # [L, B, cap(/n), Hkv, D] (paged: the page pools)
    v_cache: jnp.ndarray,
    starts,  # int32 [B]: global position of each row's chunk base
    lens,  # int32 [B]: valid tokens per row (0 = inactive row)
    write_starts,  # int32 [B]: skip KV writes below this (shared prefix)
    ctx: ParallelCtx,
    *,
    layer,  # int32: the layer of the stacked caches this chunk serves
    window: Optional[int] = None,
    layout: str = "striped",
    scale: Optional[float] = None,
    block_table: Optional[jnp.ndarray] = None,  # [B, max_pages]: paged cache
    k_scale: Optional[jnp.ndarray] = None,  # f32 scale tables: quantized pool
    v_scale: Optional[jnp.ndarray] = None,
):
    """Continuous-prefill chunk append + prefix-causal attention; returns
    (o, new_k_cache, new_v_cache) like ``decode_attention_step`` (plus the
    updated scale tables when a quantized pool passes them)."""
    return dispatch.chunk_attention_step(
        q, k_new, v_new, k_cache, v_cache, starts, lens, write_starts, ctx,
        layer=layer, window=window, layout=layout, scale=scale, block_table=block_table,
        k_scale=k_scale, v_scale=v_scale,
    )


# --------------------------------------------------------------------------
# parameter initialization
# --------------------------------------------------------------------------


def _norm_params(cfg: ModelConfig, L: int, D: int, dtype) -> dict:
    if cfg.norm == "layernorm":
        return {"ln": jnp.ones((L, D), dtype), "ln_b": jnp.zeros((L, D), dtype)}
    return {"ln": jnp.zeros((L, D), dtype)}


def init_attention_params(key, cfg: ModelConfig, L: int, dtype) -> dict:
    D, H, Hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.hd
    ks = jax.random.split(key, 8)
    p = dict(_norm_params(cfg, L, D, dtype))
    if cfg.mla is not None:
        m = cfg.mla
        qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
        p.update(
            wq_a=dense_init(ks[0], (L, D, m.q_lora_rank), dtype=dtype),
            q_ln=jnp.zeros((L, m.q_lora_rank), dtype),
            wq_b=dense_init(ks[1], (L, m.q_lora_rank, H * qk_dim), dtype=dtype),
            wkv_a=dense_init(ks[2], (L, D, m.kv_lora_rank + m.qk_rope_head_dim), dtype=dtype),
            kv_ln=jnp.zeros((L, m.kv_lora_rank), dtype),
            wkv_b=dense_init(
                ks[3], (L, m.kv_lora_rank, H * (m.qk_nope_head_dim + m.v_head_dim)), dtype=dtype
            ),
            wo=dense_init(ks[4], (L, H * m.v_head_dim, D), dtype=dtype),
        )
        return p
    p.update(
        wq=dense_init(ks[0], (L, D, H * hd), dtype=dtype),
        wk=dense_init(ks[1], (L, D, Hkv * hd), dtype=dtype),
        wv=dense_init(ks[2], (L, D, Hkv * hd), dtype=dtype),
        wo=dense_init(ks[3], (L, H * hd, D), dtype=dtype),
    )
    if cfg.qkv_bias:
        p.update(
            bq=jnp.zeros((L, H * hd), dtype),
            bk=jnp.zeros((L, Hkv * hd), dtype),
            bv=jnp.zeros((L, Hkv * hd), dtype),
        )
    return p


def init_cross_attention_params(key, cfg: ModelConfig, L: int, dtype) -> dict:
    D, H, hd = cfg.d_model, cfg.num_heads, cfg.hd
    ks = jax.random.split(key, 4)
    return {
        **_norm_params(cfg, L, D, dtype),
        "wq": dense_init(ks[0], (L, D, H * hd), dtype=dtype),
        "wk": dense_init(ks[1], (L, D, H * hd), dtype=dtype),
        "wv": dense_init(ks[2], (L, D, H * hd), dtype=dtype),
        "wo": dense_init(ks[3], (L, H * hd, D), dtype=dtype),
    }


# --------------------------------------------------------------------------
# projections (one layer slice: params without the leading L dim)
# --------------------------------------------------------------------------


def _mla_q_latent(x, p, cfg: ModelConfig, positions):
    """-> (q [B,S,H,qk] roped, latent [B,S,1,kvr+rope] roped)."""
    B, S, D = x.shape
    m = cfg.mla
    H = cfg.num_heads
    cq = rms_norm(x @ p["wq_a"], p["q_ln"])
    q = (cq @ p["wq_b"]).reshape(B, S, H, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope, q_rope = q[..., : m.qk_nope_head_dim], q[..., m.qk_nope_head_dim :]
    q_rope = rope(q_rope, positions, cfg.rope_theta)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    kv_a = x @ p["wkv_a"]  # [B,S,kvr + rope]
    c_kv = rms_norm(kv_a[..., : m.kv_lora_rank], p["kv_ln"])
    k_rope = rope(kv_a[..., None, m.kv_lora_rank :], positions, cfg.rope_theta)
    lat = jnp.concatenate([c_kv[:, :, None, :], k_rope], axis=-1)
    return q, lat


def _mla_expand(lat, wkv_b, cfg: ModelConfig):
    """latent chunk [B,m,1,kvr+rope] -> per-head (k [B,m,H,qk], v padded)."""
    m = cfg.mla
    H = cfg.num_heads
    B, S = lat.shape[0], lat.shape[1]
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    c = lat[:, :, 0, : m.kv_lora_rank]
    r = lat[..., m.kv_lora_rank :]  # [B,S,1,rope], rope already applied
    kv_b = (c @ wkv_b).reshape(B, S, H, m.qk_nope_head_dim + m.v_head_dim)
    k_nope, vv = kv_b[..., : m.qk_nope_head_dim], kv_b[..., m.qk_nope_head_dim :]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(r, (B, S, H, m.qk_rope_head_dim))], axis=-1
    )
    # pad V up to the qk head dim so one flash kernel serves q/k/v
    # (sliced back after attention; see DESIGN.md kernel notes)
    v = jnp.pad(vv, ((0, 0), (0, 0), (0, 0), (0, qk - m.v_head_dim)))
    return k, v


def _project_qkv(x, p, cfg: ModelConfig, positions):
    """-> q [B,S,H,hd_qk], k [B,S,Hkv,hd_qk], v [B,S,Hkv,hd_v_padded]"""
    B, S, D = x.shape
    H, Hkv = cfg.num_heads, cfg.num_kv_heads
    if cfg.mla is not None:
        q, lat = _mla_q_latent(x, p, cfg, positions)
        k, v = _mla_expand(lat, p["wkv_b"], cfg)
        return q, k, v
    hd = cfg.hd
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, Hkv, hd)
    v = v.reshape(B, S, Hkv, hd)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def _latent_wire_attention(
    q, lat, wkv_b, cfg: ModelConfig, ctx: ParallelCtx, *, causal, segments=None
):
    """MLA x Mesh-Attention with the compressed latent on the KV ring
    (beyond-paper; forward-only — see EXPERIMENTS.md §Perf): wire bytes per
    KV hop drop from 2·H·qk to kvr+rope (MiniCPM3: 15360 -> 288 per token)."""
    scale = (cfg.mla.qk_nope_head_dim + cfg.mla.qk_rope_head_dim) ** -0.5
    if segments is not None:
        from repro.core.masking import MaskSpec

        plan = dispatch.plan_from_ctx(
            ctx, mask=MaskSpec.segment(window=cfg.window), layout=cfg.causal_layout,
            backend="mesh", scale=scale,
        )
    else:
        plan = dispatch.plan_from_ctx(
            ctx, causal=causal, layout=cfg.causal_layout, backend="mesh", scale=scale,
        )
    return dispatch.latent_wire_attention(
        q, lat, wkv_b, lambda chunk, wb: _mla_expand(chunk, wb, cfg), cfg=plan, ctx=ctx,
        segments=segments,
    )


def attention_block(
    x: jnp.ndarray,  # [B, S, D]
    p: dict,  # one layer's params
    cfg: ModelConfig,
    ctx: ParallelCtx,
    positions: jnp.ndarray,
    *,
    causal: bool = True,
    segments: Optional[jnp.ndarray] = None,  # [S] int32 packed-document ids
) -> jnp.ndarray:
    """Pre-norm self-attention with residual."""
    B, S, D = x.shape
    h = rms_norm(x, p["ln"]) if cfg.norm == "rmsnorm" else _ln(x, p)
    if cfg.mla is not None and ctx.mla_latent_wire and ctx.sp_size > 1:
        q, lat = _mla_q_latent(h, p, cfg, positions)
        o = _latent_wire_attention(
            q, lat, p["wkv_b"], cfg, ctx, causal=causal, segments=segments
        )
    else:
        q, k, v = _project_qkv(h, p, cfg, positions)
        o = distributed_attention(
            q, k, v, ctx, causal=causal, window=cfg.window, layout=cfg.causal_layout,
            segments=segments,
        )
    if cfg.mla is not None:
        o = o[..., : cfg.mla.v_head_dim]
    o = o.reshape(B, S, -1) @ p["wo"]
    return x + o


def _ln(x, p):
    from repro.models.layers import layer_norm

    return layer_norm(x, p["ln"], p.get("ln_b", jnp.zeros_like(p["ln"])))


def cross_attention_block(
    x: jnp.ndarray,  # [B, S_dec, D]
    enc: jnp.ndarray,  # [B, S_enc, D] (encoder output)
    p: dict,
    cfg: ModelConfig,
    ctx: ParallelCtx,
) -> jnp.ndarray:
    B, S, D = x.shape
    H, hd = cfg.num_heads, cfg.hd
    h = rms_norm(x, p["ln"]) if cfg.norm == "rmsnorm" else _ln(x, p)
    q = (h @ p["wq"]).reshape(B, S, H, hd)
    k = (enc @ p["wk"]).reshape(B, enc.shape[1], H, hd)
    v = (enc @ p["wv"]).reshape(B, enc.shape[1], H, hd)
    o = distributed_attention(q, k, v, ctx, causal=False)
    o = o.reshape(B, S, -1) @ p["wo"]
    return x + o
