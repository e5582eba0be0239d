"""Model assembly: init / forward / loss / prefill / decode for every family.

Layers are stacked along a leading L dim and iterated with ``lax.scan`` (+
optional remat) so the lowered HLO is depth-independent — essential for the
512-device dry-run compiles.  Family switches:

  dense   — attention + gated MLP
  moe     — attention + MoE (TP or EP mode)
  ssm     — SSD blocks only (attention-free; Mesh-Attention N/A)
  hybrid  — parallel attention + SSD heads, then MLP (hymba)
  audio   — whisper-style encoder(full attn)-decoder(causal+cross) w/ stub
  vlm     — pixtral: decoder backbone + patch-embedding merge (stub frontend)

Decode uses the striped KV cache (core/decode_attention) for attention
families, O(1) state updates for SSM, and absorbed-latent MLA decode
(DeepSeek-style matrix absorption) for MiniCPM3 — the cache stores the
256-d latent, not 40 decompressed heads.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.core import kv_quant
from repro.kernels import ops as kops
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import dense_init, layer_norm, rms_norm, rope, vocab_cross_entropy
from repro.models.mlp import init_mlp_params, mlp_block
from repro.parallel.context import ParallelCtx

__all__ = [
    "init_params",
    "forward",
    "loss_fn",
    "init_cache",
    "prefill",
    "prefill_packed",
    "prefill_chunk",
    "decode_step",
    "verify_step",
    "param_count",
]


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------


def init_params(cfg: ModelConfig, key, dtype=jnp.float32, ctx: Optional[ParallelCtx] = None):
    ctx = ctx or ParallelCtx()
    keys = jax.random.split(key, 12)
    D, V, L = cfg.d_model, cfg.vocab_size, cfg.num_layers
    p: Dict = {"embed": dense_init(keys[0], (V, D), in_axis=-1, dtype=dtype)}

    layers: Dict = {}
    if cfg.family != "ssm":
        layers["attn"] = attn.init_attention_params(keys[1], cfg, L, dtype)
    if cfg.ssm is not None:
        layers["ssm"] = ssm_mod.init_ssm_params(keys[2], cfg, L, dtype)
    if cfg.moe is not None:
        layers["moe"] = moe_mod.init_moe_params(keys[3], cfg, L, dtype, ctx)
    elif cfg.family != "ssm" and cfg.d_ff > 0:
        layers["mlp"] = init_mlp_params(keys[4], cfg, L, dtype)
    if cfg.encoder_layers:
        layers["xattn"] = attn.init_cross_attention_params(keys[5], cfg, L, dtype)
    p["layers"] = layers

    if cfg.norm == "layernorm":
        p["final_ln"] = jnp.ones((D,), dtype)
        p["final_ln_b"] = jnp.zeros((D,), dtype)
    else:
        p["final_ln"] = jnp.zeros((D,), dtype)
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(keys[6], (D, V), dtype=dtype)

    if cfg.encoder_layers:
        Le = cfg.encoder_layers
        enc_layers = {
            "attn": attn.init_attention_params(keys[7], cfg, Le, dtype),
            "mlp": init_mlp_params(keys[8], cfg, Le, dtype),
        }
        enc = {"layers": enc_layers}
        if cfg.norm == "layernorm":
            enc["final_ln"] = jnp.ones((D,), dtype)
            enc["final_ln_b"] = jnp.zeros((D,), dtype)
        else:
            enc["final_ln"] = jnp.zeros((D,), dtype)
        p["encoder"] = enc
    if cfg.frontend:
        p["frontend"] = {"proj": dense_init(keys[9], (cfg.frontend_dim, D), dtype=dtype)}
    return p


def param_count(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


# --------------------------------------------------------------------------
# blocks
# --------------------------------------------------------------------------


def _final_norm(x, p, cfg):
    if cfg.norm == "layernorm":
        return layer_norm(x, p["final_ln"], p["final_ln_b"])
    return rms_norm(x, p["final_ln"])


def _decoder_block(x, lp, cfg: ModelConfig, ctx: ParallelCtx, positions, enc=None,
                   segments=None):
    """One decoder layer. Returns (x, aux_loss)."""
    aux = jnp.float32(0.0)
    if cfg.family == "ssm":
        return ssm_mod.ssm_block(x, lp["ssm"], cfg, ctx), aux
    if cfg.hybrid:
        a = attn.attention_block(x, lp["attn"], cfg, ctx, positions, segments=segments) - x
        s = ssm_mod.ssm_block(x, lp["ssm"], cfg, ctx) - x
        x = x + 0.5 * (a + s)
    else:
        x = attn.attention_block(x, lp["attn"], cfg, ctx, positions, segments=segments)
    if enc is not None:
        x = attn.cross_attention_block(x, enc, lp["xattn"], cfg, ctx)
    if cfg.moe is not None:
        x, aux = moe_mod.moe_block(x, lp["moe"], cfg, ctx)
    elif cfg.d_ff > 0:
        x = mlp_block(x, lp["mlp"], cfg, ctx)
    return x, aux


def _encoder_block(x, lp, cfg: ModelConfig, ctx: ParallelCtx, positions):
    x = attn.attention_block(x, lp["attn"], cfg, ctx, positions, causal=False)
    return mlp_block(x, lp["mlp"], cfg, ctx)


def _stack_scan(f, carry, xs, ctx: ParallelCtx):
    """lax.scan over stacked layers, or a python unroll (ctx.unroll_layers —
    used by the dry-run so XLA cost analysis sees every layer)."""
    if not ctx.unroll_layers:
        return lax.scan(f, carry, xs)
    L = jax.tree.leaves(xs)[0].shape[0]
    ys = []
    for i in range(L):
        carry, y = f(carry, jax.tree.map(lambda a: a[i], xs))
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree.map(lambda *zs: jnp.stack(zs), *ys)
    else:
        ys = None
    return carry, ys


def _scan_layers(x, layers, body, ctx: ParallelCtx):
    """scan over stacked layer params, accumulating aux loss."""

    def f(carry, lp):
        x, aux = carry
        x, a = body(x, lp)
        return (x, aux + a), None

    if ctx.remat:
        f = jax.checkpoint(f, prevent_cse=False)
    (x, aux), _ = _stack_scan(f, (x, jnp.float32(0.0)), layers, ctx)
    return x, aux


# the attention cache leaves: carried WHOLE through the layer loop, never
# scanned as per-layer slices, so each layer's write is one scatter into the
# stack and each read indexes it in place (a scanned slice would be copied
# out of the stack and written back into a new one, every layer, every step)
_KV_KEYS = ("k", "v", "k_scale", "v_scale")
# cache entries every layer shares: per-slot positions, the block table
_SHARED_KEYS = ("pos", "bt")


def _split_cache(cache):
    """(kv, per_layer): the attention K/V (and scale) stacks, carried whole,
    and the per-layer state (SSM, cross-attention K/V) scanned slice by
    slice."""
    kv = {k: cache[k] for k in _KV_KEYS if k in cache}
    per_layer = {k: v for k, v in cache.items() if k not in _KV_KEYS + _SHARED_KEYS}
    return kv, per_layer


def _cache_layer_loop(body, x, cache, layers, ctx: ParallelCtx, remat: bool = False):
    """Run ``body(x, kv, lp, layer, cl) -> (x, kv, cl)`` over the stacked
    layers: the attention stacks ``kv`` ride in the carry whole with the
    layer index scanned beside the params, the per-layer state ``cl`` is
    scanned.  Returns ``(x, cache)`` with every cache leaf but ``pos``/``bt``
    replaced."""
    kv, per_layer = _split_cache(cache)
    num_layers = jax.tree.leaves(layers)[0].shape[0]

    def f(carry, inp):
        x, kv = carry
        lp, layer, cl = inp
        x, kv, cl = body(x, kv, lp, layer, cl)
        return (x, kv), cl

    if remat:
        f = jax.checkpoint(f, prevent_cse=False)
    xs = (layers, jnp.arange(num_layers, dtype=jnp.int32), per_layer)
    (x, kv), per_layer = _stack_scan(f, (x, kv), xs, ctx)
    return x, {**cache, **kv, **per_layer}


# --------------------------------------------------------------------------
# forward / loss
# --------------------------------------------------------------------------


def _encode_audio(params, cfg, ctx, frames):
    """Stubbed conv frontend: mel frames -> projected embeddings -> encoder."""
    x = frames.astype(params["embed"].dtype) @ params["frontend"]["proj"]
    x = ctx.constrain(x, "seq", None)
    pos = jnp.arange(cfg.encoder_seq, dtype=jnp.int32)
    enc = params["encoder"]

    def body(h, lp):
        return _encoder_block(h, lp, cfg, ctx, pos), jnp.float32(0.0)

    x, _ = _scan_layers(x, enc["layers"], body, ctx)
    if cfg.norm == "layernorm":
        x = layer_norm(x, enc["final_ln"], enc["final_ln_b"])
    else:
        x = rms_norm(x, enc["final_ln"])
    return x


def _merge_patches(x, params, positions, patches, num_patches):
    """VLM stub: positions < num_patches take projected patch embeddings
    (works under striping: gathered by true position)."""
    px = patches.astype(x.dtype) @ params["frontend"]["proj"]  # [B, P, D]
    idx = jnp.clip(positions, 0, num_patches - 1)
    gathered = jnp.take(px, idx, axis=1)  # [B, S, D]
    mask = (positions < num_patches)[None, :, None]
    return jnp.where(mask, gathered, x)


def forward(params, cfg: ModelConfig, ctx: ParallelCtx, batch: Dict) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """-> (logits [B,S,V], aux_loss). batch: tokens [B,S], positions [S],
    optional segments [S] (packed multi-document rows: causal within each
    document), frames [B,S_enc,F] (audio) / patches [B,P,F] (vlm)."""
    tokens = batch["tokens"]
    positions = batch["positions"]
    segments = batch.get("segments")
    if segments is not None and cfg.ssm is not None:
        raise ValueError(
            "packed multi-document batches are attention-only: the SSD "
            "recurrent state has no per-document reset"
        )
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.frontend == "vision_stub":
        x = _merge_patches(x, params, positions, batch["patches"], cfg.num_patches)
    x = ctx.constrain(x, "seq", None)

    enc = None
    if cfg.encoder_layers:
        enc = _encode_audio(params, cfg, ctx, batch["frames"])

    body = functools.partial(
        _decoder_block, cfg=cfg, ctx=ctx, positions=positions, enc=enc, segments=segments
    )
    x, aux = _scan_layers(x, params["layers"], lambda h, lp: body(h, lp), ctx)
    x = _final_norm(x, params, cfg)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ head.astype(x.dtype)
    return logits, aux


def loss_fn(params, cfg: ModelConfig, ctx: ParallelCtx, batch: Dict) -> Tuple[jnp.ndarray, Dict]:
    logits, aux = forward(params, cfg, ctx, batch)
    ce = vocab_cross_entropy(logits, batch["labels"], batch.get("mask"))
    loss = ce + 0.01 * aux
    return loss, {"loss": loss, "ce": ce, "aux": aux}


# --------------------------------------------------------------------------
# serving: cache init / prefill / decode
# --------------------------------------------------------------------------


def _attn_cache_dims(cfg: ModelConfig):
    """(kv_heads, k_dim, v_dim) as stored in the cache."""
    if cfg.mla is not None:
        m = cfg.mla
        d = m.kv_lora_rank + m.qk_rope_head_dim
        return 1, d, d  # absorbed-latent cache: one "head" of latent width
    return cfg.num_kv_heads, cfg.hd, cfg.hd


def init_cache(cfg: ModelConfig, batch: int, cap: int, dtype=jnp.bfloat16, ctx=None,
               paged=None, kv_dtype: str = "fp"):
    """Decode cache with a PER-SLOT position vector ``pos: [B]`` — each batch
    row (serving slot) may sit at a different depth, which is what lets the
    continuous-batching engine decode mixed-depth slots in one jitted step.

    ``paged`` (a ``repro.serve.kv_pool.PagedLayout``) switches the attention
    K/V to a physical page pool ``[L, num_pages, n*page_size, Hkv, D]`` plus
    an int32 block table ``"bt": [batch, max_pages]`` (-1 = unallocated):
    memory scales with allocated pages, not ``batch x cap``, and identical
    prompt prefixes can share refcounted pages.  SSM / cross-attention state
    stays per-slot dense (it is O(1) or encoder-sized per slot).

    ``kv_dtype`` ("fp" | "int8" | "fp8", paged only) stores the page pool
    quantized with per-(token, kv-head) scales in side tables
    ``"k_scale"/"v_scale": [L, num_pages, n*page_size, Hkv]`` f32 that share
    the pool's physical indexing (same page ids, same columns)."""
    L = cfg.num_layers
    cache: Dict = {"pos": jnp.zeros((batch,), jnp.int32)}
    if cfg.family != "ssm":
        hkv, dk, dv = _attn_cache_dims(cfg)
        if paged is not None:
            n = ctx.sp_size if ctx is not None else 1
            if paged.n != n:
                raise ValueError(
                    f"paged layout is sharded over n={paged.n} but the ctx has "
                    f"sp_size={n}"
                )
            if paged.virtual_cap < cap:
                raise ValueError(
                    f"paged virtual capacity {paged.virtual_cap} < cap {cap}"
                )
            store = kv_quant.storage_dtype(kv_dtype, dtype)
            cache["k"] = jnp.zeros((L, paged.num_pages, paged.chunk, hkv, dk), store)
            cache["v"] = jnp.zeros((L, paged.num_pages, paged.chunk, hkv, dv), store)
            cache["bt"] = jnp.full((batch, paged.max_pages), -1, jnp.int32)
            if kv_dtype != "fp":
                shape = (L, paged.num_pages, paged.chunk, hkv)
                cache["k_scale"] = jnp.zeros(shape, kv_quant.SCALE_DTYPE)
                cache["v_scale"] = jnp.zeros(shape, kv_quant.SCALE_DTYPE)
        else:
            if kv_dtype != "fp":
                raise ValueError("quantized KV storage requires the paged cache")
            cache["k"] = jnp.zeros((L, batch, cap, hkv, dk), dtype)
            cache["v"] = jnp.zeros((L, batch, cap, hkv, dv), dtype)
    if cfg.ssm is not None:
        cache["ssm"] = ssm_mod.init_ssm_cache(cfg, L, batch, dtype)
    if cfg.encoder_layers:
        # cross-attention K/V precomputed from the encoder at prefill
        H, hd = cfg.num_heads, cfg.hd
        cache["cross_k"] = jnp.zeros((L, batch, cfg.encoder_seq, H, hd), dtype)
        cache["cross_v"] = jnp.zeros((L, batch, cfg.encoder_seq, H, hd), dtype)
    return cache


def _decode_qkv(h, lp, cfg: ModelConfig, pos):
    """Cache-space projections for decode / chunk append. h [B,S,D] ->
    (q [B,S,Hq,dk], k_new [B,S,hkv,dk], v_new [B,S,hkv,dv], scale).
    ``pos`` is a scalar, a [B] per-slot vector (S=1 decode), or a full [B,S]
    position grid (continuous-prefill chunks)."""
    B, S = h.shape[0], h.shape[1]
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 2:
        positions = pos  # [B, S] chunk grid
    elif pos.ndim == 1:
        positions = pos[:, None]
    else:
        positions = jnp.full((1,), pos, jnp.int32)
    if cfg.mla is not None:
        m = cfg.mla
        qk = m.qk_nope_head_dim + m.qk_rope_head_dim
        cq = rms_norm(h @ lp["wq_a"], lp["q_ln"])
        q = (cq @ lp["wq_b"]).reshape(B, S, cfg.num_heads, qk)
        q_nope, q_rope = q[..., : m.qk_nope_head_dim], q[..., m.qk_nope_head_dim :]
        q_rope = rope(q_rope, positions, cfg.rope_theta)
        kv_a = h @ lp["wkv_a"]
        c_kv = rms_norm(kv_a[..., : m.kv_lora_rank], lp["kv_ln"])
        k_rope = rope(kv_a[..., None, m.kv_lora_rank :], positions, cfg.rope_theta)
        # absorb W^{kv_b}_K into q: q_lat[h, r] = sum_n q_nope[h,n] Wb[r, h, n]
        wb = lp["wkv_b"].reshape(m.kv_lora_rank, cfg.num_heads, -1)
        wb_k = wb[..., : m.qk_nope_head_dim]
        q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, wb_k)
        q_eff = jnp.concatenate([q_lat, q_rope], axis=-1)  # [B,S,H,kvr+rope]
        kv_new = jnp.concatenate([c_kv[:, :, None, :], k_rope], axis=-1)  # latent "K"
        scale = qk**-0.5
        return q_eff, kv_new, kv_new, scale
    hd = cfg.hd
    q = h @ lp["wq"]
    k = h @ lp["wk"]
    v = h @ lp["wv"]
    if cfg.qkv_bias:
        q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
    q = rope(q.reshape(B, S, cfg.num_heads, hd), positions, cfg.rope_theta)
    k = rope(k.reshape(B, S, cfg.num_kv_heads, hd), positions, cfg.rope_theta)
    v = v.reshape(B, S, cfg.num_kv_heads, hd)
    return q, k, v, hd**-0.5


def _decode_attn_out(o, h_in, lp, cfg: ModelConfig):
    B, S = o.shape[0], o.shape[1]
    if cfg.mla is not None:
        m = cfg.mla
        o_lat = o[..., : m.kv_lora_rank]  # latent-space values
        wb = lp["wkv_b"].reshape(m.kv_lora_rank, cfg.num_heads, -1)
        wb_v = wb[..., m.qk_nope_head_dim :]
        ov = jnp.einsum("bshr,rhv->bshv", o_lat, wb_v)
        return h_in + ov.reshape(B, S, -1) @ lp["wo"]
    return h_in + o.reshape(B, S, -1) @ lp["wo"]


def _attn_update(out, kv):
    """The ``(o, k, v[, k_scale, v_scale])`` of an attention cache step ->
    ``(o, kv)`` with the updated stacks."""
    return out[0], {**kv, **dict(zip(_KV_KEYS, out[1:]))}


def _decode_block(x, lp, kv, layer, cache_l, cfg: ModelConfig, ctx: ParallelCtx, pos,
                  bt=None):
    """One layer's decode.  ``kv``: the attention cache stacks over every
    layer (written at ``layer`` only); ``cache_l``: this layer's slices of
    the per-layer state; ``bt`` is the (layer-shared) block table when the
    K/V cache is paged.  Returns (y, kv, new cache_l)."""
    new_cache = dict(cache_l)
    if cfg.family == "ssm":
        y, new_cache["ssm"] = ssm_mod.ssm_decode_step(x, lp["ssm"], cache_l["ssm"], cfg)
        return y, kv, new_cache

    h = rms_norm(x, lp["attn"]["ln"]) if cfg.norm == "rmsnorm" else layer_norm(
        x, lp["attn"]["ln"], lp["attn"]["ln_b"]
    )
    q, k_new, v_new, scale = _decode_qkv(h, lp["attn"], cfg, pos)
    # the decode cache is ALWAYS striped (even for contiguous-train archs):
    # prefill restripes K/V once; appends then stay load-balanced forever
    o, kv = _attn_update(attn.decode_attention_step(
        q, k_new, v_new, kv["k"], kv["v"], pos, ctx, layer=layer,
        window=cfg.window, layout="striped", scale=scale, block_table=bt,
        k_scale=kv.get("k_scale"), v_scale=kv.get("v_scale"),
    ), kv)
    y = _decode_attn_out(o, x, lp["attn"], cfg)

    if cfg.hybrid:
        s, new_cache["ssm"] = ssm_mod.ssm_decode_step(x, lp["ssm"], cache_l["ssm"], cfg)
        y = x + 0.5 * ((y - x) + (s - x))

    if cfg.encoder_layers:
        # cross-attention against the precomputed encoder K/V
        hc = rms_norm(y, lp["xattn"]["ln"]) if cfg.norm == "rmsnorm" else layer_norm(
            y, lp["xattn"]["ln"], lp["xattn"]["ln_b"]
        )
        B = y.shape[0]
        qc = (hc @ lp["xattn"]["wq"]).reshape(B, 1, cfg.num_heads, cfg.hd)
        oc, _ = kops.block_attention(
            qc, cache_l["cross_k"], cache_l["cross_v"], kops.full_band()
        )
        y = y + oc.reshape(B, 1, -1) @ lp["xattn"]["wo"]

    if cfg.moe is not None:
        y, _ = moe_mod.moe_block(y, lp["moe"], cfg, ctx)
    elif cfg.d_ff > 0:
        y = mlp_block(y, lp["mlp"], cfg, ctx)
    return y, kv, new_cache


def decode_step(params, cache, tokens, cfg: ModelConfig, ctx: ParallelCtx):
    """One greedy decode step over all slots.
    tokens [B,1] -> (next [B,1], new cache, logits [B,1,V]).

    ``cache["pos"]`` is the per-slot position vector [B] (a scalar still
    works for legacy callers); every row advances by one — rows holding
    retired/free slots tick harmlessly (their cache writes are masked past
    capacity and their outputs are ignored by the engine).

    A paged cache's block table ``cache["bt"]`` is threaded to the decode
    backend VERBATIM (layer-shared device operand): ``ctx.decode_kernel``
    picks whether it drives a page gather or is scalar-prefetched into the
    native paged kernel (kernels/paged_decode.py).  The K/V stacks ride the
    layer loop whole (``_cache_layer_loop``): under a jit that donates the
    cache, the step updates them in place."""
    pos = cache["pos"]
    bt = cache.get("bt")  # paged K/V: block table, shared by every layer
    x = jnp.take(params["embed"], tokens, axis=0)
    x = ctx.constrain(x, None, None)

    def body(x, kv, lp, layer, cl):
        return _decode_block(x, lp, kv, layer, cl, cfg, ctx, pos, bt=bt)

    x, new_cache = _cache_layer_loop(body, x, cache, params["layers"], ctx)
    x = _final_norm(x, params, cfg)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ head.astype(x.dtype)
    nxt = jnp.argmax(logits, axis=-1).astype(tokens.dtype)
    new_cache["pos"] = pos + 1
    return nxt, new_cache, logits


def prefill_chunk(params, cfg: ModelConfig, ctx: ParallelCtx, batch: Dict, cache):
    """Continuous prefill: append one C-token chunk per slot into the live
    cache and run prefix-causal attention over everything resident.

    ``batch`` carries fixed-shape [B(=num_slots), C] operands so ONE jitted
    trace serves every tick:

      * ``tokens``  [B, C] int32 — chunk tokens, right-padded per row
      * ``starts``  [B] int32 — absolute position of each row's chunk base
      * ``lens``    [B] int32 — valid tokens per row (0 = inactive row:
        nothing is written and the row's output is garbage to be ignored)
      * ``write_starts`` [B] int32 — skip KV writes below this absolute
        position (a shared prefix already resident in the paged pool)
      * ``pos_set`` [B] int32 — new ``cache["pos"]`` per row, or -1 to keep
        the current value (mid-prefill rows stay parked past capacity so the
        shared decode step's writes keep dropping)

    Returns (logits [B, V] at each row's LAST valid chunk token, new cache).
    The logits row is only meaningful for rows whose final chunk this is —
    the engine samples the first generated token from it that same tick, so
    a chunked request's first token lands on exactly the tick its one-shot
    twin would have produced it.  Token-for-token equivalence with one-shot
    ``prefill`` holds because the chunk path runs the SAME banded kernel,
    stripe math, and lse-psum combine (bitwise on the reference backend).

    Works on the dense sharded cache and the paged pool (``cache["bt"]``);
    attention-only decoder archs (no SSM state, no cross-attention, no
    frontend) — the same restriction packed/paged prefill already has.
    """
    tokens = batch["tokens"]
    lens = jnp.asarray(batch["lens"], jnp.int32)
    pos_set = jnp.asarray(batch["pos_set"], jnp.int32)
    C = tokens.shape[1]
    x, new_cache = _chunk_forward(
        params, cfg, ctx, tokens, batch["starts"], lens, batch["write_starts"],
        cache,
    )
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    last = jnp.clip(lens - 1, 0, C - 1)
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)  # [B, 1, D]
    logits = x_last[:, 0] @ head.astype(x.dtype)  # [B, V]
    new_cache["pos"] = jnp.where(pos_set >= 0, pos_set, cache["pos"])
    return logits, new_cache


def _chunk_forward(params, cfg: ModelConfig, ctx: ParallelCtx, tokens, starts,
                   lens, write_starts, cache):
    """Shared core of ``prefill_chunk`` and ``verify_step``: append a
    [B, C] chunk batch into the live cache through the banded multi-row
    attention path and return the final-norm hidden states for EVERY chunk
    position.  Returns ``(x [B, C, D], new cache)``."""
    if cfg.ssm is not None or cfg.encoder_layers or cfg.frontend is not None:
        raise ValueError("chunked prefill serves attention-only decoder archs")
    starts = jnp.asarray(starts, jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    write_starts = jnp.asarray(write_starts, jnp.int32)
    C = tokens.shape[1]
    positions = starts[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    bt = cache.get("bt")  # paged K/V: block table, shared by every layer
    x = jnp.take(params["embed"], tokens, axis=0)
    x = ctx.constrain(x, None, None)

    def body(x, kv, lp, layer, cl):
        h = rms_norm(x, lp["attn"]["ln"]) if cfg.norm == "rmsnorm" else layer_norm(
            x, lp["attn"]["ln"], lp["attn"]["ln_b"]
        )
        q, k_new, v_new, scale = _decode_qkv(h, lp["attn"], cfg, positions)
        # the decode cache is ALWAYS striped; chunk rows scatter straight to
        # their owner shards exactly like single-token appends
        o, kv = _attn_update(attn.chunk_attention_step(
            q, k_new, v_new, kv["k"], kv["v"], starts, lens, write_starts,
            ctx, layer=layer, window=cfg.window, layout="striped", scale=scale,
            block_table=bt, k_scale=kv.get("k_scale"), v_scale=kv.get("v_scale"),
        ), kv)
        y = _decode_attn_out(o, x, lp["attn"], cfg)
        if cfg.moe is not None:
            y, _ = moe_mod.moe_block(y, lp["moe"], cfg, ctx)
        elif cfg.d_ff > 0:
            y = mlp_block(y, lp["mlp"], cfg, ctx)
        return y, kv, cl

    x, new_cache = _cache_layer_loop(body, x, cache, params["layers"], ctx)
    return _final_norm(x, params, cfg), new_cache


def verify_step(params, cfg: ModelConfig, ctx: ParallelCtx, batch: Dict, cache,
                return_logits: bool = False):
    """Speculative verify: score K candidate tokens per slot in ONE banded
    chunk launch and commit the longest accepted prefix in-graph.

    ``batch`` carries fixed-shape [B(=num_slots), K] operands (one jit trace
    serves every tick):

      * ``tokens`` [B, K] int32 — column 0 is the row's CURRENT token
        (exactly what vanilla decode would feed this tick), columns
        ``1 .. K-1`` the proposer's draft
      * ``starts`` [B] int32 — each row's current cache position (the
        current token's K/V is written there, as in plain decode)
      * ``lens``   [B] int32 — 0: inactive row (nothing written, ``pos``
        unchanged); 1: a plain one-token decode tick; ``k``: verify a
        ``k-1``-token draft
      * ``write_starts`` [B] int32 — forwarded to the chunk scatter
        (normally == starts)

    Greedy longest-accepted-prefix: with ``y[i] = argmax`` of the logits at
    chunk position i, draft token ``tokens[i+1]`` is ACCEPTED while it
    equals ``y[i]`` — each accepted position's context is by then fully
    committed tokens, so ``y[i]`` is bitwise what vanilla decode would have
    produced at that step.  The committed tokens are ``y[0 .. commit-1]``
    with ``commit = accepted + 1`` (the output at the last accepted
    position is always kept: it is vanilla decode's next token whether or
    not any draft survived).  K/V for positions past the committed prefix
    is stale speculative data — invisible behind the band (reads stop at
    ``pos``) and rewritten before ``pos`` ever reaches it; the paged engine
    additionally frees now-unneeded tail pages (allocator rollback).

    Returns ``(y [B, K] int32, commit [B] int32, new cache)`` with
    ``pos = starts + commit`` for active rows; ``return_logits`` appends the
    raw per-position logits ``[B, K, V]`` (debug / error-bound checks)."""
    tokens = batch["tokens"]
    starts = jnp.asarray(batch["starts"], jnp.int32)
    lens = jnp.asarray(batch["lens"], jnp.int32)
    B, K = tokens.shape
    x, new_cache = _chunk_forward(
        params, cfg, ctx, tokens, starts, lens, batch["write_starts"], cache
    )
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ head.astype(x.dtype)  # [B, K, V]
    y = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, K]
    if K > 1:
        match = (tokens[:, 1:] == y[:, :-1]) & (
            jnp.arange(1, K, dtype=jnp.int32)[None, :] < lens[:, None]
        )
        accepted = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)
    else:
        accepted = jnp.zeros((B,), jnp.int32)
    commit = jnp.where(lens > 0, jnp.minimum(accepted + 1, lens), 0)
    new_cache["pos"] = jnp.where(lens > 0, starts + commit, cache["pos"])
    if return_logits:
        return y, commit, new_cache, logits
    return y, commit, new_cache


def _cache_scatter_indices(cfg: ModelConfig, S: int, cap: int, n: int):
    """Static map: prefill K/V index j -> striped-cache global index.

    Striped cache convention: position p lives at global index
    (p % n) * (cap/n) + p // n (shard p % n, slot p // n).  For striped-train
    archs the prefill array index j already means position
    (j // (S/n)) + n*(j % (S/n)), which maps to contiguous per-shard blocks —
    zero data movement.  Contiguous-train archs (hymba) pay one restripe.
    """
    import numpy as np

    j = np.arange(S)
    if n <= 1:
        return jnp.asarray(j)
    if cfg.causal_layout == "striped":
        p = (j // (S // n)) + n * (j % (S // n))
    else:
        p = j
    g = (p % n) * (cap // n) + p // n
    return jnp.asarray(g)


def _paged_prefill_coords(positions, bt_rows, n: int, page_size: int, write_mask):
    """Scatter coordinates for writing true positions ``positions`` [S]
    through a block table into the pool ``[num_pages, n*page_size, ...]``
    (striped cache convention: position p lives on shard p % n at local
    index p // n, i.e. pool column (p % n) * page_size + (p // n) % page_size
    of logical page (p // n) // page_size).  ``bt_rows`` is one request's
    row [max_pages], or a per-token [S, max_pages] (packed prefill, each
    token routed through its own document's slot).  Masked / unallocated
    tokens get an out-of-range page index so ``mode="drop"`` discards them."""
    max_pages = bt_rows.shape[-1]
    p = jnp.asarray(positions, jnp.int32)
    j = p // n
    lp = j // page_size
    col = (p % n) * page_size + j % page_size
    lp_c = jnp.clip(lp, 0, max_pages - 1)
    if bt_rows.ndim == 1:
        page = bt_rows[lp_c]
    else:
        page = jnp.take_along_axis(bt_rows, lp_c[:, None], axis=1)[:, 0]
    write = write_mask & (page >= 0) & (lp < max_pages)
    return jnp.where(write, page, jnp.int32(2**30)), col


def _project_kv_for_cache(h, lp, cfg: ModelConfig, positions):
    """The K/V (or MLA latent) a prefill writes into the cache for ``h``
    [B, S, D] at ``positions`` [S]."""
    B, S = h.shape[0], h.shape[1]
    if cfg.mla is not None:
        m = cfg.mla
        kv_a = h @ lp["wkv_a"]
        c_kv = rms_norm(kv_a[..., : m.kv_lora_rank], lp["kv_ln"])
        k_rope = rope(kv_a[..., None, m.kv_lora_rank :], positions, cfg.rope_theta)
        lat = jnp.concatenate([c_kv[:, :, None, :], k_rope], axis=-1)
        return lat, lat
    k = h @ lp["wk"]
    v = h @ lp["wv"]
    if cfg.qkv_bias:
        k, v = k + lp["bk"], v + lp["bv"]
    k = rope(k.reshape(B, S, cfg.num_kv_heads, cfg.hd), positions, cfg.rope_theta)
    v = v.reshape(B, S, cfg.num_kv_heads, cfg.hd)
    return k, v


def prefill(params, cfg: ModelConfig, ctx: ParallelCtx, batch: Dict, cache):
    """Forward over the prompt, writing the striped KV cache per layer.

    For striped-layout archs the prefill chunks ARE the cache shards (token t
    on shard t mod n) — K/V land with no resharding; this is the paper's
    locality property carried into serving.

    ``batch`` may carry an optional ``"length": [B]`` of true prompt lengths
    when tokens are right-padded to a bucket (the continuous-batching
    engine's bucketed prefill): the returned logits are taken at each row's
    own last REAL position and ``cache["pos"]`` starts each row at its own
    length.  Causality makes the trailing pad tokens invisible to the real
    ones, and decode overwrites each pad's cache entry before first reading
    that position.

    A PAGED ``cache`` (it carries ``"bt"``) is the whole slot pool: K/V
    scatter through the block-table row of ``batch["slot"]`` (int32 scalar)
    straight into the physical pages.  ``batch["shared_len"]`` (int32 scalar,
    default 0) marks a prefix admitted as SHARED pages — those positions are
    skipped (the owner's K/V is already there and other slots are reading
    it); pads (``positions >= length``) never touch the pool, so no pages are
    spent on bucket padding.  Requires batch=1 tokens and an attention-only
    decoder arch (SSM state and cross-attention K/V stay per-slot dense).
    """
    tokens, positions = batch["tokens"], batch["positions"]
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.frontend == "vision_stub":
        x = _merge_patches(x, params, positions, batch["patches"], cfg.num_patches)
    x = ctx.constrain(x, "seq", None)
    enc = None
    if cfg.encoder_layers:
        enc = _encode_audio(params, cfg, ctx, batch["frames"])

    S = tokens.shape[1]
    has_attn = cfg.family != "ssm"
    has_ssm = cfg.ssm is not None
    paged = "bt" in cache
    if paged:
        if cfg.ssm is not None or cfg.encoder_layers:
            raise ValueError("the paged cache serves attention-only decoder archs")
        if tokens.shape[0] != 1:
            raise ValueError("paged prefill writes one request (batch=1) per call")
        n = max(ctx.sp_size, 1)
        page_size = cache["k"].shape[2] // n
        slot = jnp.asarray(batch["slot"], jnp.int32)
        shared_len = jnp.asarray(batch.get("shared_len", 0), jnp.int32)
        length_s = (
            batch["length"].astype(jnp.int32)[0] if "length" in batch else jnp.int32(S)
        )
        write_mask = (positions < length_s) & (positions >= shared_len)
        page_idx, col_idx = _paged_prefill_coords(
            positions, cache["bt"][slot], n, page_size, write_mask
        )
        g_idx = None
    else:
        cap = cache["k"].shape[2] if has_attn else None
        g_idx = _cache_scatter_indices(cfg, S, cap, ctx.sp_size) if has_attn else None

    def body(x, kv, lp, layer, cl):
        new_cl = dict(cl)
        aux = jnp.float32(0.0)
        if has_attn:
            h = rms_norm(x, lp["attn"]["ln"]) if cfg.norm == "rmsnorm" else layer_norm(
                x, lp["attn"]["ln"], lp["attn"]["ln_b"]
            )
            kk, vv = _project_kv_for_cache(h, lp["attn"], cfg, positions)
            # one scatter per array into the stacks; a quantized pool
            # quantizes at write time, exactly like appends
            if paged:
                kv = kv_quant.store(kv, (layer, page_idx, col_idx), kk[0], vv[0])
            else:
                rows = jnp.arange(kk.shape[0])[:, None]
                kv = kv_quant.store(kv, (layer, rows, g_idx[None, :]), kk, vv)
        if cfg.encoder_layers:
            B = x.shape[0]
            new_cl["cross_k"] = (enc @ lp["xattn"]["wk"]).reshape(
                B, cfg.encoder_seq, cfg.num_heads, cfg.hd
            ).astype(cl["cross_k"].dtype)
            new_cl["cross_v"] = (enc @ lp["xattn"]["wv"]).reshape(
                B, cfg.encoder_seq, cfg.num_heads, cfg.hd
            ).astype(cl["cross_v"].dtype)
        # run the block; collect SSM final state where present
        if cfg.family == "ssm":
            x, st = ssm_mod.ssm_block(x, lp["ssm"], cfg, ctx, return_state=True)
            new_cl["ssm"] = {
                "conv": st["conv"].astype(cl["ssm"]["conv"].dtype),
                "state": st["state"],
            }
        elif cfg.hybrid:
            a = attn.attention_block(x, lp["attn"], cfg, ctx, positions) - x
            sx, st = ssm_mod.ssm_block(x, lp["ssm"], cfg, ctx, return_state=True)
            new_cl["ssm"] = {
                "conv": st["conv"].astype(cl["ssm"]["conv"].dtype),
                "state": st["state"],
            }
            x = x + 0.5 * (a + (sx - x))
            if cfg.d_ff > 0:
                x = mlp_block(x, lp["mlp"], cfg, ctx)
        else:
            x, aux = _decoder_block(x, lp, cfg, ctx, positions, enc=enc)
        return x, kv, new_cl

    x, new_cache = _cache_layer_loop(body, x, cache, params["layers"], ctx, remat=ctx.remat)
    x = _final_norm(x, params, cfg)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    B = tokens.shape[0]
    if "length" in batch:
        # right-padded bucket: each row's last real token sits where
        # positions == length-1 (striping scrambles index != position)
        length = batch["length"].astype(jnp.int32)
        last_idx = jnp.argmax(positions[None, :] == (length[:, None] - 1), axis=1)
        x_last = jnp.take_along_axis(x, last_idx[:, None, None], axis=1)
        new_pos = length
    else:
        # under striping the LAST POSITION is not the last index
        last_idx = jnp.argmax(positions)
        x_last = jnp.take(x, last_idx[None], axis=1)
        new_pos = jnp.full((B,), S, jnp.int32)
    logits = x_last @ head.astype(x.dtype)
    if paged:
        # the pool cache's pos covers every slot; only this one was prefilled
        new_cache["pos"] = cache["pos"].at[slot].set(length_s)
    else:
        new_cache["pos"] = new_pos
    return logits, new_cache


def prefill_packed(params, cfg: ModelConfig, ctx: ParallelCtx, batch: Dict, cache):
    """Packed multi-document prefill: several prompts share ONE batch row.

    The row carries a document (segment-id) attention mask — causal within
    each prompt, nothing across prompts — and each document's K/V is
    scattered into ITS OWN slot row of the pool cache, so one forward pass
    prefills several serving slots.

    ``batch`` (all in the row's striped order where applicable):
      tokens    [1, P]  the packed, right-padded row
      positions [P]     per-document positions (restart at each doc start)
      segments  [P]     document id per token; pads carry id >= k
      doc_lens  [k]     true prompt lengths (runtime)
      slots     [k]     pool slot per document (runtime)
      shared_lens [k]   optional: tokens admitted as SHARED pages per doc
                        (paged cache only) — skipped by the scatter

    ``cache`` is the POOL cache ([L, num_slots, cap, ...]), or the PAGED pool
    ([L, num_pages, n*page_size, ...] + block table ``"bt"``) — each
    document's K/V then scatters through its slot's block-table row, and
    positions below ``shared_lens[d]`` are left to the pages' owner.  Returns
    (first-token logits [k, V], new cache).  Attention-only decoder archs:
    the SSD recurrent state has no per-document reset, encoder/frontend
    archs have per-row side inputs that do not pack.
    """
    if cfg.ssm is not None or cfg.encoder_layers or cfg.frontend:
        raise ValueError("packed prefill supports attention-only decoder archs")
    tokens, positions = batch["tokens"], batch["positions"]
    segments = batch["segments"]
    doc_lens = batch["doc_lens"].astype(jnp.int32)
    slots = batch["slots"].astype(jnp.int32)
    k_docs = slots.shape[0]
    n = ctx.sp_size
    paged = "bt" in cache

    x = jnp.take(params["embed"], tokens, axis=0)
    x = ctx.constrain(x, "seq", None)

    pad = segments >= k_docs
    seg_c = jnp.clip(segments, 0, k_docs - 1)
    if paged:
        # paged coordinates per token: document d's position p goes through
        # slot slots[d]'s block-table row to (page, n*page_size column);
        # pads and shared-prefix positions are dropped by the scatter
        page_size = cache["k"].shape[2] // max(n, 1)
        shared = batch.get("shared_lens")
        shared = (
            jnp.zeros((k_docs,), jnp.int32) if shared is None
            else jnp.asarray(shared, jnp.int32)
        )
        write_mask = (~pad) & (positions >= shared[seg_c])
        row_idx, g_idx = _paged_prefill_coords(
            positions, cache["bt"][slots[seg_c]], max(n, 1), page_size, write_mask
        )
    else:
        nslots, cap = cache["k"].shape[1], cache["k"].shape[2]
        # cache coordinates per token: document d's position p lands in slot
        # row slots[d] at the striped cache index (p % n)*(cap/n) + p//n;
        # pads get an out-of-range row and are dropped by the scatter
        row_idx = jnp.where(pad, nslots, slots[seg_c])
        if n > 1:
            g_idx = (positions % n) * (cap // n) + positions // n
        else:
            g_idx = positions

    def body(x, kv, lp, layer, cl):
        h = rms_norm(x, lp["attn"]["ln"]) if cfg.norm == "rmsnorm" else layer_norm(
            x, lp["attn"]["ln"], lp["attn"]["ln_b"]
        )
        kk, vv = _project_kv_for_cache(h, lp["attn"], cfg, positions)
        # one scatter per array into the stacks (a quantized pool quantizes
        # at write time, like appends); pads are dropped
        kv = kv_quant.store(kv, (layer, row_idx, g_idx), kk[0], vv[0])
        x, _ = _decoder_block(x, lp, cfg, ctx, positions, segments=segments)
        return x, kv, cl

    x, new_cache = _cache_layer_loop(body, x, cache, params["layers"], ctx, remat=ctx.remat)
    x = _final_norm(x, params, cfg)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    # document d's last real token sits where segments == d AND positions ==
    # doc_lens[d]-1 (striping scrambles index != position)
    match = (segments[None, :] == jnp.arange(k_docs)[:, None]) & (
        positions[None, :] == (doc_lens - 1)[:, None]
    )
    last_idx = jnp.argmax(match, axis=1)  # [k]
    x_last = x[0, last_idx]  # [k, D]
    logits = x_last @ head.astype(x.dtype)
    new_cache["pos"] = cache["pos"].at[slots].set(doc_lens)
    return logits, new_cache
