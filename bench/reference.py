"""Plain reference of the benchmark's dense decoder, in jax.numpy.

It imports nothing of the program.  The architecture is the llama-style
decoder the configuration files name (granite-8b-code): pre-norm blocks,
RMSNorm with gain ``1 + w`` (eps 1e-6), rotary embeddings on the two halves
of each head (theta from the configuration), grouped-query causal attention
with scale ``head_dim ** -0.5``, a SwiGLU MLP (silu(h w1) * (h w3)) w2, a
final RMSNorm and an untied output head.  Parameters arrive as the nested dict
``{embed, layers: {attn: {ln, wq, wk, wv, wo}, mlp: {ln, w1, w2, w3}},
final_ln, lm_head}`` with layers stacked on a leading axis.

``Numerics`` says how it computes: the reference proper keeps activations in
float32 and multiplies at the highest precision; the control of ``correct``
(see PERF.md) keeps activations in bfloat16 and rounds every matrix-product
input to float8.  Attention runs in query blocks (each block against every
key, masked), so a 4k sequence fits in little memory.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Arch:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float = 10000.0

    @classmethod
    def from_config(cls, c: dict) -> "Arch":
        return cls(c["num_hidden_layers"], c["hidden_size"], c["num_attention_heads"],
                   c["num_key_value_heads"], c["head_dim"], c["intermediate_size"],
                   c["vocab_size"], c.get("rope_theta", 10000.0))

    def matmul_params(self) -> int:
        """Parameters that take part in a matrix product per token (all but
        the embedding table, which is read by index)."""
        attn = self.d_model * self.head_dim * (2 * self.heads + 2 * self.kv_heads)
        mlp = 3 * self.d_model * self.d_ff
        return self.layers * (attn + mlp) + self.d_model * self.vocab


@dataclasses.dataclass(frozen=True)
class Numerics:
    act: object = jnp.float32  # activation dtype
    fp8_inputs: bool = False  # round every matmul input to float8_e4m3fn
    precision: str = "highest"  # jax matmul precision for float32 products


REFERENCE = Numerics()
FP8 = Numerics(act=jnp.bfloat16, fp8_inputs=True, precision="default")


def _mm(a, b, num: Numerics):
    if num.fp8_inputs:
        a = a.astype(jnp.float8_e4m3fn)
        b = b.astype(jnp.float8_e4m3fn)
    a, b = a.astype(num.act), b.astype(num.act)
    return jnp.matmul(a, b, preferred_element_type=jnp.float32).astype(num.act)


def _norm(x, w, num: Numerics, eps=1e-6):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(num.act)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs  # [S, half]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(x.dtype)


class Reference:
    def __init__(self, arch: Arch, num: Numerics = REFERENCE, *, block_q: int = 512):
        self.a, self.num, self.block_q = arch, num, block_q

    def _attention(self, q, k, v):
        """q [B,S,H,D], k/v [B,S,Hkv,D] -> [B,S,H,D], causal, in query blocks."""
        a, num = self.a, self.num
        B, S = q.shape[:2]
        g = a.heads // a.kv_heads
        bq = min(self.block_q, S)
        nb = S // bq
        qb = q.reshape(B, nb, bq, a.kv_heads, g, a.head_dim).transpose(1, 0, 2, 3, 4, 5)
        kpos = jnp.arange(S)

        def block(args):
            qi, i = args  # [B, bq, Hkv, g, D]
            if num.fp8_inputs:
                qi, kk = (t.astype(jnp.float8_e4m3fn).astype(num.act) for t in (qi, k))
            else:
                kk = k
            s = jnp.einsum("bqkgd,bskd->bkgqs", qi, kk,
                           preferred_element_type=jnp.float32) * a.head_dim ** -0.5
            qpos = i * bq + jnp.arange(bq)
            vis = kpos[None, :] <= qpos[:, None]
            s = jnp.where(vis, s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1).astype(num.act)
            vv = v.astype(jnp.float8_e4m3fn).astype(num.act) if num.fp8_inputs else v
            if num.fp8_inputs:
                p = p.astype(jnp.float8_e4m3fn).astype(num.act)
            o = jnp.einsum("bkgqs,bskd->bqkgd", p, vv, preferred_element_type=jnp.float32)
            return o.astype(num.act)

        o = jax.lax.map(block, (qb, jnp.arange(nb)))  # [nb, B, bq, Hkv, g, D]
        return o.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, a.heads, a.head_dim)

    def hidden(self, params, tokens):
        """Final-normed hidden states [B, S, D] of ``tokens`` [B, S]."""
        a, num = self.a, self.num
        B, S = tokens.shape
        x = jnp.take(params["embed"], tokens, axis=0).astype(num.act)
        pos = jnp.arange(S)

        def layer(x, lp):
            at, ml = lp["attn"], lp["mlp"]
            h = _norm(x, at["ln"], num)
            q = _mm(h, at["wq"], num).reshape(B, S, a.heads, a.head_dim)
            k = _mm(h, at["wk"], num).reshape(B, S, a.kv_heads, a.head_dim)
            v = _mm(h, at["wv"], num).reshape(B, S, a.kv_heads, a.head_dim)
            q = jax.vmap(lambda t: _rope(t, pos, a.rope_theta))(q)
            k = jax.vmap(lambda t: _rope(t, pos, a.rope_theta))(k)
            o = self._attention(q, k, v).reshape(B, S, a.heads * a.head_dim)
            x = x + _mm(o, at["wo"], num)
            h = _norm(x, ml["ln"], num)
            up = (jax.nn.silu(_mm(h, ml["w1"], num).astype(jnp.float32))
                  * _mm(h, ml["w3"], num).astype(jnp.float32)).astype(num.act)
            x = x + _mm(up, ml["w2"], num)
            return x, None

        with jax.default_matmul_precision(num.precision):
            x, _ = jax.lax.scan(layer, x, params["layers"])
        return _norm(x, params["final_ln"], num)

    def logits(self, params, tokens):
        with jax.default_matmul_precision(self.num.precision):
            out = _mm(self.hidden(params, tokens), params["lm_head"], self.num)
        return out.astype(jnp.float32)
