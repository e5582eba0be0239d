"""Model FLOPs of the serving steps, from shapes: the operations the model
needs, for MFU.

``attention_flops`` is the sound part of the program's
``benchmarks/common.attention_flops`` (its forward).
"""

from __future__ import annotations


def attention_flops(seq: int, heads: int, head_dim: int, *, causal: bool = True) -> float:
    """Products of one layer's attention over ``seq`` tokens (batch 1):
    2 matmuls of 2 FLOP per visible (query, key) pair and head-dim element."""
    pairs = seq * seq * (0.5 if causal else 1.0)
    return 4.0 * pairs * heads * head_dim


def prefill_flops(arch, prompt_len: int) -> float:
    """Forward of one prompt: 2 N per token plus causal attention."""
    return (2.0 * arch.matmul_params() * prompt_len
            + arch.layers * attention_flops(prompt_len, arch.heads, arch.head_dim))


def decode_flops(arch, depth: int) -> float:
    """One generated token attending ``depth`` keys: 2 N plus 4 depth H D
    per layer."""
    return (2.0 * arch.matmul_params()
            + arch.layers * 4.0 * depth * arch.heads * arch.head_dim)

