"""Operations and HBM bytes of one ``paged_flash_decode`` call (one layer,
every slot), from the slot depths.

Bytes are those the kernel must move: each slot's visible pages of K and V
(``ceil(depth / page_size)`` pages; a free slot's clamped index still
fetches one page), the queries, and the split-K partial outputs and
log-sum-exps it writes in float32.  This is the modelled K/V volume of
``benchmarks/decode_bench.modeled_hbm_bytes_per_token`` (native kernel),
extended by the partials.  FLOPs: 2 products of 2 FLOP per visible key,
head and head-dim element.
"""

from __future__ import annotations

import math


def cost(depths, *, num_slots, heads, kv_heads, head_dim, page_size, max_pages,
         kv_bytes=2, q_bytes=2, pages_per_split=4):
    """(flops, bytes) of one call; ``depths`` holds the keys each active
    slot attends; the other ``num_slots - len(depths)`` slots are free."""
    pages = sum(max(1, math.ceil(d / page_size)) for d in depths)
    pages += num_slots - len(depths)
    page_bytes = page_size * kv_heads * head_dim * kv_bytes * 2  # K and V
    splits = max(1, math.ceil(max_pages / pages_per_split))
    partials = num_slots * splits * heads * (head_dim + 1) * 4
    q = num_slots * heads * head_dim * q_bytes
    flops = sum(4.0 * d * heads * head_dim for d in depths)
    return flops, pages * page_bytes + partials + q
