"""The cost functions against counts made by hand at one small shape."""

from bench.costs import paged_flash_decode, step
from bench.reference import Arch

# 2 layers, d 8, 2 heads of 4 over 1 KV head, d_ff 16, vocab 10
A = Arch(layers=2, d_model=8, heads=2, kv_heads=1, head_dim=4, d_ff=16, vocab=10)


def test_matmul_params_by_hand():
    # per layer: wq 8x8, wk 8x4, wv 8x4, wo 8x8, w1/w3 8x16, w2 16x8; head 8x10
    per_layer = 64 + 32 + 32 + 64 + 3 * 128
    assert A.matmul_params() == 2 * per_layer + 80


def test_attention_flops_by_hand():
    # causal seq 4: 10 visible pairs... counted as S^2/2 = 8 pairs, 2 heads,
    # 2 products of 2 FLOP per pair and head-dim element (4)
    assert step.attention_flops(4, 2, 4) == 4 * 8 * 2 * 4
    assert step.attention_flops(4, 2, 4, causal=False) == 4 * 16 * 2 * 4


def test_step_flops_by_hand():
    n = A.matmul_params()
    assert step.prefill_flops(A, 4) == 2 * n * 4 + 2 * 256
    assert step.decode_flops(A, 5) == 2 * n + 2 * 4 * 5 * 2 * 4


def test_paged_decode_cost_by_hand():
    # slots at depths 1 and 17 on 16-token pages (1 + 2 pages), 2 free slots
    # (1 page each): 5 pages of 16 tokens x 1 KV head x 4 x 2 bytes x (K, V)
    flops, nbytes = paged_flash_decode.cost(
        [1, 17], num_slots=4, heads=2, kv_heads=1, head_dim=4, page_size=16,
        max_pages=8, pages_per_split=4)
    assert flops == 4 * 1 * 2 * 4 + 4 * 17 * 2 * 4
    pages = 5 * 16 * 1 * 4 * 2 * 2
    partials = 4 * 2 * 2 * (4 + 1) * 4  # slots x splits x heads x (o, lse) f32
    q = 4 * 2 * 4 * 2
    assert nbytes == pages + partials + q

