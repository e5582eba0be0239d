"""Paged-native flash-decode kernel vs the gather-then-dense oracle.

The native kernel (kernels/paged_decode.py) must agree with the gather path
(page-gather + band kernel) to combine-order fp tolerance for arbitrary
depths, page tables, pool sizes, shard geometries, and windows — and must be
EXACT about what it reads: tail positions of a partial last page and
unallocated pages are poisoned with huge values that would blow up any leak.
"""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import jax
import jax.numpy as jnp

from repro.core import decode_attention as da
from repro.core import dispatch
from repro.core import kv_quant
from repro.core.am import CommModel
from repro.kernels import ops
from repro.kernels import paged_decode as pk
from repro.kernels.ref import NEG_INF
from repro.parallel.context import ParallelCtx
from repro.serve.kv_pool import PageAllocator, PagedLayout

H, HKV, D = 4, 2, 8
POISON = 1e4  # any leak of a masked/unallocated position is unmissable

# native-vs-oracle tolerance per storage mode: both paths dequantize the SAME
# stored values, so quantization noise cancels and only combine-order fp error
# remains; quantized modes get a little headroom for the extra scale multiply
_TOLS = {
    "fp": (2e-5, 1e-5), "bf16": (2e-5, 1e-5), "int8": (5e-5, 2e-5), "fp8": (5e-5, 2e-5),
}


def _build_pool(rng, depths, page_size, max_pages, extra_pages=0, kv_dtype="fp"):
    """Allocator-backed local pool: slot rows at the given LOCAL depths, all
    unwritten positions (page tails past depth, free pages) poisoned.

    ``kv_dtype != "fp"`` stores the pool quantized (scale side tables
    returned last); the dense oracle copy then holds the DEQUANTIZED values,
    so oracle comparisons check the read path, not quantization noise.
    Quantized poison: saturated codes under a huge scale."""
    lay = PagedLayout(
        num_pages=len(depths) * max_pages + extra_pages,
        page_size=page_size, max_pages=max_pages, n=1,
    )
    alloc = PageAllocator(lay, quantized=kv_dtype != "fp")
    if kv_dtype == "fp":
        k_pool = np.full((lay.num_pages, page_size, HKV, D), POISON, np.float32)
        v_pool = np.full_like(k_pool, POISON)
        k_scale = v_scale = None
    else:
        store = np.dtype(kv_quant.storage_dtype(kv_dtype))
        k_pool = np.full((lay.num_pages, page_size, HKV, D), 127, np.int8).astype(store)
        v_pool = k_pool.copy()
        k_scale = np.full((lay.num_pages, page_size, HKV), POISON, np.float32)
        v_scale = k_scale.copy()
    dense_k = np.zeros((len(depths), max_pages * page_size, HKV, D), np.float32)
    dense_v = np.zeros_like(dense_k)
    for slot, d in enumerate(depths):
        prompt = rng.integers(0, 2**30, (d,), dtype=np.int32)
        alloc.alloc_slot(slot, prompt, 0)
        for p in range(d):
            kv = rng.normal(size=(2, HKV, D)).astype(np.float32)
            lp, off = p // page_size, p % page_size
            pid = alloc.block_table[slot, lp]
            if kv_dtype == "fp":
                k_pool[pid, off], v_pool[pid, off] = kv[0], kv[1]
                dense_k[slot, p], dense_v[slot, p] = kv[0], kv[1]
            else:
                qk, sk = kv_quant.quantize(jnp.asarray(kv[0]), kv_dtype)
                qv, sv = kv_quant.quantize(jnp.asarray(kv[1]), kv_dtype)
                k_pool[pid, off], k_scale[pid, off] = np.asarray(qk), np.asarray(sk)
                v_pool[pid, off], v_scale[pid, off] = np.asarray(qv), np.asarray(sv)
                dense_k[slot, p] = np.asarray(kv_quant.dequantize(qk, sk))
                dense_v[slot, p] = np.asarray(kv_quant.dequantize(qv, sv))
    bt = jnp.asarray(alloc.device_table(len(depths)))
    out = (alloc, jnp.asarray(k_pool), jnp.asarray(v_pool), bt, dense_k, dense_v)
    if kv_dtype != "fp":
        out += (jnp.asarray(k_scale), jnp.asarray(v_scale))
    return out


def _stack1(*caches):
    """One-layer stacks: the cache entries take ``[L, ...]`` stacks + a layer."""
    return tuple(jnp.asarray(c)[None] for c in caches)


def _scales1(k_scale, v_scale):
    """A quantized pool's scale tables as one-layer stacks (none for fp)."""
    if k_scale is None:
        return {}
    return dict(zip(("k_scale", "v_scale"), _stack1(k_scale, v_scale)))


def _oracle_partial(q, dense_k, dense_v, pos, kv_off, stride, window):
    """Gather-then-dense band partial — the exact reference path."""
    hi = (window - 1) if window else da.BAND_INF
    return da._banded_partial(
        q, jnp.asarray(dense_k), jnp.asarray(dense_v),
        jnp.asarray(pos, jnp.int32), kv_off, stride, hi, D**-0.5,
    )


# --------------------------------------------------------------------------
# hypothesis: native == gather over random depths / tables / pools / geometry
# --------------------------------------------------------------------------


@settings(max_examples=12, deadline=None)
@given(
    depths=st.lists(st.integers(min_value=1, max_value=12), min_size=1, max_size=3),
    page_size=st.sampled_from([1, 2, 4]),
    stride=st.sampled_from([1, 2, 4]),
    window=st.sampled_from([None, 3, 8]),
    vector_pos=st.booleans(),
    kv_dtype=st.sampled_from(["fp", "int8"]),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
def test_native_matches_gather_oracle(
    depths, page_size, stride, window, vector_pos, kv_dtype, seed
):
    rng = np.random.default_rng(seed)
    max_pages = -(-max(depths) // page_size) + 1  # at least one never-written page
    shard = rng.integers(0, stride)  # striped shard geometry: kv_off = i
    built = _build_pool(rng, depths, page_size, max_pages, kv_dtype=kv_dtype)
    _, k_pool, v_pool, bt, dense_k, dense_v = built[:6]
    k_scale, v_scale = built[6:] if kv_dtype != "fp" else (None, None)
    q = jnp.asarray(rng.normal(size=(len(depths), 1, H, D)), jnp.float32)
    # global position whose last visible LOCAL slot is depth-1 on this shard
    pos = np.asarray([shard + stride * (d - 1) for d in depths], np.int32)
    if not vector_pos:
        pos = pos.min()  # scalar pos: every row at the same (lowest) depth
    o_n, lse_n = pk.paged_flash_decode(
        q, *_stack1(k_pool, v_pool), bt, jnp.asarray(pos), shard, 0,
        stride_kv=stride, window=window, **_scales1(k_scale, v_scale),
    )
    o_g, lse_g = _oracle_partial(q, dense_k, dense_v, pos, shard, stride, window)
    atol, rtol = _TOLS[kv_dtype]
    np.testing.assert_allclose(np.asarray(o_n), np.asarray(o_g), atol=atol, rtol=rtol)
    np.testing.assert_allclose(np.asarray(lse_n), np.asarray(lse_g), atol=atol, rtol=rtol)


# --------------------------------------------------------------------------
# partial last page: the in-page tail mask is where a page walk silently breaks
# --------------------------------------------------------------------------


def test_partial_last_page_exact_against_truncated_oracle():
    """Depths not divisible by page_size: the kernel must weigh the partial
    page by its LIVE tail only.  The oracle here sees just the first d
    positions (no masked garbage at all), so any tail leak — wrong lse
    weight, poison read — breaks the comparison loudly."""
    rng = np.random.default_rng(0)
    page_size, max_pages = 4, 4
    depths = [1, 5, 11]  # 1 = lone token in a page; 5, 11 = ragged tails
    _, k_pool, v_pool, bt, dense_k, dense_v = _build_pool(
        rng, depths, page_size, max_pages
    )
    q = jnp.asarray(rng.normal(size=(len(depths), 1, H, D)), jnp.float32)
    pos = jnp.asarray([d - 1 for d in depths], jnp.int32)
    o_n, lse_n = pk.paged_flash_decode(
        q, *_stack1(k_pool, v_pool), bt, pos, 0, 0, stride_kv=1
    )
    for slot, d in enumerate(depths):
        o_ref, lse_ref = ops.block_attention(
            q[slot : slot + 1],
            jnp.asarray(dense_k[slot : slot + 1, :d]),
            jnp.asarray(dense_v[slot : slot + 1, :d]),
            (d - 1, 0, 0, da.BAND_INF),
        )
        np.testing.assert_allclose(
            np.asarray(o_n[slot]), np.asarray(o_ref[0]), atol=2e-5, rtol=1e-5
        )
        np.testing.assert_allclose(
            np.asarray(lse_n[slot]), np.asarray(lse_ref[0]), atol=2e-5, rtol=1e-5
        )


def test_empty_shard_returns_exact_empty_band():
    """A shard holding nothing visible must return o = 0, lse = NEG_INF
    exactly (the psum combine depends on it): a row that visits no page
    must not resurrect with weight exp(NEG_INF - NEG_INF) = 1."""
    rng = np.random.default_rng(1)
    _, k_pool, v_pool, bt, _, _ = _build_pool(rng, [8], 4, 3)
    q = jnp.asarray(rng.normal(size=(1, 1, H, D)), jnp.float32)
    # striped shard i=3 of n=4 sees positions 3, 7, ...; pos=2 hides them all
    o, lse = pk.paged_flash_decode(
        q, *_stack1(k_pool, v_pool), bt, jnp.int32(2), 3, 0, stride_kv=4
    )
    np.testing.assert_array_equal(np.asarray(o), 0.0)
    np.testing.assert_array_equal(np.asarray(lse), np.float32(NEG_INF))


# --------------------------------------------------------------------------
# the block walk: one grid step per slot, P pages per DMA block
# --------------------------------------------------------------------------

_PS = 16  # serving page size: P = pages_per_block(16) pages per block
_P = pk.pages_per_block(_PS)


def _shuffled_pool(rng, depths, max_pages, kv_dtype, layers=1):
    """Slots at the given LOCAL depths on physical pages drawn from a
    permutation of the pool (two pages no table names); every unwritten
    position poisoned.  A depth of 0 is a free slot: its table row is all
    -1.  The pool is a stack of ``layers`` layers sharing the block table,
    each with its own K/V.  Returns the stacked pools (bf16 / quantized
    storage as asked), their stacked scale tables or None, the block table,
    and per layer the dense f32 views the oracle reads (the stored values,
    dequantized): ``[layers, B, m, Hkv, D]`` for K and for V."""
    counts = [-(-d // _PS) for d in depths]
    num_pages = sum(counts) + 2
    phys = rng.permutation(num_pages)
    bt = np.full((len(depths), max_pages), -1, np.int32)
    used = np.zeros((num_pages, _PS), bool)
    taken = 0
    for b, (d, c) in enumerate(zip(depths, counts)):
        bt[b, :c] = phys[taken : taken + c]
        taken += c
        flat = np.arange(d)
        used[bt[b, flat // _PS], flat % _PS] = True
    kv = rng.normal(size=(2, layers, num_pages, _PS, HKV, D)).astype(np.float32)
    kv = np.where(used[None, None, ..., None, None], kv, POISON)
    scales = (None, None)
    if kv_dtype == "bf16":
        pools = tuple(jnp.asarray(x, jnp.bfloat16) for x in kv)
        stored = tuple(np.asarray(x.astype(jnp.float32)) for x in pools)
    else:
        qs = [kv_quant.quantize(jnp.asarray(x), kv_dtype) for x in kv]
        pools = tuple(c for c, _ in qs)
        scales = tuple(sc for _, sc in qs)
        stored = tuple(np.asarray(kv_quant.dequantize(c, sc)) for c, sc in qs)
    dense = []
    for x in stored:
        view = np.zeros((layers, len(depths), max_pages * _PS, HKV, D), np.float32)
        for b, d in enumerate(depths):
            flat = np.arange(d)
            view[:, b, :d] = x[:, bt[b, flat // _PS], flat % _PS]
        dense.append(view)
    return pools, scales, jnp.asarray(bt), dense


# (local depths, stride_kv, kv_offset, window); every case holds a free
# slot (depth 0) first, whose row must come back exactly (0, NEG_INF)
_WALK_CASES = {
    # 1 token, one page, one whole block, one token into the second block,
    # and a depth ending mid-page in the second block
    "mixed-depths": ([0, 1, _PS, _P * _PS, _P * _PS + 1, _P * _PS + _PS + 5], 1, 0, None),
    # striped shard 1 of 2: local slot j holds global position 1 + 2j
    "stride2-offset": ([0, 3, _PS + 2, _P * _PS + 7], 2, 1, None),
    # the window's first visible position falls mid-page, mid-block
    "window-in-block": ([0, _PS - 3, 2 * _P * _PS + 9, _P * _PS + _PS + 5], 1, 0, 3 * _PS + 7),
}


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8", "fp8"])
@pytest.mark.parametrize("case", sorted(_WALK_CASES))
def test_block_walk_matches_gather_oracle(case, kv_dtype):
    """Shuffled physical pages, mixed depths around the page and block
    boundaries, shard stride and offset, and a window starting inside a
    block: the block walk must read exactly the visible positions."""
    depths, stride, kv_off, window = _WALK_CASES[case]
    rng = np.random.default_rng(7)
    max_pages = -(-max(depths) // _PS) + 1
    (k_pool, v_pool), (k_scale, v_scale), bt, (dense_k, dense_v) = _shuffled_pool(
        rng, depths, max_pages, kv_dtype
    )
    q = jnp.asarray(rng.normal(size=(len(depths), 1, H, D)), jnp.float32)
    # the free slot sits at position 0 with an all -1 table row, as the
    # engine leaves it; every other row attends to its last written slot
    pos = np.asarray([kv_off + stride * max(d - 1, 0) for d in depths], np.int32)
    o_n, lse_n = pk.paged_flash_decode(
        q, k_pool, v_pool, bt, jnp.asarray(pos), kv_off, 0,
        stride_kv=stride, window=window, k_scale=k_scale, v_scale=v_scale,
    )
    np.testing.assert_array_equal(np.asarray(o_n[0]), 0.0)
    np.testing.assert_array_equal(np.asarray(lse_n[0]), np.float32(NEG_INF))
    o_g, lse_g = _oracle_partial(q, dense_k[0], dense_v[0], pos, kv_off, stride, window)
    atol, rtol = _TOLS[kv_dtype]
    np.testing.assert_allclose(np.asarray(o_n[1:]), np.asarray(o_g[1:]), atol=atol, rtol=rtol)
    np.testing.assert_allclose(
        np.asarray(lse_n[1:]), np.asarray(lse_g[1:]), atol=atol, rtol=rtol
    )


@pytest.mark.parametrize("geometry", ["whole", "shard"])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_layer_index_reads_its_layer_of_the_stack(kv_dtype, geometry):
    """The kernel takes the model's stacked ``[L, num_pages, ...]`` pool and
    a layer index: every layer of a 3-layer stack (one block table, its own
    K/V and scales in each layer) must match the gather oracle over THAT
    layer, for a whole pool and a striped shard's (stride 2, offset 1), the
    index traced as in the model's layer loop; a free slot far past any
    depth still reads nothing."""
    stride, kv_off = {"whole": (1, 0), "shard": (2, 1)}[geometry]
    depths = [0, 5, _PS + 3, _P * _PS + 7]
    rng = np.random.default_rng(11)
    max_pages = -(-max(depths) // _PS) + 1
    (k_pool, v_pool), (k_scale, v_scale), bt, (dense_k, dense_v) = _shuffled_pool(
        rng, depths, max_pages, kv_dtype, layers=3
    )
    q = jnp.asarray(rng.normal(size=(len(depths), 1, H, D)), jnp.float32)
    pos = np.asarray([kv_off + stride * max(d - 1, 0) for d in depths], np.int32)
    pos[0] = 2**30  # the free slot's position runs on past any depth, as a parked slot's
    decode = jax.jit(lambda layer: pk.paged_flash_decode(
        q, k_pool, v_pool, bt, jnp.asarray(pos), kv_off, layer,
        stride_kv=stride, k_scale=k_scale, v_scale=v_scale,
    ))
    atol, rtol = _TOLS[kv_dtype]
    for layer in range(3):
        o_n, lse_n = decode(jnp.int32(layer))
        o_g, lse_g = _oracle_partial(q, dense_k[layer], dense_v[layer], pos, kv_off,
                                     stride, None)
        np.testing.assert_array_equal(np.asarray(lse_n[0]), np.float32(NEG_INF))
        np.testing.assert_allclose(np.asarray(o_n[1:]), np.asarray(o_g[1:]),
                                   atol=atol, rtol=rtol, err_msg=f"layer {layer}")
        np.testing.assert_allclose(np.asarray(lse_n[1:]), np.asarray(lse_g[1:]),
                                   atol=atol, rtol=rtol, err_msg=f"layer {layer}")


# --------------------------------------------------------------------------
# copy-on-write: decode through shared then privately-copied pages
# --------------------------------------------------------------------------


def test_cow_shared_page_decode():
    """Two slots share their prompt's page; slot 1 then appends through a CoW
    copy.  The native kernel must read each slot's CURRENT table — the shared
    page for slot 0, the private copy for slot 1."""
    rng = np.random.default_rng(2)
    page_size = 4
    lay = PagedLayout(num_pages=8, page_size=page_size, max_pages=2, n=1)
    alloc = PageAllocator(lay)
    prompt = np.arange(4, dtype=np.int32)  # exactly one chunk -> registered
    alloc.alloc_slot(0, prompt, 4)
    got = alloc.alloc_slot(1, prompt, 4)
    assert got.shared_pages == 1
    k_pool = np.full((lay.num_pages, page_size, HKV, D), POISON, np.float32)
    v_pool = np.full_like(k_pool, POISON)
    shared_kv = rng.normal(size=(2, page_size, HKV, D)).astype(np.float32)
    pid = int(alloc.block_table[0, 0])
    k_pool[pid], v_pool[pid] = shared_kv[0], shared_kv[1]
    # slot 1 appends at pos 2 (inside the shared page) -> private copy
    cp = alloc.ensure_append(1, 2)
    assert cp is not None
    src, dst = cp
    k_pool[dst], v_pool[dst] = k_pool[src].copy(), v_pool[src].copy()
    new_kv = rng.normal(size=(2, HKV, D)).astype(np.float32)
    k_pool[dst, 2], v_pool[dst, 2] = new_kv[0], new_kv[1]

    bt = jnp.asarray(alloc.device_table(2))
    q = jnp.asarray(rng.normal(size=(2, 1, H, D)), jnp.float32)
    pos = jnp.asarray([3, 2], jnp.int32)
    o_n, lse_n = pk.paged_flash_decode(
        jnp.asarray(q), *_stack1(k_pool, v_pool), bt, pos, 0, 0, stride_kv=1,
    )
    dense_k = np.zeros((2, lay.max_pages * page_size, HKV, D), np.float32)
    dense_v = np.zeros_like(dense_k)
    dense_k[0, :4], dense_v[0, :4] = shared_kv[0], shared_kv[1]
    dense_k[1, :4], dense_v[1, :4] = k_pool[dst], v_pool[dst]
    o_g, lse_g = _oracle_partial(q, dense_k, dense_v, pos, 0, 1, None)
    np.testing.assert_allclose(np.asarray(o_n), np.asarray(o_g), atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(lse_n), np.asarray(lse_g), atol=2e-5, rtol=1e-5)


# --------------------------------------------------------------------------
# dense cache as one implicit page run (the paged kernel for the dense engine too)
# --------------------------------------------------------------------------


@pytest.mark.parametrize("m,window", [(16, None), (32, 5), (24, None)])
def test_dense_split_k_matches_band(m, window):
    rng = np.random.default_rng(3)
    B = 3
    k_cache = jnp.asarray(rng.normal(size=(B, m, HKV, D)), jnp.float32)
    v_cache = jnp.asarray(rng.normal(size=(B, m, HKV, D)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, 1, H, D)), jnp.float32)
    pos = jnp.asarray(rng.integers(0, m, (B,)), jnp.int32)
    o_band = da.sharded_cache_decode(
        q, *_stack1(k_cache, v_cache), pos, 0, None, 1, window=window, kernel="band"
    )
    o_native = da.sharded_cache_decode(
        q, *_stack1(k_cache, v_cache), pos, 0, None, 1, window=window, kernel="native"
    )
    np.testing.assert_allclose(
        np.asarray(o_native), np.asarray(o_band), atol=2e-5, rtol=1e-5
    )


# --------------------------------------------------------------------------
# dispatch seam: the kernel-variant flag routes and keys correctly
# --------------------------------------------------------------------------


@pytest.mark.parametrize("kv_dtype", ["fp", "int8"])
def test_decode_step_kernel_flag_paged_n1(kv_dtype):
    # depths chosen so the append position sits inside an ALLOCATED page —
    # the engine guarantees this via ensure_append before every tick (an
    # unallocated append target is out of contract: the scatter drops the
    # write, the native kernel skips the page, and the gather path would
    # read clamped page 0 through the band)
    rng = np.random.default_rng(4)
    depths = [5, 3]
    page_size, max_pages = 2, 4
    built = _build_pool(rng, depths, page_size, max_pages, kv_dtype=kv_dtype)
    _, k_pool, v_pool, bt = built[:4]
    scales = built[6:] if kv_dtype != "fp" else (None, None)
    ctx = ParallelCtx()
    q = jnp.asarray(rng.normal(size=(2, 1, H, D)), jnp.float32)
    kn = jnp.asarray(rng.normal(size=(2, 1, HKV, D)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(2, 1, HKV, D)), jnp.float32)
    pos = jnp.asarray(depths, jnp.int32)  # append AT depth, attend <= pos
    outs, pools = {}, {}
    for kernel in ("gather", "native"):
        out = dispatch.decode_attention_step(
            q, kn, vn, *_stack1(k_pool, v_pool), pos, ctx, layer=0,
            block_table=bt, decode_kernel=kernel, **_scales1(*scales),
        )
        outs[kernel] = np.asarray(out[0])
        # quantized: the updated scale tables ride along and must match too
        pools[kernel] = tuple(np.asarray(a) for a in out[1:])
    atol, rtol = _TOLS[kv_dtype]
    np.testing.assert_allclose(outs["native"], outs["gather"], atol=atol, rtol=rtol)
    # the UPDATE is kernel-independent: bitwise-identical pool/scale writes
    # (the fp path keeps its exact bitwise guarantee; quantize-on-write is
    # deterministic, so the quantized path holds it too)
    for a, b in zip(pools["gather"], pools["native"]):
        np.testing.assert_array_equal(a, b)


def test_native_falls_back_to_gather_under_ref_backend():
    """REPRO_KERNELS=ref must serve 'native' with the gather oracle (bitwise
    equal outputs), so pure-jnp environments keep one code path."""
    rng = np.random.default_rng(5)
    _, k_pool, v_pool, bt, _, _ = _build_pool(rng, [5], 2, 4)
    q = jnp.asarray(rng.normal(size=(1, 1, H, D)), jnp.float32)
    pos = jnp.asarray([4], jnp.int32)
    ops.set_backend("ref")
    try:
        pools = _stack1(k_pool, v_pool)
        o_n = da.paged_cache_decode(q, *pools, bt, pos, 0, None, 1, kernel="native")
        o_g = da.paged_cache_decode(q, *pools, bt, pos, 0, None, 1, kernel="gather")
    finally:
        ops.set_backend("auto")
    np.testing.assert_array_equal(np.asarray(o_n), np.asarray(o_g))


def test_plan_key_distinguishes_decode_kernel():
    comm = CommModel(seq=256, hidden=128, n=4)
    hw = dispatch.HW_PROFILES["default"]
    keys = {
        dispatch._plan_key(
            dispatch.AttentionPlanConfig(n=4, paged=True, decode_kernel=dk), comm, hw
        )[0]
        for dk in ("native", "gather")
    }
    assert len(keys) == 2
    with pytest.raises(ValueError):
        dispatch.AttentionPlanConfig(decode_kernel="warp")
    # the n==1 dense path never builds a plan config: the resolver itself
    # must reject typos instead of silently serving the default kernel
    with pytest.raises(ValueError):
        dispatch.decode_attention_step(
            jnp.zeros((1, 1, H, D)), jnp.zeros((1, 1, HKV, D)),
            jnp.zeros((1, 1, HKV, D)), jnp.zeros((1, 1, 8, HKV, D)),
            jnp.zeros((1, 1, 8, HKV, D)), jnp.int32(0), ParallelCtx(), layer=0,
            decode_kernel="nativ",
        )


# --------------------------------------------------------------------------
# engine: version-gated block-table upload
# --------------------------------------------------------------------------


def test_block_table_upload_is_version_gated():
    """Decode ticks whose appends stay inside the current page must NOT
    re-upload the device block table; only allocator mutations (prefill,
    chunk-boundary appends, CoW, retirement) do."""
    from repro.configs import get_config
    from repro.models import transformer as tfm
    from repro.serve.engine import ServeEngine

    cfg = get_config("granite-8b").reduced()
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(6)
    # page_size 16 = one chunk holds prompt + all new tokens: after the
    # prefill upload, every decode tick stays inside the page
    eng = ServeEngine(cfg, params, max_seq=64, num_slots=2, paged=True, page_size=16)
    eng.submit(rng.integers(0, cfg.vocab_size, (4,), dtype=np.int32), 5)
    eng.step()  # prefill + first decode tick
    uploads_after_prefill = eng.bt_uploads
    assert uploads_after_prefill >= 1
    while eng.has_work:
        eng.step()
    # retirement frees pages (a table mutation) -> at most one more upload
    # would show on a NEXT sync; the decode ticks themselves added none
    assert eng.bt_uploads == uploads_after_prefill
    ticks = eng._tick
    assert eng.bt_uploads < ticks
    assert eng.kv_cache_stats()["bt_uploads"] == float(eng.bt_uploads)
