"""Compile the Pallas kernels for a TPU v5e without a chip.

The TPU compiler (libtpu) compiles for a described ``v5e:2x2`` topology, so
Mosaic's refusals (block shapes that break the (8, 128) tiling, unaligned
in-kernel slices, VMEM overruns) fail here instead of on the chip.  The
attention shapes are granite-8b's at real widths: 32 query heads over 8 KV
heads, head_dim 128, 128x128 blocks, bf16; the SSD scan runs at Mamba-2's
64-token chunks.  The serving engine's jitted steps are compiled too, to check
that they update the paged pool in place.  Nothing runs.

The topology is described inside a fixture: only one process at a time may
load the TPU library, so no module-import-time code may touch it.
"""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as fa
from repro.kernels import paged_decode as pk
from repro.kernels import ssd_scan as ssd

B, S, H, HKV, D = 1, 2048, 32, 8, 128
LAYERS = 8  # the serving cell's stacked pool: the kernel reads one layer of it


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # an AOT compile for a described device is written to the persistent
    # cache but cannot be read back without a chip: keep the cache off
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text  # the Mosaic kernel, not an emulation
    return compiled


@pytest.mark.parametrize("segments", [False, True], ids=["band", "band+segments"])
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_flash_attention_compiles_for_v5e(one_chip, direction, segments):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    q = sds((B, S, H, D), jnp.bfloat16)
    kv = sds((B, S, HKV, D), jnp.bfloat16)
    band = sds((4,), jnp.int32)
    seg = sds((S,), jnp.int32)
    kw = dict(scale=D**-0.5, block_q=128, block_kv=128, interpret=False)
    if direction == "fwd":
        def f(q, k, v, band, seg):
            return fa.flash_attention_fwd(
                q, k, v, band, seg_q=seg if segments else None,
                seg_kv=seg if segments else None, **kw,
            )
        _compile(f, q, kv, kv, band, seg)
    else:
        lse = sds((B, H, S), jnp.float32)

        def f(q, k, v, o, lse, do, band, seg):
            return fa.flash_attention_bwd(
                q, k, v, o, lse, do, band, seg_q=seg if segments else None,
                seg_kv=seg if segments else None, **kw,
            )
        _compile(f, q, kv, kv, q, lse, q, band, seg)


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8", "fp8"])
def test_paged_decode_compiles_for_v5e(one_chip, kv_dtype):
    """The serving pool: 4 slots, 16-token pages, max_seq 4096, one layer
    of an 8-layer stack."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    slots, page, max_pages = 4, 16, 256
    elem = {"bf16": jnp.bfloat16, "int8": jnp.int8, "fp8": jnp.float8_e4m3fn}[kv_dtype]
    q = sds((slots, 1, H, D), jnp.bfloat16)
    pool = sds((LAYERS, slots * max_pages, page, HKV, D), elem)
    scale = sds((LAYERS, slots * max_pages, page, HKV), jnp.float32)
    bt = sds((slots, max_pages), jnp.int32)
    pos = sds((slots,), jnp.int32)
    layer = sds((), jnp.int32)
    quant = kv_dtype != "bf16"

    def f(q, k, v, bt, pos, layer, ks, vs):
        return pk.paged_flash_decode(
            q, k, v, bt, pos, 0, layer, stride_kv=1, interpret=False,
            k_scale=ks if quant else None, v_scale=vs if quant else None,
        )
    _compile(f, q, pool, pool, bt, pos, layer, scale, scale)


_CELL_POOLS = {
    # the serving cell: 64 slots, 16-token pages, max_seq 4096, 8,192 pages
    "bf16": (64, 16, 256, 8192, jnp.bfloat16, 1, None),
    "int8": (64, 16, 256, 8192, jnp.int8, 1, None),
    "fp8": (64, 16, 256, 8192, jnp.float8_e4m3fn, 1, None),
    # a dense [64, 4096] cache viewed as 128-token pages (2 pages a block)
    "dense-view": (64, 128, 32, 64 * 32, jnp.bfloat16, 1, None),
    # striped shard of a 2-way sequence split under a sliding window
    "stride2-window": (64, 16, 256, 8192, jnp.bfloat16, 2, 1024),
}


@pytest.mark.parametrize("pool", sorted(_CELL_POOLS))
def test_paged_decode_compiles_at_cell_shapes(one_chip, pool):
    """The block walk at the chat cell's pool, plus the dense view and a
    sharded windowed geometry: VMEM for the double-buffered blocks and
    Mosaic's DMA slicing are checked here, before the chip."""
    slots, page, max_pages, num_pages, elem, stride, window = _CELL_POOLS[pool]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    quant = elem in (jnp.int8, jnp.float8_e4m3fn)
    q = sds((slots, 1, H, D), jnp.bfloat16)
    kv = sds((LAYERS, num_pages, page, HKV, D), elem)
    scale = sds((LAYERS, num_pages, page, HKV), jnp.float32)
    bt = sds((slots, max_pages), jnp.int32)
    pos = sds((slots,), jnp.int32)
    off = sds((), jnp.int32)  # traced, as under shard_map
    layer = sds((), jnp.int32)  # traced, as in the model's layer loop

    def f(q, k, v, bt, pos, off, layer, ks, vs):
        return pk.paged_flash_decode(
            q, k, v, bt, pos, off, layer, stride_kv=stride, window=window,
            interpret=False, k_scale=ks if quant else None,
            v_scale=vs if quant else None,
        )
    _compile(f, q, kv, kv, bt, pos, off, layer, scale, scale)


@pytest.mark.parametrize("groups", [1, 2])
def test_ssd_scan_compiles_for_v5e(one_chip, groups):
    """The Mamba-2 SSD scan at 64-token chunks, head dim 64, state 128."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    heads, p, n = 8, 64, 128
    x = sds((B, S, heads, p), jnp.bfloat16)
    dt = sds((B, S, heads), jnp.float32)
    a = sds((heads,), jnp.float32)
    bc = sds((B, S, groups, n), jnp.bfloat16)
    _compile(lambda *args: ssd.ssd_scan_fwd(*args, chunk=64, interpret=False),
             x, dt, a, bc, bc)


# --------------------------------------------------------------------------
# the serving steps update the paged pool in place
# --------------------------------------------------------------------------

# a 3-layer granite-shaped model with 128-wide heads (the kernel's lane
# tiling), whose pool is too large for on-chip memory as at the cell's size:
# 3 layers x 8,192 pages of 8 tokens x 2 KV heads x 128
_POOL = (3, 8192, 8, 2, 128)


@pytest.fixture(scope="module")
def tiny_engine():
    from repro.configs import get_config
    from repro.models import transformer as tfm
    from repro.serve.config import ServeConfig
    from repro.serve.engine import ServeEngine

    cfg = dataclasses.replace(get_config("granite-8b").reduced(), num_layers=_POOL[0],
                              head_dim=_POOL[4])
    params = tfm.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16)
    # the engine holds a small pool here; the steps are compiled for _POOL
    return ServeEngine(cfg, params, serve=ServeConfig(
        max_seq=64, num_slots=4, paged=True, page_size=_POOL[2], num_pages=37,
        cache_dtype=jnp.bfloat16, decode_kernel="native",
    ))


def _step_args(eng, step, one_chip):
    """(jitted step, abstract args on the described chip) of one serving step."""
    def on(x):
        return jax.ShapeDtypeStruct(jnp.shape(x), jnp.asarray(x).dtype, sharding=one_chip)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    params = jax.tree.map(on, eng.params)
    cache = jax.tree.map(on, eng._cache)
    cache["k"] = cache["v"] = jax.ShapeDtypeStruct(_POOL, jnp.bfloat16, sharding=one_chip)
    B = eng.num_slots
    if step == "decode":
        return eng._decode, (params, cache, i32(B, 1))
    if step == "packed-prefill":
        return eng._get_prefill_packed(32, 2), (params, cache, i32(1, 32), i32(2), i32(2),
                                                i32(2))
    if step == "prefill":
        return eng._get_prefill(32), (params, cache, i32(1, 32), i32(), i32(), i32())
    if step == "chunk":
        return eng._chunk_step, (params, cache, i32(B, 8), i32(B), i32(B), i32(B), i32(B))
    return eng._verify, (params, cache, i32(B, 4), i32(B), i32(B))


@pytest.mark.parametrize("step", ["decode", "packed-prefill", "prefill", "chunk", "verify"])
def test_serving_step_updates_pool_in_place(one_chip, tiny_engine, monkeypatch, step):
    """Compiled for the chip, each jitted serving step takes the stacked pool
    as a donated input and returns it aliased, produces no array the size of
    one layer's slice of the pool (the model carries the stack whole through
    its layer loop and the kernel reads ``pool.at[layer, page]``), and needs
    less scratch memory than one such slice."""
    # code that asks the platform sees the CPU here: compile the kernel, not
    # its interpreter, for the described chip
    monkeypatch.setattr(pk, "resolve_interpret", lambda interpret=None: False)
    jitted, args = _step_args(tiny_engine, step, one_chip)
    compiled = jitted.lower(*args).compile()
    text = compiled.as_text()
    if step == "decode":
        assert "tpu_custom_call" in text  # the paged kernel itself

    # 1. the K and V stacks are aliased input -> output
    paths = [p for p, _ in jax.tree_util.tree_flatten_with_path(args)[0]]
    pool_params = {
        i for i, p in enumerate(paths)
        if p[0] == jax.tree_util.SequenceKey(1)
        and p[1] in (jax.tree_util.DictKey("k"), jax.tree_util.DictKey("v"))
    }
    header = text.splitlines()[0]
    aliased = {int(m) for m in re.findall(r"\}: \((\d+), \{\}", header)}
    assert len(pool_params) == 2 and pool_params <= aliased, header[:400]

    # 2. no op yields one layer's slice, in any shape (a bitcast included)
    slice_elems = int(np.prod(_POOL[1:]))
    sliced = []
    for line in text.splitlines():
        for dims in re.findall(r"= \(?\w+\[([\d,]+)\]", line):
            if int(np.prod([int(d) for d in dims.split(",")])) == slice_elems:
                sliced.append(line.strip()[:200])
    assert not sliced, sliced[:4]

    # 3. scratch: under one layer's slice of one of the two stacks
    slice_bytes = slice_elems * jnp.dtype(jnp.bfloat16).itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < slice_bytes
