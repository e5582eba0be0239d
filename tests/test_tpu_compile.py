"""Compile the Pallas kernels for a TPU v5e without a chip.

The TPU compiler (libtpu) compiles for a described ``v5e:2x2`` topology, so
Mosaic's refusals (block shapes that break the (8, 128) tiling, unaligned
in-kernel slices, VMEM overruns) fail here instead of on the chip.  The
attention shapes are granite-8b's at real widths: 32 query heads over 8 KV
heads, head_dim 128, 128x128 blocks, bf16; the SSD scan runs at Mamba-2's
64-token chunks.  Nothing runs.

The topology is described inside a fixture: only one process at a time may
load the TPU library, so no module-import-time code may touch it.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as fa
from repro.kernels import paged_decode as pk
from repro.kernels import ssd_scan as ssd

B, S, H, HKV, D = 1, 2048, 32, 8, 128


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
    """The serving pool: 4 slots, 16-token pages, max_seq 4096."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    slots, page, max_pages = 4, 16, 256
    elem = {"bf16": jnp.bfloat16, "int8": jnp.int8, "fp8": jnp.float8_e4m3fn}[kv_dtype]
    q = sds((slots, 1, H, D), jnp.bfloat16)
    pool = sds((slots * max_pages, page, HKV, D), elem)
    scale = sds((slots * max_pages, page, HKV), jnp.float32)
    bt = sds((slots, max_pages), jnp.int32)
    pos = sds((slots,), jnp.int32)
    quant = kv_dtype != "bf16"

    def f(q, k, v, bt, pos, ks, vs):
        return pk.paged_flash_decode(
            q, k, v, bt, pos, 0, stride_kv=1, interpret=False,
            k_scale=ks if quant else None, v_scale=vs if quant else None,
        )
    _compile(f, q, pool, pool, bt, pos, scale, scale)


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
    kv = sds((num_pages, page, HKV, D), elem)
    scale = sds((num_pages, page, HKV), jnp.float32)
    bt = sds((slots, max_pages), jnp.int32)
    pos = sds((slots,), jnp.int32)
    off = sds((), jnp.int32)  # traced, as under shard_map

    def f(q, k, v, bt, pos, off, ks, vs):
        return pk.paged_flash_decode(
            q, k, v, bt, pos, off, stride_kv=stride, window=window,
            interpret=False, k_scale=ks if quant else None,
            v_scale=vs if quant else None,
        )
    _compile(f, q, kv, kv, bt, pos, off, scale, scale)


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
