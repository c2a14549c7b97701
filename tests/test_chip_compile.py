"""Ahead-of-time compiles of the paged serving kernels for a TPU v5e.

Interpret mode accepts block shapes, tilings and VMEM footprints that the
TPU compiler refuses. These tests hand both paged Pallas kernels to the
real TPU compiler, for a described (not attached) ``v5e:2x2`` topology, at
qwen3-4b's published attention widths (32 query heads, 8 KV heads, head
dim 128) with the serving pool geometry (16-token blocks, 1024 blocks).
Nothing runs; a compile that passes proves only that the chip's compiler
accepts the kernel, and that the kernel really is in the program.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and the test workers all import this
file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.flash_decode_paged import flash_decode_paged
from repro.kernels.flash_prefill_paged import flash_prefill_paged
from repro.models.registry import get_config

BLOCK_SIZE = 16
NUM_BLOCKS = 1024
TABLE_WIDTH = 64          # 1024-token tables


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _widths():
    cfg = get_config("qwen3-4b")
    return cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_


def _pool_args(sd, kv_dtype, n_kv):
    pool = sd((NUM_BLOCKS, n_kv, BLOCK_SIZE, _widths()[2]), kv_dtype)
    scales = {}
    if kv_dtype == jnp.int8:
        sc = sd((NUM_BLOCKS, n_kv, BLOCK_SIZE), jnp.float32)
        scales = {"k_scale": sc, "v_scale": sc}
    return pool, scales


def _compiled_text(fn, *args, **kwargs) -> str:
    return jax.jit(fn).lower(*args, **kwargs).compile().as_text()


# (dtype, tile, split, table width, rows): an explicit grid, and the
# derived tile (None) at the backlog cell's widest table (320 blocks) and
# batch (32 rows)
DECODE_GRIDS = [
    pytest.param(jnp.bfloat16, 8, 2, TABLE_WIDTH, 8, id="bf16"),
    pytest.param(jnp.int8, 8, 2, TABLE_WIDTH, 8, id="int8"),
    pytest.param(jnp.bfloat16, None, 1, 320, 32, id="bf16-derived-w320"),
    pytest.param(jnp.int8, None, 1, 320, 32, id="int8-derived-w320"),
]


@pytest.mark.parametrize("kv_dtype,tile,split,width,batch", DECODE_GRIDS)
def test_flash_decode_paged_compiles_for_v5e(one_chip, kv_dtype, tile,
                                             split, width, batch):
    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    hq, hkv, d = _widths()
    pool, scales = _pool_args(sd, kv_dtype, hkv)
    text = _compiled_text(
        lambda q, k, v, bt, ln, **kw: flash_decode_paged(
            q, k, v, bt, ln, kv_tile_blocks=tile, split_k=split, **kw),
        sd((batch, hq, d), jnp.bfloat16), pool, pool,
        sd((batch, width), jnp.int32), sd((batch,), jnp.int32),
        **scales)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
def test_flash_prefill_paged_compiles_for_v5e(one_chip, kv_dtype):
    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    hq, hkv, d = _widths()
    batch, chunk = 2, 256
    pool, scales = _pool_args(sd, kv_dtype, hkv)
    text = _compiled_text(
        lambda q, k, v, bt, p0, **kw: flash_prefill_paged(
            q, k, v, bt, p0, kv_tile_blocks=8, **kw),
        sd((batch, hq, chunk, d), jnp.bfloat16), pool, pool,
        sd((batch, TABLE_WIDTH), jnp.int32), sd((batch,), jnp.int32),
        **scales)
    assert "tpu_custom_call" in text
