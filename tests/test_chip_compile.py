"""Ahead-of-time compiles of the paged serving kernels for a TPU v5e.

Interpret mode accepts block shapes, tilings and VMEM footprints that the
TPU compiler refuses. These tests hand both paged Pallas kernels to the
real TPU compiler, for a described (not attached) ``v5e:2x2`` topology, at
qwen3-4b's published attention widths (32 query heads, 8 KV heads, head
dim 128) with the serving pool geometry (16-token blocks, 1024 blocks).
Nothing runs; a compile that passes proves only that the chip's compiler
accepts the kernel, and that the kernel really is in the program.

Two more compiles take the engine's whole decode and chunk-prefill step
programs at qwen3-4b widths over a 1,500-block pool, with the pools
donated as the engine donates them, and read the compiled program's
scratch memory: a step that updates the pool in place holds no second
copy of it.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and the test workers all import this
file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.flash_decode_paged import flash_decode_paged
from repro.kernels.flash_prefill_paged import flash_prefill_paged
from repro.models.registry import get_config, model_fns
from repro.serve.paged_step import paged_decode_step, paged_prefill_chunked

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


STEP_LAYERS = 2
STEP_BLOCKS = 1500        # the backlog cell's pool
STEP_ROWS, STEP_WIDTH, STEP_CHUNK = 32, 256, 256


def _step_programs(sd, cfg, pools):
    """(name, jitted step with the pools donated, its leading args) for
    the engine's decode and chunk-prefill programs."""
    def scales(p):
        return {"k_scale": p[2], "v_scale": p[3]} if len(p) == 4 else {}

    def decode(params, tokens, tables, lengths, *p):
        return paged_decode_step(params, tokens, p[0], p[1], tables,
                                 lengths, cfg, **scales(p))

    def chunk(params, tokens, pos0, last_rel, table, blk, off, *p):
        return paged_prefill_chunked(params, tokens, pos0, last_rel, p[0],
                                     p[1], table, blk, off, cfg,
                                     **scales(p))

    def donated(fn, lead):
        return jax.jit(fn, donate_argnums=tuple(
            range(len(lead), len(lead) + len(pools))))

    i32 = jnp.int32
    params = jax.tree.map(lambda a: sd(a.shape, a.dtype), jax.eval_shape(
        model_fns(cfg).init, jax.random.PRNGKey(0)))
    dec = (params, sd((STEP_ROWS,), i32), sd((STEP_ROWS, STEP_WIDTH), i32),
           sd((STEP_ROWS,), i32))
    chk = (params, sd((1, STEP_CHUNK), i32), sd((), i32), sd((1,), i32),
           sd((1, STEP_WIDTH), i32), sd((STEP_CHUNK,), i32),
           sd((STEP_CHUNK,), i32))
    return [("decode", donated(decode, dec), dec),
            ("chunk", donated(chunk, chk), chk)]


@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
def test_paged_steps_update_the_pool_in_place(one_chip, monkeypatch,
                                              kv_dtype):
    """Both step programs, Pallas kernels in, keep the pool in place:
    their compiled scratch memory is under an eighth of the pools' bytes
    (a step that passes the pool through its layer scan as ``xs``/``ys``
    holds a whole second pool there), and no ``copy`` in the program has
    a pool's full shape."""
    # the dispatchers pick the Pallas kernels by the backend's name; the
    # described chip is not the process's backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    cfg = get_config("qwen3-4b").replace(param_dtype="bfloat16",
                                        n_layers=STEP_LAYERS)
    shape = (STEP_LAYERS, STEP_BLOCKS, cfg.n_kv_heads, BLOCK_SIZE,
             cfg.head_dim_)
    pools = [sd(shape, kv_dtype)] * 2
    if kv_dtype == jnp.int8:
        pools += [sd(shape[:-1], jnp.float32)] * 2
    pool_bytes = sum(p.size * p.dtype.itemsize for p in pools)
    pool_shapes = {",".join(map(str, p.shape)) for p in pools}
    for name, step, lead in _step_programs(sd, cfg, pools):
        compiled = step.lower(*lead, *pools).compile()
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < pool_bytes / 8, (name, temp, pool_bytes)
        text = compiled.as_text()
        assert "tpu_custom_call" in text, name
        copied = re.findall(r"= \w+\[([\d,]+)\]\{[^}]*\} copy\(", text)
        assert not pool_shapes & set(copied), (name, copied)
