"""Pallas TPU kernel: paged single-token decode attention with Softermax.

Same Unnormed-Softmax-Unit dataflow as ``kernels/flash_decode`` — running
IntMax + running denominator with power-of-two rescales, fused with the A·V
accumulation — but the KV cache is a *block pool*: a flat array of fixed-size
physical blocks, indirected through a per-sequence block table. Because the
Softermax recurrence is order-free (every rescale is an exact exponent add),
blocks can be streamed in table order with no pre-pass over the scores, which
is exactly what makes the paged layout free for this kernel.

The layer index, the block ids each grid column reads (the block table,
clamped as below) and the per-row lengths are scalar-prefetch operands
(``PrefetchScalarGridSpec``, SMEM): their entries are available *before*
the kernel body runs, so the KV BlockSpec index maps perform the gather —
each grid step DMAs physical blocks of the one layer straight from the
whole ``(L, N, Hkv, BS, D)`` pool into VMEM — and the body reads its
row's length as a scalar. The layer scan of ``serve/paged_step.py``
carries that whole pool and writes each layer's new rows into it in
place, so no program copies the pool. (A ``(1, 1)`` VMEM block over a
``(B, 1)`` lengths array is refused by the TPU compiler for B > 1: a
block's last two dims must be (8, 128)-divisible or span the array.)

Three grid-level restructurings over the naive per-head walk (all three are
pure reorganizations of the same recurrence — outputs are unchanged):

* **Whole-block gathers, KV heads folded in.** The grid is ``(B, S,
  spl)``: one step owns a row's every query head, its query tile is
  ``(Hkv, G, D)``, and each gather operand takes one whole pool block
  ``(1, Hkv, BS, D)`` — every KV head of the block in one contiguous DMA
  (32 KB at 8 heads of 128 in bf16) where a per-head grid moved it in
  ``Hkv`` copies from ``Hkv`` steps. The QK and AV dots run batched over
  the head axis. On a TPU v5e each grid step and gather operand costs
  fixed scalar work, so the grid's size, not the bytes, set the time of
  the per-head walk.
* **Multi-block KV tiles.** Each kv grid step gathers ``kv_tile_blocks``
  (T) whole blocks — T DMAs the pipeline overlaps within one step — and
  processes them as one ``(Hkv, T*BS, D)`` VMEM tile. Without a tile from
  the caller, T comes from the block shape (``ref.decode_tile_blocks``:
  ``T*BS >= 128`` rows, a K tile of at most 512 KiB, so many KV heads or
  wide heads get a smaller T), clamped to the table width. The block each
  slot reads is looked up once per launch (``ref.live_block_column``): a
  slot past its row's live blocks reads the row's last live block, so
  dead slots and the steps past a row's length repeat a block index, and
  the pipeline copies nothing for a repeated index; ``@pl.when`` skips
  their compute.
* **Split-K.** The KV walk is partitioned across a *parallel* grid axis of
  ``split_k`` lanes; each lane emits its partial ``(m, d, acc)`` state and
  a small jnp second stage merges them with the associative Softermax
  combine (``core.softermax.softermax_merge`` — exact power-of-two
  rescales under the joint IntMax) before the final normalize. One long
  request's decode step then finishes in ~1/split_k of the serial table
  walk instead of serializing on a single lane.

**Fused int8 dequant-on-gather.** With ``k_scale``/``v_scale`` (per-row f32
scales, block-indexed like the pool) the K/V pools are int8: the HBM→VMEM
DMA moves half the bytes, and dequantization is fused *after* the matmuls
instead of widening the tiles — ``S = q·Kᵀ`` against the raw int8 codes
then ``S *= k_scale`` per column (exact: the scale is a per-row constant of
K), and ``p *= v_scale`` before ``p·V`` (same identity on the V side). Both
rescales touch the (Hkv, group, T*BS) score tile, not the value tile, so
the dequant cost stays O(tile-row) while the accumulate stays fp32 — the
paper's int-storage / wide-accumulate split applied to the KV side. TPU
tiling note: int8 VMEM tiles are (32, 128)-granular (vs (16, 128) for
bf16), so int8 pools waste no sublane padding when ``block_size >= 32``.

Table entries past a sequence's length may be garbage (the pool's reserved
block 0): no slot reads them, and the length mask zeroes the columns of the
last live block past the length.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.numerics import NEG_INF
from repro.core.softermax import softermax_finalize, softermax_merge
from repro.kernels.flash_decode_paged.ref import (decode_tile_blocks,
                                                  live_block_column,
                                                  split_layout)


def concat_tiles(refs, axis: int = 0, idx: tuple = (0, 0)):
    """Assemble one VMEM tile from the T per-slot gather operands (each
    ref holds one pool block of one layer; ``idx`` strips its leading
    unit block axes). Shared by the decode and prefill kernel bodies: the
    decode kernel's whole-block ``(1, 1, Hkv, BS, D)`` operands concat
    along rows (axis 1) and its ``(1, Hkv, 1, BS)`` scales (``idx=(0,)``)
    along axis 2; the prefill kernel's ``(1, 1, 1, BS, D)`` blocks
    (``idx=(0, 0, 0)``) concat along rows (axis 0) and its ``(1, 1, 1,
    BS)`` scale rows along columns (axis 1)."""
    if len(refs) == 1:
        return refs[0][idx]
    return jnp.concatenate([r[idx] for r in refs], axis=axis)


def layer_operands(k_pool, v_pool, k_scale, v_scale, layer):
    """The operands both paged kernels take: the pools with their layer
    axis, the layer index as a ``(1,)`` int32 scalar-prefetch operand,
    and an int8 pool's scales of that one layer as f32 ``(N, Hkv, 1,
    BS)``. A one-layer ``(N, Hkv, BS, D)`` pool (no ``layer``) gains a
    unit layer axis, a free reshape, and is read at layer 0; a pool with
    its layer axis needs its ``layer``.

    The scales are the one operand read per layer rather than in place:
    on a TPU an f32 ``(..., Hkv, BS)`` array is laid out with its block
    axis minor (the TPU's default for a minor dim under 128 lanes), along
    which no BlockSpec can gather, so one layer's scales are relaid here
    — a copy of one layer of the scale pool, never of the pool."""
    if (layer is None) != (k_pool.ndim == 4):
        raise ValueError(f"a pool of rank {k_pool.ndim} with layer "
                         f"{layer!r}: pass a layer index with an "
                         f"(L, N, Hkv, BS, D) pool, and none with one "
                         f"layer's (N, Hkv, BS, D)")
    if layer is None:
        k_pool, v_pool = k_pool[None], v_pool[None]
    if k_scale is not None:
        N, Hkv, BS = k_scale.shape[-3:]
        k_scale, v_scale = (
            (sc if layer is None else sc[layer]).astype(jnp.float32)
            .reshape(N, Hkv, 1, BS) for sc in (k_scale, v_scale))
    lay = jnp.asarray(0 if layer is None else layer, jnp.int32).reshape(1)
    return k_pool, v_pool, k_scale, v_scale, lay


def _paged_decode_kernel(layer_ref, ids_ref, len_ref, q_ref, *rest,
                         intmax: bool, block_size: int, tile_blocks: int,
                         quantized: bool):
    T = tile_blocks
    k_refs, v_refs = rest[:T], rest[T:2 * T]
    n = 2 * T
    if quantized:
        ksc_refs, vsc_refs = rest[n:n + T], rest[n + T:n + 2 * T]
        n += 2 * T
    acc_ref, m_ref, d_ref, acc_scr, m_scr, d_scr = rest[n:]
    j = pl.program_id(2)
    spl = pl.num_programs(2)                  # kv tiles per split lane

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        d_scr[...] = jnp.zeros_like(d_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    kv_len = len_ref[pl.program_id(0)]
    jj = pl.program_id(1) * spl + j           # global kv tile index
    k_start = jj * (T * block_size)

    @pl.when(k_start < kv_len)
    def _body():
        q = q_ref[0].astype(jnp.float32)      # (Hkv, G, D)
        # T whole-block gathers (every KV head) as one (Hkv, T*BS, D) tile
        k = concat_tiles(k_refs, axis=1)
        v = concat_tiles(v_refs, axis=1)
        s = jax.lax.dot_general(
            q, k.astype(jnp.float32), (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)   # (Hkv, G, T*BS)
        if quantized:
            # dequant fused post-dot: k_scale is constant per K row, so
            # scaling the (G, T*BS) score columns equals scaling the
            # (T*BS, D) tile — for a fraction of the flops
            s = s * concat_tiles(ksc_refs, 2, (0,))  # (Hkv, 1, T*BS)
        kj = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(kj < kv_len, s, NEG_INF)
        m_prev = m_scr[...]
        # IntMax via ceil-after-reduce (ceil is monotone, so this equals
        # max(ceil(s)) with a (Hkv, G, 1) ceil instead of a full-tile pass)
        sm = jnp.max(s, axis=2, keepdims=True)
        m_new = jnp.maximum(m_prev, jnp.ceil(sm) if intmax else sm)
        alpha = jnp.exp2(m_prev - m_new)      # exact power-of-two
        p = jnp.exp2(s - m_new)
        if quantized:
            pv = p * concat_tiles(vsc_refs, 2, (0,))  # fold v_scale
        else:
            pv = p
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            pv, v.astype(jnp.float32), (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)   # (Hkv, G, D)
        d_scr[...] = d_scr[...] * alpha + jnp.sum(p, axis=2, keepdims=True)
        m_scr[...] = m_new

    @pl.when(j == spl - 1)
    def _fin():
        # emit the lane's partial state; lanes whose every tile sat past
        # kv_len emit the merge identity (NEG_INF, 0, 0) from _init
        acc_ref[0, 0] = acc_scr[...]
        m_ref[0, 0] = m_scr[...]
        d_ref[0, 0] = d_scr[...]


@functools.partial(
    jax.jit,
    static_argnames=("intmax", "kv_tile_blocks", "split_k", "interpret"))
def flash_decode_paged(
    q: jax.Array,             # (B, Hq, D) — pre-scaled single-token queries
    k_pool: jax.Array,        # (L, N, Hkv, BS, D) physical block pool of
    #                           every layer; (N, Hkv, BS, D) is one layer
    v_pool: jax.Array,
    block_tables: jax.Array,  # (B, W) int32 physical block ids
    lengths: jax.Array,       # (B,) int32 valid cache lengths
    *,
    layer=None,               # () int32 layer the kernel reads; None with
    #                           a one-layer pool
    k_scale: jax.Array = None,   # (L, N, Hkv, BS) f32: int8 pools' row
    v_scale: jax.Array = None,   # scales, laid out as the pool (no L with
    #                              a one-layer pool)
    intmax: bool = True,
    kv_tile_blocks: Optional[int] = None,  # pool blocks a kv grid step
    #                                        gathers (T); None derives it
    #                                        from the block shape
    split_k: int = 1,         # parallel partitions of the KV walk
    interpret: bool = False,
) -> jax.Array:
    k_pool, v_pool, k_scale, v_scale, lay = layer_operands(
        k_pool, v_pool, k_scale, v_scale, layer)
    B, Hq, D = q.shape
    _, _, Hkv, BS, _ = k_pool.shape
    W = block_tables.shape[1]
    G = Hq // Hkv
    quantized = k_scale is not None

    if kv_tile_blocks is None:
        kv_tile_blocks = decode_tile_blocks(BS, Hkv, D,
                                            k_pool.dtype.itemsize)
    # clamp the tiling to the table (shared geometry — ref.split_layout):
    # T-block tiles, S split lanes of spl tiles each, covering Wp >= W
    # table columns. The block each (grid column, row) gathers is looked
    # up once here, not per grid step: its live table entry, else the
    # row's last live one (``live_block_column``), so dead slots and dead
    # steps repeat a block index and the pipeline copies nothing for them
    T, S, spl, Wp = split_layout(W, kv_tile_blocks, split_k)
    lens = lengths.astype(jnp.int32)
    cols = live_block_column(jnp.arange(Wp, dtype=jnp.int32)[None, :],
                             lens[:, None], BS, W)
    ids = jnp.take_along_axis(block_tables.astype(jnp.int32), cols, axis=1)
    qf = q.reshape(B, Hkv, G, D)

    def kv_spec(t, layered, block):
        # one gather per tile slot: (layer, block id, whole block) of the
        # pools, the layer scalar-prefetched beside the ids; (block id,
        # whole block) of one layer's scales
        def _map(b, s, j, layer_ref, ids_ref, len_ref):
            return ((layer_ref[0],) if layered else ()) + \
                (ids_ref[b, (s * spl + j) * T + t],) + (0,) * len(block)
        return pl.BlockSpec((1,) * (1 + layered) + block, _map)

    in_specs = [
        pl.BlockSpec((1, Hkv, G, D), lambda b, s, j, *_: (b, 0, 0, 0)),
    ]
    in_specs += [kv_spec(t, True, (Hkv, BS, D))
                 for _ in "kv" for t in range(T)]
    inputs = [qf] + [k_pool] * T + [v_pool] * T
    if quantized:
        # scales ride the same scalar-prefetch block-table gather as the
        # values; the unit axis keeps in-kernel reads (Hkv, 1, BS)
        in_specs += [kv_spec(t, False, (Hkv, 1, BS))
                     for _ in "kv" for t in range(T)]
        inputs += [k_scale] * T + [v_scale] * T

    def out_map(b, s, j, *_):
        return (b, s, 0, 0, 0)

    part = pl.BlockSpec((1, 1, Hkv, G, 1), out_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, S, spl),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, Hkv, G, D), out_map),
            part, part,
        ],
        scratch_shapes=[
            pltpu.VMEM((Hkv, G, D), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
        ],
    )

    acc, m, d = pl.pallas_call(
        functools.partial(_paged_decode_kernel, intmax=intmax,
                          block_size=BS, tile_blocks=T, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, S, Hkv, G, D), jnp.float32),
            jax.ShapeDtypeStruct((B, S, Hkv, G, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, S, Hkv, G, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(lay, ids, lens, *inputs)

    # second stage: associative Softermax merge of the split partials under
    # the joint (Int)Max, then the one deferred normalize. With split_k=1
    # this is exactly the old in-kernel epilogue (scale = 2^0 = 1).
    _, d2, acc2 = softermax_merge(m, d, acc, axis=1)
    o = softermax_finalize(acc2, d2)          # (B, Hkv, G, D)
    return o.reshape(B, Hq, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# Legacy per-head single-block kernel — benchmark baseline only.
# ---------------------------------------------------------------------------


def _paged_decode_kernel_single(bt_ref, len_ref, q_ref, k_ref, v_ref, *rest,
                                intmax: bool, block_size: int,
                                quantized: bool, n_q_heads: int):
    if quantized:
        ksc_ref, vsc_ref, o_ref, acc_scr, m_scr, d_scr = rest
    else:
        o_ref, acc_scr, m_scr, d_scr = rest
    j = pl.program_id(1)
    nb = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        d_scr[...] = jnp.zeros_like(d_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    kv_len = len_ref[pl.program_id(0) // n_q_heads]
    k_start = j * block_size

    @pl.when(k_start < kv_len)
    def _body():
        q = q_ref[0].astype(jnp.float32)              # (1, D)
        k = k_ref[0, 0].astype(jnp.float32)           # (BS, D)
        v = v_ref[0, 0].astype(jnp.float32)           # (BS, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # (1, BS)
        if quantized:
            s = s * ksc_ref[0, 0]                     # (1, BS)
        kj = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kj < kv_len, s, NEG_INF)
        m_prev = m_scr[...]
        sl = jnp.ceil(s) if intmax else s
        m_new = jnp.maximum(m_prev, jnp.max(sl, axis=1, keepdims=True))
        alpha = jnp.exp2(m_prev - m_new)              # exact power-of-two
        p = jnp.exp2(s - m_new)
        pv = p * vsc_ref[0, 0] if quantized else p
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            pv, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        d_scr[...] = d_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_scr[...] = m_new

    @pl.when(j == nb - 1)
    def _fin():
        d = d_scr[...]
        recip = jnp.where(d > 0, 1.0 / jnp.where(d > 0, d, 1.0), 0.0)
        o_ref[0] = (acc_scr[...] * recip).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("intmax", "interpret"))
def flash_decode_paged_single(
    q: jax.Array,             # (B, Hq, D) — pre-scaled single-token queries
    k_pool: jax.Array,        # (N, Hkv, BS, D) physical block pool
    v_pool: jax.Array,
    block_tables: jax.Array,  # (B, nb) int32 physical block ids
    lengths: jax.Array,       # (B,) int32 valid cache lengths
    *,
    k_scale: jax.Array = None,
    v_scale: jax.Array = None,
    intmax: bool = True,
    interpret: bool = False,
) -> jax.Array:
    """The pre-tiling kernel: grid ``(B*Hq, nb)``, one pool block per kv
    step, every query head of a GQA group re-gathering the group's shared
    KV. Kept ONLY as the baseline that ``benchmarks/decode_paged_bench.py``
    measures the grouped/tiled/split kernel against (and as a parity oracle
    for the restructure); serving dispatches the grouped kernel above."""
    B, Hq, D = q.shape
    N, Hkv, BS, _ = k_pool.shape
    nb = block_tables.shape[1]
    group = Hq // Hkv
    quantized = k_scale is not None

    qf = q.reshape(B * Hq, 1, D)
    lens = lengths.astype(jnp.int32)
    bt = block_tables.astype(jnp.int32)

    def kv_map(bh, j, bt_ref, len_ref):
        return (bt_ref[bh // Hq, j], (bh % Hq) // group, 0, 0)

    def q_map(bh, j, bt_ref, len_ref):
        return (bh, 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, D), q_map),
        pl.BlockSpec((1, 1, BS, D), kv_map),
        pl.BlockSpec((1, 1, BS, D), kv_map),
    ]
    inputs = [qf, k_pool, v_pool]
    if quantized:
        in_specs += [pl.BlockSpec((1, 1, 1, BS), kv_map),
                     pl.BlockSpec((1, 1, 1, BS), kv_map)]
        inputs += [k_scale.astype(jnp.float32).reshape(N, Hkv, 1, BS),
                   v_scale.astype(jnp.float32).reshape(N, Hkv, 1, BS)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B * Hq, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, D), q_map),
        scratch_shapes=[
            pltpu.VMEM((1, D), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel_single, intmax=intmax,
                          block_size=BS, quantized=quantized,
                          n_q_heads=Hq),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * Hq, 1, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(bt, lens, *inputs)

    return out.reshape(B, Hq, D)
