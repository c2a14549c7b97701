"""Pallas TPU kernel: paged single-token decode attention with Softermax.

Same Unnormed-Softmax-Unit dataflow as ``kernels/flash_decode`` — running
IntMax + running denominator with power-of-two rescales, fused with the A·V
accumulation — but the KV cache is a *block pool*: a flat array of fixed-size
physical blocks, indirected through a per-sequence block table. Because the
Softermax recurrence is order-free (every rescale is an exact exponent add),
blocks can be streamed in table order with no pre-pass over the scores, which
is exactly what makes the paged layout free for this kernel.

The layer index, the block table and the per-row lengths are
scalar-prefetch operands (``PrefetchScalarGridSpec``, SMEM). The K and V
pools stay in HBM (``memory_space=pl.ANY``) as the whole ``(L, N, Hkv,
BS, D)`` arrays the layer scan of ``serve/paged_step.py`` carries and
updates in place, so no program copies the pool; the kernel DMAs the
blocks it needs of the one layer itself. (A ``(1, 1)`` VMEM block over a
``(B, 1)`` lengths array is refused by the TPU compiler for B > 1: a
block's last two dims must be (8, 128)-divisible or span the array.)

Three restructurings over the naive per-head walk (all pure
reorganizations of the same recurrence — outputs are unchanged):

* **Whole-block copies, KV heads folded in.** One program owns a row's
  every query head: its query tile is ``(Hkv, G, D)`` and each copy moves
  one whole pool block ``(Hkv, BS, D)`` — every KV head of the block in one
  contiguous DMA (32 KB at 8 heads of 128 in bf16). The QK and AV dots run
  batched over the head axis.
* **A walk of each row's live tiles.** The grid is ``(B, S)``: one program
  per row and split lane. A tile is T (``kv_tile_blocks``) whole blocks,
  processed as one ``(Hkv, T*BS, D)`` VMEM tile; without a tile from the
  caller T comes from the block shape (``ref.decode_tile_blocks``:
  ``T*BS >= 128`` rows, a K tile of at most 512 KiB), clamped to the table
  width. The program reads its row's length from SMEM and loops over the
  lane's live tiles only (``ref.live_tile_range``), double-buffered: while
  one half of a ``2 x T``-block VMEM buffer is computed on, the next tile's
  blocks are copied into the other. Table columns past a row's last live
  block are neither copied nor attended, however wide the table is padded
  (``paged_step.table_width_bucket``), so a launch's DMA and compute
  follow the batch's live tokens, not its padded width.
* **Split-K.** The tile walk is partitioned across a *parallel* grid axis
  of ``split_k`` lanes; each lane emits its partial ``(m, d, acc)`` state
  (the merge identity ``(NEG_INF, 0, 0)`` when it holds no live tile) and
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
paper's int-storage / wide-accumulate split applied to the KV side. The
scales ride the same copy loop as the values. TPU tiling note: int8 VMEM
tiles are (32, 128)-granular (vs (16, 128) for bf16), so int8 pools waste
no sublane padding when ``block_size >= 32``.

Table entries past a sequence's length may be garbage (the pool's reserved
block 0): no copy reads them. In a row's last tile the slots past its last
live block are not copied; their V rows (and v-scales) are zeroed, and the
length mask gives their columns, and those of the last live block past the
length, exactly zero weight.
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
                                                  live_tile_range, scale_rows,
                                                  split_layout)


def concat_tiles(refs, axis: int = 0, idx: tuple = (0, 0)):
    """Assemble one VMEM tile from T per-block refs (each holds one pool
    block of one layer; ``idx`` indexes what to load of it). Shared by the
    decode and prefill kernel bodies: the decode kernel's ``(Hkv, BS,
    D)`` buffer slots (``idx=(...,)``) concat along rows (axis 1) and its
    ``(Hkv, 1, BS)`` scale slots along axis 2; the prefill kernel's ``(1,
    1, 1, BS, D)`` blocks (``idx=(0, 0, 0)``) concat along rows (axis 0)
    and its ``(1, 1, 1, BS)`` scale rows along columns (axis 1)."""
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
    which no block copy can gather, so one layer's scales are relaid here
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
                         tiles_per_lane: int, quantized: bool):
    T, BS = tile_blocks, block_size
    n_heads = q_ref.shape[1]
    n_src = 4 if quantized else 2
    srcs, (acc_ref, m_ref, d_ref) = rest[:n_src], rest[n_src:n_src + 3]
    bufs = rest[n_src + 3:2 * n_src + 3]     # (2, T, ...) VMEM, as srcs
    acc_scr, m_scr, d_scr, sems = rest[2 * n_src + 3:]
    kbuf, vbuf = bufs[0], bufs[1]
    row = pl.program_id(0)
    kv_len = len_ref[row]
    width = ids_ref.shape[1]
    lo, hi, n_blocks = live_tile_range(kv_len, pl.program_id(1),
                                       tiles_per_lane, T, BS, width)
    layer = layer_ref[0]

    def copies(col, slot, t):
        # the DMAs of one live block into slot t of buffer half ``slot``:
        # K and V of the layer (and the int8 pool's scales of that layer)
        blk = ids_ref[row, col]
        at = [(layer, blk), (layer, blk), (blk,), (blk,)][:n_src]
        return [pltpu.make_async_copy(src.at[i], buf.at[slot, t],
                                      sems.at[slot])
                for src, buf, i in zip(srcs, bufs, at)]

    def fetch(tile, slot):
        for t in range(T):
            col = tile * T + t

            @pl.when(col < n_blocks)
            def _start():
                for c in copies(col, slot, t):
                    c.start()

            @pl.when(col >= n_blocks)
            def _dead():
                # a slot past the last live block is not copied: zero its
                # values (and v-scales) so its masked columns add exactly 0
                vbuf[slot, t] = jnp.zeros(vbuf.shape[2:], vbuf.dtype)
                if quantized:
                    bufs[3][slot, t] = jnp.zeros(bufs[3].shape[2:],
                                                 jnp.float32)

    def wait(tile, slot):
        for t in range(T):
            col = tile * T + t

            @pl.when(col < n_blocks)
            def _wait():
                for c in copies(col, slot, t):
                    c.wait()

    def scale_tile(buf, slot):
        # (Hkv, 1, T*BS) scales of a tile from its T lane-dense (1, lanes)
        # slots, each holding one block's (Hkv, BS) scales head-major
        rows = [buf[slot, t] for t in range(T)]
        return jnp.stack([
            jnp.concatenate([r[:, h * BS:(h + 1) * BS] for r in rows],
                            axis=1) for h in range(n_heads)])

    def tile_body(tile, slot):
        k_start = tile * (T * BS)
        q = q_ref[0].astype(jnp.float32)      # (Hkv, G, D)
        # T whole blocks (every KV head) as one (Hkv, T*BS, D) tile
        k = concat_tiles([kbuf.at[slot, t] for t in range(T)], axis=1,
                         idx=(...,))
        v = concat_tiles([vbuf.at[slot, t] for t in range(T)], axis=1,
                         idx=(...,))
        s = jax.lax.dot_general(
            q, k.astype(jnp.float32), (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)   # (Hkv, G, T*BS)
        if quantized:
            # dequant fused post-dot: k_scale is constant per K row, so
            # scaling the (G, T*BS) score columns equals scaling the
            # (T*BS, D) tile — for a fraction of the flops
            s = s * scale_tile(bufs[2], slot)     # (Hkv, 1, T*BS)
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
            pv = p * scale_tile(bufs[3], slot)    # fold v_scale
        else:
            pv = p
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            pv, v.astype(jnp.float32), (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)   # (Hkv, G, D)
        d_scr[...] = d_scr[...] * alpha + jnp.sum(p, axis=2, keepdims=True)
        m_scr[...] = m_new

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    d_scr[...] = jnp.zeros_like(d_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(lo < hi)
    def _first():
        fetch(lo, lo % 2)

    def step(tile, carry):
        slot = tile % 2

        @pl.when(tile + 1 < hi)
        def _next():
            fetch(tile + 1, 1 - slot)

        wait(tile, slot)
        tile_body(tile, slot)
        return carry

    jax.lax.fori_loop(lo, hi, step, 0)
    # the lane's partial state; a lane with no live tile emits the merge
    # identity (NEG_INF, 0, 0)
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
    kv_tile_blocks: Optional[int] = None,  # pool blocks a kv tile holds
    #                                        (T); None derives it from
    #                                        the block shape
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
    # T-block tiles, S split lanes of at most spl tiles each; a lane walks
    # only its tiles that hold live blocks of its row
    T, S, spl, _ = split_layout(W, kv_tile_blocks, split_k)
    qf = q.reshape(B, Hkv, G, D)

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [
        pl.BlockSpec((1, Hkv, G, D), lambda b, s, *_: (b, 0, 0, 0)),
        hbm, hbm,
    ]
    inputs = [qf, k_pool, v_pool]
    bufs = [pltpu.VMEM((2, T, Hkv, BS, D), k_pool.dtype)] * 2
    if quantized:
        # the scales ride the same copy loop as the values, one lane-dense
        # row of every KV head's scales a block (``ref.scale_rows``)
        k_scale, v_scale = scale_rows(k_scale), scale_rows(v_scale)
        in_specs += [hbm, hbm]
        inputs += [k_scale, v_scale]
        bufs += [pltpu.VMEM((2, T) + k_scale.shape[1:], jnp.float32)] * 2

    def out_map(b, s, *_):
        return (b, s, 0, 0, 0)

    part = pl.BlockSpec((1, 1, Hkv, G, 1), out_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, S),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, Hkv, G, D), out_map),
            part, part,
        ],
        scratch_shapes=bufs + [
            pltpu.VMEM((Hkv, G, D), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )

    acc, m, d = pl.pallas_call(
        functools.partial(_paged_decode_kernel, intmax=intmax,
                          block_size=BS, tile_blocks=T, tiles_per_lane=spl,
                          quantized=quantized),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, S, Hkv, G, D), jnp.float32),
            jax.ShapeDtypeStruct((B, S, Hkv, G, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, S, Hkv, G, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(lay, block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      *inputs)

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
