"""Pallas TPU kernel: paged single-token decode attention with Softermax.

Same Unnormed-Softmax-Unit dataflow as ``kernels/flash_decode`` — running
IntMax + running denominator with power-of-two rescales, fused with the A·V
accumulation — but the KV cache is a *block pool*: a flat array of fixed-size
physical blocks, indirected through a per-sequence block table. Because the
Softermax recurrence is order-free (every rescale is an exact exponent add),
blocks can be streamed in table order with no pre-pass over the scores, which
is exactly what makes the paged layout free for this kernel.

The block table and the per-row lengths are scalar-prefetch operands
(``PrefetchScalarGridSpec``, SMEM): their entries are available *before*
the kernel body runs, so the KV BlockSpec index maps perform the gather —
each grid step DMAs physical blocks from the pool directly into VMEM — and
the body reads its row's length as a scalar. (A ``(1, 1)`` VMEM block over
a ``(B, 1)`` lengths array is refused by the TPU compiler for B > 1: a
block's last two dims must be (8, 128)-divisible or span the array.)

Three grid-level restructurings over the naive per-head walk (all three are
pure reorganizations of the same recurrence — outputs are unchanged):

* **GQA grouping.** Grid axis 0 is ``B*Hkv``, not ``B*Hq``: one lane owns a
  whole GQA group, its query tile is ``(group, D)``, and the block-table
  gather that used to run once per *query* head now runs once per *KV*
  head — a ``group``× cut in gather DMA — while the QK/AV dots grow from
  ``(1, D)`` vector products into real ``(group, ·)`` MXU matmuls.
* **Multi-block KV tiles.** Each kv grid step gathers ``kv_tile_blocks``
  (T) pool blocks — T block-granular DMAs the pipeline overlaps within one
  step — and processes them as a single ``(T*BS, D)`` VMEM tile, so with
  ``T*BS >= 128`` the dots are MXU-shaped and the per-step mask/rescale
  overhead amortizes over T blocks. Table entries past the real table width
  are clamped to the pool's reserved garbage block 0 (the wrapper pads the
  table), and ``@pl.when`` skips compute on tiles that start past the
  sequence length, so short requests stop paying for the batch-max table
  width.
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
rescales touch the (group, T*BS) score tile, not the (T*BS, D) value tile,
so the dequant cost stays O(tile-row) while the accumulate stays fp32 — the
paper's int-storage / wide-accumulate split applied to the KV side. TPU
tiling note: int8 VMEM tiles are (32, 128)-granular (vs (16, 128) for
bf16), so int8 pools waste no sublane padding when ``block_size >= 32``.

Table entries past a sequence's length may be garbage (the pool's reserved
block 0): the length mask zeroes their contribution and the gather of block
0 is a wasted-but-harmless DMA.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.numerics import NEG_INF
from repro.core.softermax import softermax_finalize, softermax_merge
from repro.kernels.flash_decode_paged.ref import split_layout


def concat_tiles(refs, axis: int = 0):
    """Assemble one VMEM tile from the T per-slot gather operands (each
    ref holds one pool block, leading (1, 1) block axes stripped). Shared
    by the decode and prefill kernel bodies — values concat along rows
    (axis 0), the (1, BS) scale rows along columns (axis 1)."""
    if len(refs) == 1:
        return refs[0][0, 0]
    return jnp.concatenate([r[0, 0] for r in refs], axis=axis)


def _paged_decode_kernel(bt_ref, len_ref, q_ref, *rest, intmax: bool,
                         block_size: int, tile_blocks: int, quantized: bool,
                         kv_heads: int):
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

    kv_len = len_ref[pl.program_id(0) // kv_heads]
    jj = pl.program_id(1) * spl + j           # global kv tile index
    k_start = jj * (T * block_size)

    @pl.when(k_start < kv_len)
    def _body():
        q = q_ref[0].astype(jnp.float32)      # (G, D)
        # T block-granular gathers assembled into one (T*BS, D) VMEM tile
        k = concat_tiles(k_refs)
        v = concat_tiles(v_refs)
        s = jax.lax.dot_general(
            q, k.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)   # (G, T*BS)
        if quantized:
            # dequant fused post-dot: k_scale is constant per K row, so
            # scaling the (G, T*BS) score columns equals scaling the
            # (T*BS, D) tile — for a fraction of the flops
            s = s * concat_tiles(ksc_refs, axis=1)   # (1, T*BS) broadcast
        kj = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kj < kv_len, s, NEG_INF)
        m_prev = m_scr[...]
        # IntMax via ceil-after-reduce (ceil is monotone, so this equals
        # max(ceil(s)) with a (G, 1) ceil instead of a (G, T*BS) pass)
        sm = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, jnp.ceil(sm) if intmax else sm)
        alpha = jnp.exp2(m_prev - m_new)      # exact power-of-two
        p = jnp.exp2(s - m_new)
        if quantized:
            pv = p * concat_tiles(vsc_refs, axis=1)  # fold v_scale into p
        else:
            pv = p
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            pv, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        d_scr[...] = d_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
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
    k_pool: jax.Array,        # (N, Hkv, BS, D) physical block pool
    v_pool: jax.Array,        # (N, Hkv, BS, D)
    block_tables: jax.Array,  # (B, W) int32 physical block ids
    lengths: jax.Array,       # (B,) int32 valid cache lengths
    *,
    k_scale: jax.Array = None,   # (N, Hkv, BS) f32: int8 pools' row scales
    v_scale: jax.Array = None,
    intmax: bool = True,
    kv_tile_blocks: int = 1,  # pool blocks gathered per kv grid step (T)
    split_k: int = 1,         # parallel partitions of the KV walk
    interpret: bool = False,
) -> jax.Array:
    B, Hq, D = q.shape
    N, Hkv, BS, _ = k_pool.shape
    W = block_tables.shape[1]
    G = Hq // Hkv
    quantized = k_scale is not None

    # clamp the tiling to the table (shared geometry — ref.split_layout):
    # T-block tiles, S split lanes of spl tiles each; the table pads to
    # the S*spl*T cover with garbage block 0 (padded entries sit past
    # every length — masked, and their repeated block-0 gather is a
    # harmless DMA)
    T, S, spl, Wp = split_layout(W, kv_tile_blocks, split_k)
    bt = jnp.pad(block_tables.astype(jnp.int32), ((0, 0), (0, Wp - W)))

    qf = q.reshape(B * Hkv, G, D)
    lens = lengths.astype(jnp.int32)

    def kv_map(t):
        # one gather map per tile slot; values and scales share it
        def _map(bh, s, j, bt_ref, len_ref):
            jj = s * spl + j
            return (bt_ref[bh // Hkv, jj * T + t], bh % Hkv, 0, 0)
        return _map

    in_specs = [
        pl.BlockSpec((1, G, D), lambda bh, s, j, bt_ref, len_ref: (bh, 0, 0)),
    ]
    in_specs += [pl.BlockSpec((1, 1, BS, D), kv_map(t)) for t in range(T)]
    in_specs += [pl.BlockSpec((1, 1, BS, D), kv_map(t)) for t in range(T)]
    inputs = [qf] + [k_pool] * T + [v_pool] * T
    if quantized:
        # scales ride the same scalar-prefetch block-table gather as the
        # values; the trailing unit axis keeps in-kernel reads 2-D
        ksr = k_scale.astype(jnp.float32).reshape(N, Hkv, 1, BS)
        vsr = v_scale.astype(jnp.float32).reshape(N, Hkv, 1, BS)
        in_specs += [pl.BlockSpec((1, 1, 1, BS), kv_map(t))
                     for t in range(T)]
        in_specs += [pl.BlockSpec((1, 1, 1, BS), kv_map(t))
                     for t in range(T)]
        inputs += [ksr] * T + [vsr] * T

    def out_map(bh, s, j, bt_ref, len_ref):
        return (bh, s, 0, 0)

    part = pl.BlockSpec((1, 1, G, 1), out_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B * Hkv, S, spl),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, G, D), out_map),
            part, part,
        ],
        scratch_shapes=[
            pltpu.VMEM((G, D), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
    )

    acc, m, d = pl.pallas_call(
        functools.partial(_paged_decode_kernel, intmax=intmax,
                          block_size=BS, tile_blocks=T, quantized=quantized,
                          kv_heads=Hkv),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B * Hkv, S, G, D), jnp.float32),
            jax.ShapeDtypeStruct((B * Hkv, S, G, 1), jnp.float32),
            jax.ShapeDtypeStruct((B * Hkv, S, G, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(bt, lens, *inputs)

    # second stage: associative Softermax merge of the split partials under
    # the joint (Int)Max, then the one deferred normalize. With split_k=1
    # this is exactly the old in-kernel epilogue (scale = 2^0 = 1).
    _, d2, acc2 = softermax_merge(m, d, acc, axis=1)
    o = softermax_finalize(acc2, d2)          # (B*Hkv, G, D)
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
