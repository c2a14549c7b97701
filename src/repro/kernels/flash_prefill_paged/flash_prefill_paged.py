"""Pallas TPU kernel: paged chunked-prefill attention with Softermax.

The prefill-side sibling of ``kernels/flash_decode_paged``: a *tile* of
suffix queries (one chunk of a long prompt, at absolute positions
``pos0 .. pos0+Sq-1``) attends directly against block-table-resident KV —
the cached prefix plus the chunk's own freshly scattered rows — with the
paper's Unnormed-Softmax-Unit recurrence carrying the running (IntMax,
denominator, accumulator) triple across KV tiles. Because every Softermax
rescale is an exact power-of-two exponent add, the physical blocks can be
streamed in table order with no pre-pass and no gather: the online state
*is* the carry, which is what makes chunked prefill free for this layout —
across chunk boundaries nothing needs to be handed over, the earlier
chunks' contribution lives in the pool and the recurrence is order-free.

Layout (same conventions as the decode kernel):

* KV is the pool of every layer ``(L, N, Hkv, BS, D)``, read at one
  ``layer``; ``block_tables`` holds each sequence's physical block ids in
  *logical* order, so the key at logical position ``p`` lives at
  ``pool[layer, table[p // BS], :, p % BS]``.
* The layer index, the table and the per-row ``q_pos0`` are
  scalar-prefetch operands (SMEM): the KV BlockSpec index maps do the
  gather, and the body reads its row's first query position as a scalar.
* **GQA grouping** — grid axis 0 is ``B*Hkv``: one lane owns a whole GQA
  group with a ``(group, BQ, D)`` query tile (flattened to
  ``(group*BQ, D)`` for the dots), so the block-table gather runs once per
  KV head instead of once per query head and the QK/AV dots are
  ``group``× taller MXU matmuls.
* **Multi-block KV tiles** — each kv grid step gathers ``kv_tile_blocks``
  (T) pool blocks (T block-granular DMAs overlapped within the step) and
  processes them as one ``(T*BS, D)`` VMEM tile; the wrapper pads the
  table to a tile multiple with garbage block 0. Grid
  ``(B*Hkv, nq, ceil(W/T))``; the kv axis is sequential and scratch
  carries (m, d, acc) across it.
* Causality is positional: column ``jj*T*BS + r`` is valid for query row
  ``pos0 + i*BQ + s`` iff ``col <= row`` — this one mask covers the
  all-valid prefix columns, the in-chunk triangle, and the not-yet-written
  tail rows of the last block alike. KV tiles entirely above the diagonal
  of a query tile are skipped (prefix tiles are the workload and are never
  skippable); the padded table tail always sits above the diagonal, so pad
  tiles cost no compute.

Query rows past the true chunk length are padding: every score they keep
is finite (column 0 is always causally valid), so they produce garbage-
but-finite output rows the caller slices off.

**Fused int8 dequant-on-gather.** With ``k_scale``/``v_scale`` (per-row
f32 scales, block-indexed like the pools) the K/V pools are int8: the
gather DMA moves half the bytes and dequantization folds into the score
tile — ``S *= k_scale`` per column after the QK dot, ``p *= v_scale``
before the AV dot (both exact; a scale is constant along its K/V row).
The rescales are O(group·BQ·T·BS) where widening the tiles would be
O(T·BS·D), and the accumulator stays fp32 either way.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.numerics import NEG_INF
from repro.core.softermax import softermax_finalize
from repro.kernels.flash_decode_paged.flash_decode_paged import (
    concat_tiles, layer_operands)
from repro.kernels.flash_decode_paged.ref import split_layout


def _paged_prefill_kernel(layer_ref, bt_ref, pos_ref, q_ref, *rest,
                          intmax: bool, block_q: int, block_size: int,
                          tile_blocks: int, group: int, kv_heads: int,
                          quantized: bool):
    T = tile_blocks
    k_refs, v_refs = rest[:T], rest[T:2 * T]
    n = 2 * T
    if quantized:
        ksc_refs, vsc_refs = rest[n:n + T], rest[n + T:n + 2 * T]
        n += 2 * T
    o_ref, acc_scr, m_scr, d_scr = rest[n:]
    i, j = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        d_scr[...] = jnp.zeros_like(d_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # absolute pos of q row 0; logical pos of kv tile row 0
    q_start = pos_ref[pl.program_id(0) // kv_heads] + i * block_q
    k_start = j * (T * block_size)

    @pl.when(k_start <= q_start + block_q - 1)
    def _body():
        # (G, BQ, D) query tile flattened to (G*BQ, D): every group head
        # shares the gathered KV tile and the mask repeats per head
        q = q_ref[0].astype(jnp.float32).reshape(group * block_q, -1)
        k = concat_tiles(k_refs, idx=(0, 0, 0))
        v = concat_tiles(v_refs, idx=(0, 0, 0))
        s = jax.lax.dot_general(
            q, k.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # (G*BQ, T*BS)
        if quantized:
            # k_scale is constant per K row: scaling the score columns is
            # the exact dequant, for O(G·BQ·T·BS) instead of O(T·BS·D)
            s = s * concat_tiles(ksc_refs, axis=1)    # (1, T*BS) broadcast
        rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        qi = q_start + rows % block_q                 # same mask per head
        kj = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kj <= qi, s, NEG_INF)
        m_prev = m_scr[...]
        # IntMax via ceil-after-reduce (ceil is monotone, so this equals
        # max(ceil(s)) with a (G*BQ, 1) ceil instead of a full-size pass)
        sm = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, jnp.ceil(sm) if intmax else sm)
        alpha = jnp.exp2(m_prev - m_new)              # exact power-of-two
        p = jnp.exp2(s - m_new)
        if quantized:
            pv = p * concat_tiles(vsc_refs, axis=1)   # fold v_scale into p
        else:
            pv = p
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            pv, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        d_scr[...] = d_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_scr[...] = m_new

    @pl.when(j == nk - 1)
    def _fin():
        o = softermax_finalize(acc_scr[...], d_scr[...])   # (G*BQ, D)
        o_ref[0] = o.reshape(group, block_q, -1).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("intmax", "block_q", "kv_tile_blocks", "interpret"))
def flash_prefill_paged(
    q: jax.Array,             # (B, Hq, Sq, D) pre-scaled chunk queries
    k_pool: jax.Array,        # (L, N, Hkv, BS, D) physical block pool of
    #                           every layer; (N, Hkv, BS, D) is one layer
    v_pool: jax.Array,
    block_tables: jax.Array,  # (B, W) int32, logical order; must cover every
    #                           position <= pos0 + Sq - 1
    q_pos0: jax.Array,        # (B,) int32 absolute position of q[:, :, 0]
    *,
    layer=None,               # () int32 layer the kernel reads; None with
    #                           a one-layer pool
    k_scale: jax.Array = None,   # (L, N, Hkv, BS) f32: int8 pools' row
    v_scale: jax.Array = None,   # scales, laid out as the pool (no L with
    #                              a one-layer pool)
    intmax: bool = True,
    block_q: int = 128,
    kv_tile_blocks: int = 1,  # pool blocks gathered per kv grid step (T)
    interpret: bool = False,
) -> jax.Array:
    k_pool, v_pool, k_scale, v_scale, lay = layer_operands(
        k_pool, v_pool, k_scale, v_scale, layer)
    B, Hq, Sq, D = q.shape
    _, _, Hkv, BS, _ = k_pool.shape
    W = block_tables.shape[1]
    G = Hq // Hkv
    quantized = k_scale is not None

    # prefill has no split axis: split_layout with split_k=1 degenerates
    # to the pure tile clamp + pad, keeping the geometry derivation shared
    T, _, nk, Wp = split_layout(W, kv_tile_blocks, 1)
    bt = jnp.pad(block_tables.astype(jnp.int32), ((0, 0), (0, Wp - W)))

    block_q = min(block_q, Sq)
    pq = (-Sq) % block_q
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    Sqp = Sq + pq
    nq = Sqp // block_q

    qf = qp.reshape(B, Hkv, G, Sqp, D).reshape(B * Hkv, G, Sqp, D)
    pos = q_pos0.astype(jnp.int32)

    def kv_spec(t, layered, block):
        # one gather per tile slot: (layer, block id, KV head) of the
        # pools, the layer scalar-prefetched beside the table; (block id,
        # KV head) of one layer's scales
        def _map(bh, i, j, layer_ref, bt_ref, pos_ref):
            return ((layer_ref[0],) if layered else ()) + \
                (bt_ref[bh // Hkv, j * T + t], bh % Hkv) + \
                (0,) * (len(block) - 1)
        return pl.BlockSpec((1,) * (1 + layered) + block, _map)

    def q_map(bh, i, j, *_):
        return (bh, 0, i, 0)

    in_specs = [pl.BlockSpec((1, G, block_q, D), q_map)]
    in_specs += [kv_spec(t, True, (1, BS, D))
                 for _ in "kv" for t in range(T)]
    inputs = [qf] + [k_pool] * T + [v_pool] * T
    if quantized:
        # scales ride the same scalar-prefetch gather as the values; the
        # unit axis keeps in-kernel reads 2-D (TPU-friendly)
        in_specs += [kv_spec(t, False, (1, 1, BS))
                     for _ in "kv" for t in range(T)]
        inputs += [k_scale] * T + [v_scale] * T

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B * Hkv, nq, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, G, block_q, D), q_map),
        scratch_shapes=[
            pltpu.VMEM((G * block_q, D), jnp.float32),
            pltpu.VMEM((G * block_q, 1), jnp.float32),
            pltpu.VMEM((G * block_q, 1), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        functools.partial(_paged_prefill_kernel, intmax=intmax,
                          block_q=block_q, block_size=BS, tile_blocks=T,
                          group=G, kv_heads=Hkv, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * Hkv, G, Sqp, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(lay, bt, pos, *inputs)

    out = out.reshape(B, Hkv, G, Sqp, D).reshape(B, Hq, Sqp, D)
    return out[:, :, :Sq, :]
