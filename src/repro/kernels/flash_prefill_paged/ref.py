"""Pure-jnp oracle for the paged chunked-prefill kernel.

``paged_prefill_ref`` materializes each sequence's logical KV through its
block table (the same ``gather_kv`` as the decode oracle) and runs the
closed-form softermax with the positional causal mask — logical column
``j`` is visible to query row ``pos0 + i`` iff ``j <= pos0 + i``. That one
mask is the whole story: prefix columns (all < pos0) are always visible,
the chunk's own columns form the causal triangle, and table rows past the
last query position (pad tail of the final block) are never visible.

``paged_prefill_split_ref`` is the CPU execution path of the serving
engine's chunked prefill: identical math, but the bulk of the prefix
columns — provably below every query position when the table is an exact
(or chunk-quantized) cover — skip the mask compare/select entirely; only a
static-size tail region is masked. XLA turns the gathers into one take per
chunk, and per-chunk the score matrix is only (Sq, pos0 + Sq) — the
serve-layer chunking, not these oracles, is what kills the quadratic
one-shot blow-up.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.numerics import NEG_INF
from repro.kernels.flash_decode_paged.ref import (gather_kv, gather_kv_dequant,
                                                  gather_scales, split_layout)


def prefill_gather_oracle(
    k_pool: jax.Array,        # (N, Hkv, BS, D)
    v_pool: jax.Array,
    block_tables: jax.Array,  # (B, W) int32
    q_pos0,                   # (B,) absolute position of each row's q[0]
    q_len: int,               # Sq as passed to the kernel (incl. padding)
    *,
    kv_tile_blocks: int = 1,
    block_q: int = 128,
    cover_blocks=None,        # (B,) REAL table entries per row; default
    #                           assumes the table is the exact cover of
    #                           pos0 + q_len positions
    k_scale: jax.Array = None,   # (N, Hkv, BS) f32 when the pools are int8
    v_scale: jax.Array = None,
):
    """MEASURE one prefill launch's gather traffic: pad the table as the
    kernel wrapper does, run the ref layer's actual gathers for ONE kv
    walk, and multiply by the number of query tiles ``nq`` — the kernel's
    grid ``(B*Hkv, nq, nk)`` re-streams the whole walk once per query
    tile. Counterpart of ``flash_decode_paged.ref.decode_gather_oracle``;
    ``serve/kernel_costs.prefill_launch_cost`` must match it exactly."""
    B, W = block_tables.shape
    _, Hkv, BS, _ = k_pool.shape
    T, _, nk, Wp = split_layout(W, kv_tile_blocks, 1)
    BQ = min(block_q, q_len)
    nq = -(-q_len // BQ)
    bt = jnp.pad(block_tables.astype(jnp.int32), ((0, 0), (0, Wp - W)))

    gk = gather_kv(k_pool, bt)
    gv = gather_kv(v_pool, bt)
    walk = int(gk.nbytes) + int(gv.nbytes)
    per_block = gk.dtype.itemsize * BS * k_pool.shape[-1] * 2
    if k_scale is not None:
        gks = gather_scales(k_scale, bt)
        gvs = gather_scales(v_scale, bt)
        walk += int(gks.nbytes) + int(gvs.nbytes)
        per_block += gks.dtype.itemsize * BS * 2
    if cover_blocks is None:
        cover_blocks = [-(-(int(p) + q_len) // BS) for p in list(q_pos0)]
    useful_blocks = sum(min(int(c), Wp) for c in list(cover_blocks))
    gather = walk * nq
    useful = useful_blocks * Hkv * per_block * nq
    return {"gather_bytes": gather, "useful_bytes": useful,
            "waste_bytes": gather - useful,
            "grid_steps": B * Hkv * nq * nk, "padded_width": Wp}


def paged_prefill_ref(
    q: jax.Array,             # (B, Hq, Sq, D) pre-scaled
    k_pool: jax.Array,        # (N, Hkv, BS, D), or (L, N, ...) with layer
    v_pool: jax.Array,
    block_tables: jax.Array,  # (B, W) int32, logical order
    q_pos0: jax.Array,        # (B,) int32 absolute position of q[:, :, 0]
    *,
    layer=None,               # () int32 layer of a pool with a layer axis
    k_scale: jax.Array = None,   # (N, Hkv, BS) f32 when the pools are int8
    v_scale: jax.Array = None,
    intmax: bool = True,
) -> jax.Array:
    B, Hq, Sq, D = q.shape
    Hkv = k_pool.shape[-3]
    group = Hq // Hkv
    k = gather_kv_dequant(k_pool, k_scale, block_tables,
                          layer=layer)                   # (B,Hkv,W*BS,D)
    v = gather_kv_dequant(v_pool, v_scale, block_tables, layer=layer)
    K = k.shape[2]
    qg = q.reshape(B, Hkv, group, Sq, D)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg.astype(jnp.float32),
                   k.astype(jnp.float32))
    qi = q_pos0.astype(jnp.int32)[:, None] + jnp.arange(Sq)[None, :]
    kj = jnp.arange(K, dtype=jnp.int32)
    valid = kj[None, None, :] <= qi[:, :, None]            # (B, Sq, K)
    s = jnp.where(valid[:, None, None, :, :], s, NEG_INF)
    # ceil is monotone: ceil(max(s)) == max(ceil(s)), so IntMax needs only
    # a (…, 1) ceil after the reduce instead of a full-size pass — and the
    # denominator divides the (…, D) *output*, not the (…, K) weights,
    # exactly the kernel's normalize-at-the-end dataflow
    m = jnp.max(s, axis=-1, keepdims=True)
    m = jnp.ceil(m) if intmax else m
    p = jnp.exp2(s - m)
    d = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p, v.astype(jnp.float32))
    o = o * jnp.where(d > 0, 1.0 / jnp.where(d > 0, d, 1.0), 0.0)
    return o.reshape(B, Hq, Sq, D).astype(q.dtype)


def paged_prefill_split_ref(
    q: jax.Array,             # (B, Hq, Sq, D) pre-scaled
    k_pool: jax.Array,        # (N, Hkv, BS, D), or (L, N, ...) with layer
    v_pool: jax.Array,
    block_tables: jax.Array,  # (B, W) int32, logical order
    q_pos0: jax.Array,        # (B,) int32 absolute position of q[:, :, 0]
    *,
    layer=None,               # () int32 layer of a pool with a layer axis
    tail_blocks: int,
    k_scale: jax.Array = None,   # (N, Hkv, BS) f32 when the pools are int8
    v_scale: jax.Array = None,
    intmax: bool = True,
) -> jax.Array:
    """CPU serving fast path: same attention as ``paged_prefill_ref``, but
    the leading ``W - tail_blocks`` table blocks are treated as *provably
    causally valid* — no mask comparison, no select, no NEG_INF fill over
    the bulk of the prefix — and only the static-size tail region pays the
    positional causal mask. The chunked prefill's scores are ~95% prefix
    columns, so dropping two elementwise passes there is a large win on
    elementwise-bound CPU attention.

    CONTRACT (the caller must guarantee, it is not checked): every column
    of the first ``W - tail_blocks`` blocks sits at a logical position
    ``<= min(q_pos0)``. With ``tail_blocks = 2*ceil(Sq/BS) + 1`` this holds
    whenever ``W <= ceil((pos0+Sq)/BS) + ceil(Sq/BS) - 1`` — i.e. the
    table is the exact cover of ``pos0 + Sq`` positions, or that cover
    rounded up to a multiple of the chunk's block count (the engine's
    chunk-table bucketing); padded tail entries (garbage block 0) land in
    the masked region. For arbitrary (e.g. pow2-padded) tables use
    ``paged_prefill_ref``.
    """
    B, Hq, Sq, D = q.shape
    Hkv, BS, _ = k_pool.shape[-3:]
    group = Hq // Hkv
    W = block_tables.shape[1]
    t = min(tail_blocks, W)
    qg = q.reshape(B, Hkv, group, Sq, D).astype(jnp.float32)
    qi = q_pos0.astype(jnp.int32)[:, None] + jnp.arange(Sq)[None, :]

    k2 = gather_kv_dequant(k_pool, k_scale, block_tables[:, W - t:],
                           layer=layer)
    v2 = gather_kv_dequant(v_pool, v_scale, block_tables[:, W - t:],
                           layer=layer)
    s2 = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k2.astype(jnp.float32))
    kj = (W - t) * BS + jnp.arange(t * BS, dtype=jnp.int32)
    valid = kj[None, None, :] <= qi[:, :, None]            # (B, Sq, t*BS)
    s2 = jnp.where(valid[:, None, None, :, :], s2, NEG_INF)
    m = jnp.max(s2, axis=-1, keepdims=True)
    if W > t:
        k1 = gather_kv_dequant(k_pool, k_scale,
                               block_tables[:, :W - t], layer=layer)
        v1 = gather_kv_dequant(v_pool, v_scale,
                               block_tables[:, :W - t], layer=layer)
        s1 = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k1.astype(jnp.float32))
        m = jnp.maximum(m, jnp.max(s1, axis=-1, keepdims=True))
    m = jnp.ceil(m) if intmax else m
    p2 = jnp.exp2(s2 - m)
    d = jnp.sum(p2, axis=-1, keepdims=True)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p2, v2.astype(jnp.float32))
    if W > t:
        p1 = jnp.exp2(s1 - m)
        d = d + jnp.sum(p1, axis=-1, keepdims=True)
        o = o + jnp.einsum("bhgqk,bhkd->bhgqd", p1, v1.astype(jnp.float32))
    o = o * jnp.where(d > 0, 1.0 / jnp.where(d > 0, d, 1.0), 0.0)
    return o.reshape(B, Hq, Sq, D).astype(q.dtype)
