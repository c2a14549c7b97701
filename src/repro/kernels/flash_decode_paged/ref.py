"""Pure-jnp oracles for the paged decode kernel.

``gather_kv`` materializes a request's logical cache from the pool through
its block table; ``paged_decode_ref`` is then exactly the contiguous decode
oracle on the gathered cache. This is also the CPU execution path of the
serving engine (``serve/paged_step.py``) — XLA turns the block-table gather
into one take per step, and the attention math is bit-for-bit the contiguous
``_masked_decode`` computation.

**Grouped-gather cost faithfulness.** Every oracle here gathers KV exactly
once per *KV* head — ``pool[block_tables]`` pulls all ``Hkv`` heads of a
block in one take — and queries are reshaped to ``(B, Hkv, group, …)`` so
the group dimension rides the einsum batch axes; KV is never expanded
(repeated/broadcast-materialized) across the query group. That is the same
operand-movement shape as the grouped Pallas kernel's one-gather-per-group
lanes, so the refs stay cost-faithful oracles, not just numeric ones.

``paged_decode_split_ref`` mirrors the kernel's split-K structure: the
(padded) KV walk is cut into ``split_k`` partitions, each reduced to its
partial ``(m, d, acc)`` state in closed form, and the partials are combined
with the associative Softermax merge (``core.softermax.softermax_merge``)
— the exact contract the kernel's parallel split lanes + jnp combine stage
implement, including the identity state ``(NEG_INF, 0, 0)`` for partitions
that sit entirely past a sequence's length.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.numerics import NEG_INF
from repro.core.softermax import softermax_finalize, softermax_merge
from repro.kernels.flash_decode.ref import decode_ref


def split_layout(W: int, kv_tile_blocks: int, split_k: int):
    """THE clamped tile/split geometry for a table of ``W`` blocks —
    ``(T, S, spl, Wp)``: T blocks per kv tile, S split lanes of ``spl``
    tiles each, table padded to ``Wp = S*spl*T`` blocks. The kernel
    wrapper, the split oracle, and the decode bench's gather-traffic model
    must all partition identically, so the derivation lives here once."""
    T = max(1, min(kv_tile_blocks, W))
    tiles = -(-W // T)
    S = max(1, min(split_k, tiles))
    spl = -(-tiles // S)
    return T, S, spl, S * spl * T


# the derived decode tile: enough pool blocks that one grid step's dots
# reach ``DECODE_TILE_ROWS`` token rows, but never a K tile (every KV head
# of T blocks) past ``DECODE_TILE_BYTES`` of VMEM
DECODE_TILE_ROWS = 128
DECODE_TILE_BYTES = 512 * 1024


def decode_tile_blocks(block_size: int, kv_heads: int, head_dim: int,
                       itemsize: int) -> int:
    """THE decode tile T when the caller names none, from the pool's block
    shape ``(Hkv, BS, D)`` and element size: the fewest blocks giving
    ``T*BS >= DECODE_TILE_ROWS`` rows, cut to what ``DECODE_TILE_BYTES``
    holds of K, at least 1. ``split_layout`` then clamps it to the table
    width. A model with many KV heads or wide heads gets a smaller T."""
    rows = -(-DECODE_TILE_ROWS // block_size)
    fit = DECODE_TILE_BYTES // (kv_heads * block_size * head_dim * itemsize)
    return max(1, min(rows, fit))


def live_tile_range(kv_len, lane, tiles_per_lane: int, tile_blocks: int,
                    block_size: int, width: int, xp=jnp):
    """THE tiles split lane ``lane`` of a row of ``kv_len`` tokens walks:
    ``(lo, hi, n_blocks)``, tiles ``lo <= tile < hi`` of ``tile_blocks``
    table columns each, where the row's ``n_blocks = min(ceil(kv_len /
    block_size), width)`` live blocks lie; ``hi <= lo`` is a lane with no
    live tile. Columns at or past ``n_blocks`` are never copied. The
    kernel (on SMEM scalars), the gather oracle and the cost model
    (``xp=np``, on host arrays) all walk through it."""
    n_blocks = xp.minimum((kv_len + block_size - 1) // block_size, width)
    lo = lane * tiles_per_lane
    hi = xp.minimum(lo + tiles_per_lane,
                    (n_blocks + tile_blocks - 1) // tile_blocks)
    return lo, hi, n_blocks


SCALE_LANES = 128


def scale_rows(scales: jax.Array) -> jax.Array:
    """An int8 pool's per-row scales of one layer, ``(N, Hkv, BS)`` or
    ``(N, Hkv, 1, BS)``, as the decode kernel copies them: one lane-dense
    f32 row per block, ``(N, 1, lanes)``, every KV head's ``BS`` scales in
    head order, padded to a whole number of ``SCALE_LANES``-lane tiles
    (a TPU copy's minor dim has to fill whole tiles)."""
    N = scales.shape[0]
    row = int(np.prod(scales.shape[1:]))
    lanes = -(-row // SCALE_LANES) * SCALE_LANES
    return jnp.pad(scales.astype(jnp.float32).reshape(N, 1, row),
                   ((0, 0), (0, 0), (0, lanes - row)))


def _take_blocks(pool: jax.Array, block_tables: jax.Array, layer):
    """``pool[block_tables]`` of a one-layer pool, or ``pool[layer,
    block_tables]`` of a pool with its layer axis: only the tables'
    blocks are taken, never the layer's whole slice."""
    if layer is None:
        return pool[block_tables]
    return pool[layer, block_tables]


def gather_kv(pool: jax.Array, block_tables: jax.Array,
              layer=None) -> jax.Array:
    """(N, Hkv, BS, D) pool — or (L, N, Hkv, BS, D) with a ``layer``
    index — + (B, nb) table -> (B, Hkv, nb*BS, D) caches."""
    B, nb = block_tables.shape
    Hkv, BS, D = pool.shape[-3:]
    g = _take_blocks(pool, block_tables, layer)   # (B, nb, Hkv, BS, D)
    g = jnp.moveaxis(g, 2, 1)                 # (B, Hkv, nb, BS, D)
    return g.reshape(B, Hkv, nb * BS, D)


def gather_scales(scales: jax.Array, block_tables: jax.Array,
                  layer=None) -> jax.Array:
    """(N, Hkv, BS) scale pool — or (L, N, Hkv, BS) with a ``layer``
    index — + (B, nb) table -> (B, Hkv, nb*BS)."""
    B, nb = block_tables.shape
    Hkv, BS = scales.shape[-2:]
    g = _take_blocks(scales, block_tables, layer)  # (B, nb, Hkv, BS)
    g = jnp.moveaxis(g, 2, 1)
    return g.reshape(B, Hkv, nb * BS)


def gather_kv_dequant(pool: jax.Array, scales, block_tables: jax.Array,
                      dtype=jnp.float32, layer=None) -> jax.Array:
    """Gather + (optional) int8 dequant: the pure-JAX mirror of the
    kernels' fused dequant-on-gather. ``scales=None`` is the plain path;
    ``layer`` indexes a pool that keeps its layer axis."""
    g = gather_kv(pool, block_tables, layer)
    if scales is None:
        return g
    s = gather_scales(scales, block_tables, layer)
    return (g.astype(jnp.float32) * s[..., None].astype(jnp.float32)
            ).astype(dtype)


def decode_gather_oracle(
    k_pool: jax.Array,        # (N, Hkv, BS, D)
    v_pool: jax.Array,
    block_tables: jax.Array,  # (B, W) int32
    lengths,                  # (B,) kv lengths the kernel attends
    *,
    kv_tile_blocks: Optional[int] = None,
    split_k: int = 1,
    k_scale: jax.Array = None,   # (N, Hkv, BS) f32 when the pools are int8
    v_scale: jax.Array = None,
):
    """MEASURE (not model) one decode launch's gather traffic: walk the
    kernel's grid ``(B, S)`` and each lane's live tiles
    (``live_tile_range``), list the block every copy of the walk moves
    (each live column of a tile; columns past a row's live blocks are not
    copied), take those blocks from the pools (and an int8 pool's scales
    as the kernel lays them out, ``scale_rows``) and count bytes off the
    taken arrays' shapes, plus the int32 table the scalar prefetch moves.
    The analytic model in ``serve/kernel_costs.py`` must reproduce these
    numbers exactly.

    Returns ``{"gather_bytes", "useful_bytes", "waste_bytes",
    "grid_steps", "tile_steps", "steps_per_lane"}``; useful bytes are
    each row's live blocks (``ceil(len/BS)``, at most W), int8 scale
    siblings included, and the table, waste the rest (0 for a walk that
    copies only live blocks); ``tile_steps`` counts the tiles every lane
    walks, ``steps_per_lane`` the most one lane walks.
    """
    bt = np.asarray(block_tables, np.int32)
    B, W = bt.shape
    _, Hkv, BS, D = k_pool.shape
    if kv_tile_blocks is None:
        kv_tile_blocks = decode_tile_blocks(BS, Hkv, D,
                                            k_pool.dtype.itemsize)
    T, S, spl, _ = split_layout(W, kv_tile_blocks, split_k)
    ids, walked = [], []
    for r, ln in enumerate(np.asarray(lengths, np.int64)):
        for lane in range(S):
            lo, hi, nb = (int(x) for x in live_tile_range(
                int(ln), lane, spl, T, BS, W, xp=np))
            walked.append(max(hi - lo, 0))
            ids += [bt[r, c] for tile in range(lo, hi)
                    for c in range(tile * T, (tile + 1) * T) if c < nb]
    moved = jnp.asarray(np.asarray(ids, np.int32))  # block of every copy

    pools = [k_pool, v_pool] + ([scale_rows(k_scale), scale_rows(v_scale)]
                                if k_scale is not None else [])
    taken = [p[moved] for p in pools]     # the real takes — bytes come
    per_block = sum(int(p[0].nbytes) for p in pools)  # off their shapes
    gather = sum(int(a.nbytes) for a in taken) + int(bt.nbytes)
    live = sum(min(-(-int(ln) // BS), W) for ln in list(lengths))
    useful = live * per_block + int(bt.nbytes)
    return {"gather_bytes": gather, "useful_bytes": useful,
            "waste_bytes": gather - useful, "grid_steps": B * S,
            "tile_steps": sum(walked), "steps_per_lane": max(walked)}


def paged_decode_ref(
    q: jax.Array,             # (B, Hq, D) pre-scaled
    k_pool: jax.Array,        # (N, Hkv, BS, D), or (L, N, ...) with layer
    v_pool: jax.Array,
    block_tables: jax.Array,  # (B, nb) int32
    lengths: jax.Array,       # (B,) int32
    *,
    layer=None,               # () int32 layer of a pool with a layer axis
    k_scale: jax.Array = None,   # (N, Hkv, BS) f32 when the pools are int8
    v_scale: jax.Array = None,
    intmax: bool = True,
) -> jax.Array:
    k = gather_kv_dequant(k_pool, k_scale, block_tables, layer=layer)
    v = gather_kv_dequant(v_pool, v_scale, block_tables, layer=layer)
    return decode_ref(q, k, v, lengths, intmax=intmax)


def paged_decode_split_ref(
    q: jax.Array,             # (B, Hq, D) pre-scaled
    k_pool: jax.Array,        # (N, Hkv, BS, D), or (L, N, ...) with layer
    v_pool: jax.Array,
    block_tables: jax.Array,  # (B, W) int32
    lengths: jax.Array,       # (B,) int32
    *,
    layer=None,               # () int32 layer of a pool with a layer axis
    split_k: int = 1,
    kv_tile_blocks: int = 1,
    k_scale: jax.Array = None,   # (N, Hkv, BS) f32 when the pools are int8
    v_scale: jax.Array = None,
    intmax: bool = True,
) -> jax.Array:
    """Partition-structured oracle for the split-K kernel: pads the table
    the way the kernel wrapper does (to ``split_k * spl * kv_tile_blocks``
    blocks, pad entries = garbage block 0), reduces each partition to its
    partial ``(m, d, acc)`` in closed form, and merges with
    ``softermax_merge``. Numerically equal to ``paged_decode_ref`` up to fp
    reduction order (exactly equal where IntMax makes every rescale an
    integer exponent add and each partition's sums coincide)."""
    B, Hq, D = q.shape
    Hkv, BS, _ = k_pool.shape[-3:]
    W = block_tables.shape[1]
    G = Hq // Hkv

    _, S, _, Wp = split_layout(W, kv_tile_blocks, split_k)
    bt = jnp.pad(block_tables.astype(jnp.int32), ((0, 0), (0, Wp - W)))

    k = gather_kv_dequant(k_pool, k_scale, bt, layer=layer)  # (B,Hkv,Wp*BS,D)
    v = gather_kv_dequant(v_pool, v_scale, bt, layer=layer)
    P = (Wp * BS) // S                             # columns per partition
    k = k.reshape(B, Hkv, S, P, D)
    v = v.reshape(B, Hkv, S, P, D)
    qg = q.reshape(B, Hkv, G, D)
    s = jnp.einsum("bhgd,bhspd->bhgsp", qg.astype(jnp.float32),
                   k.astype(jnp.float32))
    kj = jnp.arange(Wp * BS, dtype=jnp.int32).reshape(S, P)
    valid = kj[None] < lengths.astype(jnp.int32)[:, None, None]  # (B, S, P)
    s = jnp.where(valid[:, None, None, :, :], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)         # (B, Hkv, G, S, 1)
    m = jnp.ceil(m) if intmax else m
    # masked columns contribute exactly 0 (exp2(NEG_INF - m) underflows),
    # but a *fully* masked partition would see exp2(0) = 1 per column —
    # zero those explicitly so empty partitions carry the merge identity
    p = jnp.where(valid[:, None, None, :, :], jnp.exp2(s - m), 0.0)
    d = jnp.sum(p, axis=-1, keepdims=True)         # (B, Hkv, G, S, 1)
    m = jnp.where(d > 0, m, NEG_INF)               # identity for empties
    acc = jnp.einsum("bhgsp,bhspd->bhgsd", p, v.astype(jnp.float32))
    _, d2, acc2 = softermax_merge(m, d, acc, axis=3)
    o = softermax_finalize(acc2, d2)               # (B, Hkv, G, D)
    return o.reshape(B, Hq, D).astype(q.dtype)
