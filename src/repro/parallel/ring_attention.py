"""Ring attention with the Softermax online recurrence (distributed softmax).

Sequence-parallel attention without materializing full K/V per chip: each of
the n model-axis ranks owns a sequence shard; K/V shards circulate the ring
(``lax.ppermute``) while every rank folds each visiting block into its
running (IntMax m, denominator d, accumulator) state — the *same* online
normalization the paper builds in hardware, here spanning chips: every
cross-block rescale is an exact power of two because the running max is kept
integral.

Wire bytes equal the all-gather it replaces; the wins are (a) peak memory —
only one visiting KV block is resident instead of the full sequence — and
(b) overlap: each permute transfers while the previous block computes.

Used by ``attention_apply`` when ``cfg.opt_ring_attention`` and the ambient
rules are sequence-parallel (seq sharded over "model").
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.numerics import NEG_INF


def _ring_inner(q, k, v, *, axis_name: str, n_ranks: int, causal: bool,
                intmax: bool):
    """Per-shard body. q: (B,Hq,S_loc,D); k/v: (B,Hkv,S_loc,D[v])."""
    B, Hq, S_loc, D = q.shape
    Hkv = k.shape[1]
    Dv = v.shape[-1]
    group = Hq // Hkv
    r = jax.lax.axis_index(axis_name)
    qg = q.reshape(B, Hkv, group, S_loc, D)
    q_pos = r * S_loc + jnp.arange(S_loc)

    m = jnp.full((B, Hkv, group, S_loc, 1), NEG_INF, jnp.float32)
    d = jnp.zeros((B, Hkv, group, S_loc, 1), jnp.float32)
    acc = jnp.zeros((B, Hkv, group, S_loc, Dv), jnp.float32)

    perm = [(i, (i + 1) % n_ranks) for i in range(n_ranks)]

    def fold(carry, kv_blk, kv_rank):
        m, d, acc = carry
        k_b, v_b = kv_blk
        s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k_b,
                       preferred_element_type=jnp.float32)
        k_pos = kv_rank * S_loc + jnp.arange(S_loc)
        if causal:
            valid = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(valid, s, NEG_INF)
        sl = jnp.ceil(s) if intmax else s
        m_new = jnp.maximum(m, jnp.max(sl, axis=-1, keepdims=True))
        alpha = jnp.exp2(m - m_new)          # integer exponent under IntMax
        p = jnp.exp2(s - m_new)
        acc = acc * alpha + jnp.einsum(
            "bhgqk,bhkd->bhgqd", p.astype(v_b.dtype), v_b,
            preferred_element_type=jnp.float32)
        d = d * alpha + jnp.sum(p, axis=-1, keepdims=True)
        return (m_new, d, acc)

    def step_fn(carry, step):
        state, kv = carry
        kv_rank = jnp.mod(r - step, n_ranks)
        state = fold(state, kv, kv_rank)
        kv = jax.lax.ppermute(kv, axis_name, perm)
        return (state, kv), None

    # lax.scan bounds live memory to ONE visiting KV block (the unrolled
    # form kept n blocks alive); the trailing extra permute is 1/n wire.
    ((m, d, acc), _), _ = jax.lax.scan(
        step_fn, ((m, d, acc), (k, v)), jnp.arange(n_ranks))
    o = jnp.where(d > 0, acc / jnp.where(d > 0, d, 1.0), 0.0)
    return o.reshape(B, Hq, S_loc, Dv).astype(q.dtype)


def ring_attention(
    q: jax.Array,  # (B, Hq, S, D) — seq logically global, sharded by caller
    k: jax.Array,
    v: jax.Array,
    mesh,
    *,
    axis_name: str = "model",
    causal: bool = True,
    intmax: bool = True,
    batch_axes: Tuple[str, ...] = ("pod", "data"),
) -> jax.Array:
    """shard_map entry: shards seq over ``axis_name``, runs the ring."""
    n = mesh.shape[axis_name]
    baxes = tuple(a for a in batch_axes if a in mesh.shape)
    spec = P(baxes if q.shape[0] % max(
        1, _prod(mesh.shape[a] for a in baxes)) == 0 else None,
        None, axis_name, None)
    inner = functools.partial(_ring_inner, axis_name=axis_name, n_ranks=n,
                              causal=causal, intmax=intmax)
    return jax.shard_map(
        inner, mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )(q, k, v)


def _prod(it):
    out = 1
    for x in it:
        out *= x
    return out
