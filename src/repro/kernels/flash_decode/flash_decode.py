"""Pallas TPU kernel: single-token decode attention with Softermax.

The decode step is the pure form of the paper's workload: one query row, a
streaming reduction over a (possibly very long) KV cache. The kernel is the
Unnormed-Softmax-Unit dataflow verbatim — running IntMax + running
denominator with power-of-two rescales — fused with the A·V accumulation, so
the cache is read from HBM exactly once per token.

Grid: ``(B*Hq, num_kv_blocks)``; kv sequential, scratch carries (m, d, acc).
Per-batch valid lengths mask the cache tail.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.numerics import NEG_INF


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_scr, m_scr, d_scr,
                   *, intmax: bool, block_k: int):
    j = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        d_scr[...] = jnp.zeros_like(d_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    kv_len = len_ref[0, 0]
    k_start = j * block_k

    @pl.when(k_start < kv_len)
    def _body():
        q = q_ref[0].astype(jnp.float32)              # (1, D)
        k = k_ref[0].astype(jnp.float32)              # (BK, D)
        v = v_ref[0].astype(jnp.float32)              # (BK, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # (1, BK)
        kj = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kj < kv_len, s, NEG_INF)
        m_prev = m_scr[...]
        sl = jnp.ceil(s) if intmax else s
        m_new = jnp.maximum(m_prev, jnp.max(sl, axis=1, keepdims=True))
        alpha = jnp.exp2(m_prev - m_new)
        p = jnp.exp2(s - m_new)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        d_scr[...] = d_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_scr[...] = m_new

    @pl.when(j == nk - 1)
    def _fin():
        d = d_scr[...]
        recip = jnp.where(d > 0, 1.0 / jnp.where(d > 0, d, 1.0), 0.0)
        o_ref[0] = (acc_scr[...] * recip).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("intmax", "block_k", "interpret"),
)
def flash_decode(
    q: jax.Array,        # (B, Hq, D) — pre-scaled single-token queries
    k: jax.Array,        # (B, Hkv, S, D) cache
    v: jax.Array,        # (B, Hkv, S, D)
    lengths: jax.Array,  # (B,) int32 valid cache lengths
    *,
    intmax: bool = True,
    block_k: int = 256,
    interpret: bool = False,
) -> jax.Array:
    B, Hq, D = q.shape
    _, Hkv, S, _ = k.shape
    group = Hq // Hkv
    block_k = min(block_k, S)
    pk = (-S) % block_k
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    Sp = S + pk
    nk = Sp // block_k

    qf = q.reshape(B * Hq, 1, D)
    kf = kp.reshape(B * Hkv, Sp, D)
    vf = vp.reshape(B * Hkv, Sp, D)
    lens = lengths.astype(jnp.int32).reshape(B, 1)

    def kv_map(bh, j):
        return ((bh // Hq) * Hkv + (bh % Hq) // group, j, 0)

    out = pl.pallas_call(
        functools.partial(_decode_kernel, intmax=intmax, block_k=block_k),
        grid=(B * Hq, nk),
        in_specs=[
            pl.BlockSpec((1, 1), lambda bh, j: (bh // Hq, 0)),
            pl.BlockSpec((1, 1, D), lambda bh, j: (bh, 0, 0)),
            pl.BlockSpec((1, block_k, D), kv_map),
            pl.BlockSpec((1, block_k, D), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, D), lambda bh, j: (bh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, 1, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, D), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(lens, qf, kf, vf)

    return out.reshape(B, Hq, D)
