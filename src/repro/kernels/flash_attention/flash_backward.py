"""Pallas TPU kernels: flash-attention backward pass (softermax-aware).

Standard two-kernel flash backward, adapted to the base-2 softmax: with
``p = 2^(s - m)/d`` (m the running IntMax — a constant under differentiation
since ceil has zero gradient, and it cancels from the simplex Jacobian),

    dP_ij   = dO_i · V_j
    delta_i = Σ_j P_ij dP_ij = dO_i · O_i
    dS_ij   = ln(2) · P_ij (dP_ij - delta_i)      ← the base-2 factor
    dV_j    = Σ_i P_ij dO_i
    dK_j    = Σ_i dS_ij Q_i
    dQ_i    = Σ_j dS_ij K_j

P is recomputed blockwise from the forward's saved (m, d) row statistics —
the recompute-instead-of-store trade that makes flash training memory-linear.
GQA: gradients are produced at Hq granularity; the caller group-sums dK/dV.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.numerics import LN_2, NEG_INF


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, m_ref, d_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr,
                *, causal: bool, block_q: int, block_k: int, q_offset: int):
    """grid (BH, nK, nQ): one K/V block accumulates over all Q blocks."""
    j, i = pl.program_id(1), pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q = q_ref[0].astype(jnp.float32)          # (BQ, D)
    k = k_ref[0].astype(jnp.float32)          # (BK, D)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)        # (BQ, D)
    m = m_ref[0].astype(jnp.float32)          # (BQ, 1)
    d = d_ref[0].astype(jnp.float32)
    delta = delta_ref[0].astype(jnp.float32)  # (BQ, 1)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (BQ, BK)
    if causal:
        qi = (i * block_q + q_offset
              + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0))
        kj = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(qi >= kj, s, NEG_INF)
    p = jnp.exp2(s - m) / jnp.maximum(d, 1e-30)                  # (BQ, BK)
    dv_scr[...] += jax.lax.dot_general(
        p, do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                      # (BK, D)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = LN_2 * p * (dp - delta)                                 # (BQ, BK)
    dk_scr[...] += jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                      # (BK, D)

    @pl.when(i == nq - 1)
    def _fin():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, m_ref, d_ref, delta_ref,
               dq_ref, dq_scr,
               *, causal: bool, block_q: int, block_k: int, q_offset: int):
    """grid (BH, nQ, nK): one Q block accumulates over all K blocks."""
    i, j = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    m = m_ref[0].astype(jnp.float32)
    d = d_ref[0].astype(jnp.float32)
    delta = delta_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if causal:
        qi = (i * block_q + q_offset
              + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0))
        kj = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(qi >= kj, s, NEG_INF)
    p = jnp.exp2(s - m) / jnp.maximum(d, 1e-30)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = LN_2 * p * (dp - delta)
    dq_scr[...] += jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(j == nk - 1)
    def _fin():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret"),
)
def flash_attention_bwd(
    q: jax.Array,   # (B, Hq, Sq, D) pre-scaled (same as forward)
    k: jax.Array,   # (B, Hkv, Sk, D)
    v: jax.Array,
    o: jax.Array,   # forward output (B, Hq, Sq, D)
    do: jax.Array,  # cotangent
    m: jax.Array,   # (B, Hq, Sq, 1) forward row max (IntMax)
    d: jax.Array,   # (B, Hq, Sq, 1) forward denominator
    *,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
):
    """Returns (dq, dk, dv) with dk/dv at (B, Hkv, ...) (group-summed)."""
    B, Hq, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    group = Hq // Hkv
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    pq = (-Sq) % block_q
    pk = (-Sk) % block_k
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    op = jnp.pad(o, ((0, 0), (0, 0), (0, pq), (0, 0)))
    dop = jnp.pad(do, ((0, 0), (0, 0), (0, pq), (0, 0)))
    # padded q rows: force empty softmax rows (d=1, m=0 → p=2^NEG_INF=0)
    mp = jnp.pad(m, ((0, 0), (0, 0), (0, pq), (0, 0)))
    dp_ = jnp.pad(d, ((0, 0), (0, 0), (0, pq), (0, 0)),
                  constant_values=1.0)
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    Sqp, Skp = Sq + pq, Sk + pk
    nq, nk = Sqp // block_q, Skp // block_k
    q_offset = Sk - Sq

    delta = jnp.sum(dop.astype(jnp.float32) * op.astype(jnp.float32),
                    axis=-1, keepdims=True)

    qf = qp.reshape(B * Hq, Sqp, D)
    of = dop.reshape(B * Hq, Sqp, D)
    mf = mp.reshape(B * Hq, Sqp, 1)
    df = dp_.reshape(B * Hq, Sqp, 1)
    deltaf = delta.reshape(B * Hq, Sqp, 1)
    kf = kp.reshape(B * Hkv, Skp, D)
    vf = vp.reshape(B * Hkv, Skp, D)

    def kv_map_j_first(h, j, i):
        return ((h // Hq) * Hkv + (h % Hq) // group, j, 0)

    def kv_map_i_first(h, i, j):
        return ((h // Hq) * Hkv + (h % Hq) // group, j, 0)

    common = dict(causal=causal, block_q=block_q, block_k=block_k,
                  q_offset=q_offset)

    dkv = pl.pallas_call(
        functools.partial(_dkv_kernel, **common),
        grid=(B * Hq, nk, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda h, j, i: (h, i, 0)),
            pl.BlockSpec((1, block_k, D), kv_map_j_first),
            pl.BlockSpec((1, block_k, D), kv_map_j_first),
            pl.BlockSpec((1, block_q, D), lambda h, j, i: (h, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda h, j, i: (h, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda h, j, i: (h, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda h, j, i: (h, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda h, j, i: (h, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda h, j, i: (h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * Hq, Skp, D), jnp.float32),
            jax.ShapeDtypeStruct((B * Hq, Skp, D), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qf, kf, vf, of, mf, df, deltaf)
    dk_full, dv_full = dkv

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **common),
        grid=(B * Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, block_k, D), kv_map_i_first),
            pl.BlockSpec((1, block_k, D), kv_map_i_first),
            pl.BlockSpec((1, block_q, D), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda h, i, j: (h, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, Sqp, D), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qf, kf, vf, of, mf, df, deltaf)

    dq = dq.reshape(B, Hq, Sqp, D)[:, :, :Sq].astype(q.dtype)
    dk_full = dk_full.reshape(B, Hkv, group, Skp, D)[:, :, :, :Sk]
    dv_full = dv_full.reshape(B, Hkv, group, Skp, D)[:, :, :, :Sk]
    dk = jnp.sum(dk_full, axis=2).astype(k.dtype)   # group-sum (GQA)
    dv = jnp.sum(dv_full, axis=2).astype(v.dtype)
    return dq, dk, dv
