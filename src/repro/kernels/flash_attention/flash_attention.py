"""Pallas TPU kernel: fused attention with the Softermax online recurrence.

This is the paper's co-design mapped to the TPU memory hierarchy: the ASIC's
Unnormed-Softmax-Unit / Normalization-Unit split becomes the classic
flash-attention two-phase structure, with three Softermax-specific changes:

1. **Base 2** — scores are exponentiated with ``exp2`` directly. For the
   e-base ablation the ``log2(e)`` factor is folded into the Q scaling
   *outside* the kernel (one multiply on a [*, d_head] tensor instead of a
   [*, S, S] tensor — the software form of the paper's base replacement).
2. **IntMax** — the running max is kept as ``ceil`` of the true max, so every
   rescale factor ``2^(m_prev - m_new)`` has an integer exponent and is an
   exact power of two (the paper's shifter; an exponent-add on the VPU).
3. **Online normalization** — one pass over K/V, no explicit max pass. The
   HBM pass the ASIC saves is exactly the HBM round-trip flash attention
   saves.

Grid: ``(batch*q_heads, num_q_blocks, num_kv_blocks)`` with kv sequential.
GQA is handled in the K/V index maps (q head → kv head = h // group).
Block sizes: q/kv blocks multiples of (8, 128); d_head is kept whole in VMEM
(the assigned archs have d_head ∈ {64, 128, 192}).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.numerics import NEG_INF


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_out_ref, d_out_ref,
                  acc_scr, m_scr, d_scr,
                  *, intmax: bool, causal: bool, block_q: int, block_k: int,
                  q_offset: int, kv_len: int):
    i, j = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        d_scr[...] = jnp.zeros_like(d_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = i * block_q + q_offset
    k_start = j * block_k

    def _body():
        q = q_ref[0].astype(jnp.float32)          # (BQ, D)
        k = k_ref[0].astype(jnp.float32)          # (BK, D)
        v = v_ref[0].astype(jnp.float32)          # (BK, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)   # (BQ, BK)
        kj = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        if causal:
            qi = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            s = jnp.where(qi >= kj, s, NEG_INF)
        else:
            # padded kv tail (non-causal): mask positions beyond the true Sk
            s = jnp.where(kj < kv_len, s, NEG_INF)
        m_prev = m_scr[...]
        sl = jnp.ceil(s) if intmax else s         # IntMax
        m_new = jnp.maximum(m_prev, jnp.max(sl, axis=1, keepdims=True))
        alpha = jnp.exp2(m_prev - m_new)          # exact power-of-two rescale
        p = jnp.exp2(s - m_new)                   # base-2, no log2e multiply
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        d_scr[...] = d_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_scr[...] = m_new

    if causal:
        # Skip kv blocks strictly above the diagonal for every row in the tile.
        pl.when(k_start <= q_start + block_q - 1)(_body)
    else:
        _body()

    @pl.when(j == nk - 1)
    def _fin():
        d = d_scr[...]
        recip = jnp.where(d > 0, 1.0 / jnp.where(d > 0, d, 1.0), 0.0)
        o_ref[0] = (acc_scr[...] * recip).astype(o_ref.dtype)
        # row statistics saved for the flash backward pass
        m_out_ref[0] = m_scr[...]
        d_out_ref[0] = d_scr[...]


@functools.partial(
    jax.jit,
    static_argnames=("causal", "intmax", "block_q", "block_k", "interpret",
                     "return_stats"),
)
def flash_attention(
    q: jax.Array,  # (B, Hq, Sq, D) — pre-scaled (1/sqrt d, and log2e if e-base)
    k: jax.Array,  # (B, Hkv, Sk, D)
    v: jax.Array,  # (B, Hkv, Sk, D)
    *,
    causal: bool = True,
    intmax: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
    return_stats: bool = False,  # also return (m, d) rows for the backward
):
    B, Hq, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    group = Hq // Hkv

    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    pq = (-Sq) % block_q
    pk = (-Sk) % block_k
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    Sqp, Skp = Sq + pq, Sk + pk

    qf = qp.reshape(B * Hq, Sqp, D)
    kf = kp.reshape(B * Hkv, Skp, D)
    vf = vp.reshape(B * Hkv, Skp, D)
    nq, nk = Sqp // block_q, Skp // block_k

    def kv_map(h, i, j):
        return ((h // Hq) * Hkv + (h % Hq) // group, j, 0)

    # Decode/extension convention: queries sit at the END of the kv axis
    # (q row r attends to kv positions <= Sk - Sq + r).
    q_offset = Sk - Sq

    out, m_rows, d_rows = pl.pallas_call(
        functools.partial(
            _flash_kernel,
            intmax=intmax,
            causal=causal,
            block_q=block_q,
            block_k=block_k,
            q_offset=q_offset,
            kv_len=Sk,
        ),
        grid=(B * Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, block_k, D), kv_map),
            pl.BlockSpec((1, block_k, D), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda h, i, j: (h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * Hq, Sqp, D), q.dtype),
            jax.ShapeDtypeStruct((B * Hq, Sqp, 1), jnp.float32),
            jax.ShapeDtypeStruct((B * Hq, Sqp, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qf, kf, vf)

    o = out.reshape(B, Hq, Sqp, D)[:, :, :Sq, :]
    if return_stats:
        return (o,
                m_rows.reshape(B, Hq, Sqp, 1)[:, :, :Sq],
                d_rows.reshape(B, Hq, Sqp, 1)[:, :, :Sq])
    return o
