"""Pallas TPU kernel: row-wise Softermax (two-phase, §IV).

The kernel pair mirrors the paper's microarchitecture exactly:

* ``_unnormed_kernel``   — the *Unnormed Softmax Unit*: streams V-blocks of
  each row through VMEM, keeps a running IntMax ``m`` and running denominator
  ``d`` in VMEM scratch, renormalizing ``d`` by the exact power-of-two
  ``2^(m_prev - m_new)`` (integer exponent ⇒ exponent-add, the TPU analogue of
  the paper's shifter), and writes *unnormed* numerators ``2^(x - m_running)``
  plus the per-block running max.
* ``_normalize_kernel``  — the *Normalization Unit*: rescales each numerator
  block by ``2^(m_block - m_final)`` (again an exact power of two) and
  multiplies by the reciprocal of the final denominator.

Grid layout: ``(num_row_blocks, num_v_blocks)`` with the V dimension iterated
sequentially ("arbitrary" semantics) so scratch carries across V-blocks —
the same dataflow as the hardware streaming slices of VectorSize.

BlockSpec tiling: ``(block_rows, block_v)`` tiles live in VMEM; block_v is a
multiple of 128 (lane width) and block_rows a multiple of 8 (sublanes) so the
VPU operates on full registers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.numerics import NEG_INF

_SAFE_NEG = NEG_INF  # finite mask value; (-inf)-(-inf) NaNs are avoided


def _unnormed_kernel(x_ref, y_ref, mrun_ref, mfin_ref, dfin_ref, m_scr, d_scr,
                     *, intmax: bool):
    j = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _SAFE_NEG)
        d_scr[...] = jnp.zeros_like(d_scr)

    x = x_ref[...].astype(jnp.float32)
    m_prev = m_scr[...]
    xl = jnp.ceil(x) if intmax else x  # IntMax unit applies ceil pre-max
    local_m = jnp.max(xl, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, local_m)
    y = jnp.exp2(x - m_new)  # Power-of-Two unit (base-2: no log2e multiply)
    y_ref[...] = y.astype(y_ref.dtype)
    # Reduction unit: shift-renormalize the running sum, add local sum.
    d_scr[...] = d_scr[...] * jnp.exp2(m_prev - m_new) + jnp.sum(
        y, axis=1, keepdims=True)
    m_scr[...] = m_new
    mrun_ref[...] = m_new

    @pl.when(j == nv - 1)
    def _fin():
        mfin_ref[...] = m_scr[...]
        dfin_ref[...] = d_scr[...]


def _normalize_kernel(y_ref, mrun_ref, mfin_ref, dfin_ref, o_ref):
    y = y_ref[...].astype(jnp.float32)
    # 2^(m_block - m_final): integer exponent under IntMax ⇒ exact scaling.
    shift = jnp.exp2(mrun_ref[...] - mfin_ref[...])
    d = dfin_ref[...]
    recip = jnp.where(d > 0, 1.0 / jnp.where(d > 0, d, 1.0), 0.0)
    o_ref[...] = (y * shift * recip).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("intmax", "block_rows", "block_v", "interpret"),
)
def softermax_rows(
    x: jax.Array,
    *,
    intmax: bool = True,
    block_rows: int = 8,
    block_v: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Softermax over the last axis of a 2-D array ``(rows, V)``.

    ``intmax=True`` is the paper's algorithm; ``intmax=False`` gives the plain
    base-2 online softmax (ablation).
    """
    rows, V = x.shape
    pr = (-rows) % block_rows
    pv = (-V) % block_v
    xp = jnp.pad(x, ((0, pr), (0, pv)), constant_values=_SAFE_NEG)
    R, Vp = xp.shape
    nr, nv = R // block_rows, Vp // block_v

    y, mrun, mfin, dfin = pl.pallas_call(
        functools.partial(_unnormed_kernel, intmax=intmax),
        grid=(nr, nv),
        in_specs=[pl.BlockSpec((block_rows, block_v), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((block_rows, block_v), lambda i, j: (i, j)),
            pl.BlockSpec((block_rows, 1), lambda i, j: (i, j)),
            pl.BlockSpec((block_rows, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, Vp), jnp.float32),
            jax.ShapeDtypeStruct((R, nv), jnp.float32),
            jax.ShapeDtypeStruct((R, 1), jnp.float32),
            jax.ShapeDtypeStruct((R, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_rows, 1), jnp.float32),
            pltpu.VMEM((block_rows, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xp)

    out = pl.pallas_call(
        _normalize_kernel,
        grid=(nr, nv),
        in_specs=[
            pl.BlockSpec((block_rows, block_v), lambda i, j: (i, j)),
            pl.BlockSpec((block_rows, 1), lambda i, j: (i, j)),
            pl.BlockSpec((block_rows, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, block_v), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((R, Vp), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
    )(y, mrun, mfin, dfin)

    return out[:rows, :V]
