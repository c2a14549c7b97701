"""GPipe-style pipeline parallelism over a mesh axis (the "pod" axis).

The layer-stacked parameters (L, ...) are sharded over the pipeline axis on
their leading dim — each rank owns L/n contiguous layers (its *stage*). The
global batch splits into microbatches that flow through the ring: on every
tick each rank (a) takes its current activation (a fresh microbatch on rank
0, the neighbor's output otherwise), (b) runs its stage (a local lax.scan
over its layer slice), and (c) ``ppermute``s the result rightward. After
``M + n - 1`` ticks all microbatches have exited the last stage; the bubble
fraction is the standard (n-1)/(M+n-1).

This composes with the data/model-axis sharding of everything inside the
stage body: the stage_fn sees ordinary (microbatch, seq, d) activations and
per-layer params, so TP/FSDP rules apply unchanged within a stage.

``pipeline_forward`` wires it for the dense-LM block stack (embedding and
logits are computed outside the pipelined region).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _pipeline_inner(stage_params, x_micro, *, stage_fn: Callable,
                    axis_name: str, n_stages: int):
    """Per-rank body. stage_params: this rank's (L/n, ...) layer slice.
    x_micro: (M, B_m, S, d) — full microbatch set (only rank 0 reads it)."""
    r = jax.lax.axis_index(axis_name)
    M = x_micro.shape[0]
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    buf = jnp.zeros_like(x_micro)            # outputs (filled on last rank)
    cur = jnp.zeros_like(x_micro[0])         # activation in flight

    def tick(carry, t):
        cur, buf = carry
        # rank 0 ingests microbatch t (when in range)
        mb_in = jnp.clip(t, 0, M - 1)
        fresh = jax.lax.dynamic_index_in_dim(x_micro, mb_in, keepdims=False)
        cur = jnp.where(r == 0, fresh, cur)
        out = stage_fn(stage_params, cur)
        # last rank banks microbatch (t - (n-1)) when in range
        mb_out = t - (n_stages - 1)
        bank = (r == n_stages - 1) & (mb_out >= 0)
        buf = jax.lax.cond(
            bank,
            lambda b: jax.lax.dynamic_update_index_in_dim(
                b, out, jnp.clip(mb_out, 0, M - 1), axis=0),
            lambda b: b,
            buf)
        cur = jax.lax.ppermute(out, axis_name, perm)
        return (cur, buf), None

    (cur, buf), _ = jax.lax.scan(tick, (cur, buf),
                                 jnp.arange(M + n_stages - 1))
    # results live on the last rank only — broadcast via masked psum
    mask = (r == n_stages - 1).astype(buf.dtype)
    return jax.lax.psum(buf * mask, axis_name)


def pipeline_apply(
    stacked_params,            # (L, ...) pytree, L % n_stages == 0
    x: jax.Array,              # (B, S, d) global batch
    mesh,
    stage_fn: Callable,        # (layer_params_slice, x) -> x (scans layers)
    *,
    axis_name: str = "pod",
    microbatches: int = 4,
) -> jax.Array:
    """Run the stacked layers as an n-stage GPipe pipeline over ``axis_name``.

    Parameters enter shard_map sharded on their leading (layer) dim; the
    activations enter replicated across the pipeline axis (they are sharded
    over data/model inside stage_fn by the usual rules)."""
    n = mesh.shape[axis_name]
    B = x.shape[0]
    assert B % microbatches == 0, (B, microbatches)
    x_micro = x.reshape((microbatches, B // microbatches) + x.shape[1:])

    param_specs = jax.tree_util.tree_map(
        lambda a: P(axis_name, *([None] * (a.ndim - 1))), stacked_params)

    inner = functools.partial(_pipeline_inner, stage_fn=stage_fn,
                              axis_name=axis_name, n_stages=n)
    out = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(param_specs, P()),
        out_specs=P(),
        check_vma=False,
    )(stacked_params, x_micro)
    return out.reshape(x.shape)
