"""Train step factory: grad accumulation, remat, optional compressed DDP.

``make_train_step`` builds the pjit-able step:

    (params, opt_state, batch) → (params, opt_state, metrics)

* microbatching: the global batch splits into ``tc.microbatches`` slices;
  gradients accumulate in fp32 through a ``lax.scan`` — backward collectives
  of microbatch i overlap compute of microbatch i+1 under XLA's scheduler.
* loss = model loss (CE + z-loss + MoE aux) from the registry.
* optional int8 gradient compression (``tc.grad_compression``): the step is
  wrapped in ``shard_map`` over the data axis; per-shard gradients are
  all-reduced with error feedback (``parallel.collectives``) and the error
  buffer rides in the optimizer state extras.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, TrainConfig
from repro.optim import adamw


def make_loss_and_grad(loss_fn, tc: TrainConfig):
    def loss_wrap(params, batch):
        loss, metrics = loss_fn(params, batch)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_wrap, has_aux=True)

    def accumulate(params, batch):
        """Gradients over the whole batch, microbatched."""
        n = tc.microbatches
        if n <= 1:
            (loss, metrics), grads = grad_fn(params, batch)
            return loss, metrics, grads
        B = jax.tree_util.tree_leaves(batch)[0].shape[0]
        assert B % n == 0, (B, n)
        micro = jax.tree_util.tree_map(
            lambda x: x.reshape((n, B // n) + x.shape[1:]), batch)

        def body(carry, mb):
            loss_acc, grads_acc = carry
            (loss, metrics), grads = grad_fn(params, mb)
            grads_acc = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(jnp.float32), grads_acc, grads)
            return (loss_acc + loss, grads_acc), metrics

        zeros = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (loss_sum, grads), metrics = jax.lax.scan(
            body, (jnp.zeros(()), zeros), micro)
        grads = jax.tree_util.tree_map(lambda g: g / n, grads)
        metrics = jax.tree_util.tree_map(lambda m: m[-1], metrics)
        return loss_sum / n, metrics, grads

    return accumulate


def make_train_step(loss_fn: Callable, tc: TrainConfig):
    accumulate = make_loss_and_grad(loss_fn, tc)

    def train_step(params, opt_state: adamw.AdamWState, batch
                   ) -> Tuple[Any, adamw.AdamWState, Dict[str, jax.Array]]:
        loss, metrics, grads = accumulate(params, batch)
        params, opt_state, info = adamw.apply_updates(
            params, grads, opt_state, tc)
        out = {"loss": loss, **metrics, **info}
        return params, opt_state, out

    return train_step


def make_ddp_train_step(loss_fn: Callable, tc: TrainConfig, mesh,
                        data_axis: str = "data"):
    """shard_map DDP step with int8 error-feedback gradient compression.

    Parameters are replicated across ``data_axis``; each shard computes
    gradients on its slice of the batch; gradients cross the wire as int8.
    State carries the error-feedback buffers.
    """
    from jax.sharding import PartitionSpec as P

    from repro.parallel.collectives import compressed_psum_tree

    accumulate = make_loss_and_grad(loss_fn, tc)

    def _step(params, opt_state, errors, batch):
        loss, metrics, grads = accumulate(params, batch)
        loss = jax.lax.pmean(loss, data_axis)
        if tc.grad_compression:
            grads, errors = compressed_psum_tree(grads, data_axis, errors)
        else:
            grads = jax.tree_util.tree_map(
                lambda g: jax.lax.pmean(g, data_axis), grads)
        params, opt_state, info = adamw.apply_updates(
            params, grads, opt_state, tc)
        return params, opt_state, errors, {"loss": loss, **info}

    pspec_params = P()           # replicated
    pspec_batch = P(data_axis)   # batch-sharded

    return jax.shard_map(
        _step, mesh=mesh,
        in_specs=(pspec_params, pspec_params, pspec_params, pspec_batch),
        out_specs=(pspec_params, pspec_params, pspec_params, pspec_params),
        check_vma=False,
    )
