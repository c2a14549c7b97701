"""Schema-driven parameters: one definition → init + sharding specs.

Each module defines a nested dict of ``ParamSpec`` (shape, logical axes,
initializer). From that single schema we derive:

* ``init_params``   — materialized (optionally sharded) parameter pytree
* ``logical_specs`` — same-structured tree of logical-axis tuples, consumed
                      by the sharding rules engine to build PartitionSpecs
* ``abstract_params`` — ShapeDtypeStructs for dry-run lowering (no memory)

Layer stacks for ``lax.scan`` are built with ``stack_schema`` which prepends
a "layers" dimension to every leaf.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    logical: Tuple[Optional[str], ...]
    init: str = "normal"      # normal | zeros | ones | embed
    std: Optional[float] = None  # default: 1/sqrt(fan_in = shape[-2])

    def __post_init__(self):
        assert len(self.shape) == len(self.logical), (self.shape, self.logical)


Schema = Dict[str, Any]  # nested dict with ParamSpec leaves


def stack_schema(schema: Schema, n_layers: int) -> Schema:
    """Prepend an (n_layers,) scan dimension to every leaf."""

    def _stack(ps: ParamSpec) -> ParamSpec:
        return ParamSpec((n_layers,) + ps.shape, ("layers",) + ps.logical,
                         ps.init, ps.std)

    return jax.tree_util.tree_map(
        _stack, schema, is_leaf=lambda x: isinstance(x, ParamSpec))


def logical_specs(schema: Schema):
    return jax.tree_util.tree_map(
        lambda ps: ps.logical, schema,
        is_leaf=lambda x: isinstance(x, ParamSpec))


def _init_leaf(key, ps: ParamSpec, dtype) -> jax.Array:
    """Draws directly in ``dtype``: a bf16 leaf never passes through an
    f32 copy on the device (f32 draws are unchanged)."""
    if ps.init == "zeros":
        return jnp.zeros(ps.shape, dtype)
    if ps.init == "ones":
        return jnp.ones(ps.shape, dtype)
    if ps.init == "embed":
        std = ps.std if ps.std is not None else 1.0
    elif ps.init == "normal":
        if ps.std is not None:
            std = ps.std
        else:
            # fan-in = second-to-last dim (or last for 1-D)
            fan_in = ps.shape[-2] if len(ps.shape) >= 2 else ps.shape[-1]
            std = 1.0 / np.sqrt(max(fan_in, 1))
    else:
        raise ValueError(f"unknown init {ps.init}")
    # a Python float keeps the product in ``dtype`` (a NumPy float64 would
    # promote a bf16 draw to f32)
    return jax.random.normal(key, ps.shape, dtype) * float(std)


def init_params(key: jax.Array, schema: Schema, dtype=jnp.float32):
    """Initialize a parameter pytree from a schema (deterministic per path)."""
    leaves, treedef = jax.tree_util.tree_flatten(
        schema, is_leaf=lambda x: isinstance(x, ParamSpec))
    keys = jax.random.split(key, len(leaves))
    vals = [_init_leaf(k, ps, dtype) for k, ps in zip(keys, leaves)]
    return jax.tree_util.tree_unflatten(treedef, vals)


def abstract_params(schema: Schema, dtype=jnp.float32):
    """ShapeDtypeStruct tree (for .lower() without allocating)."""
    return jax.tree_util.tree_map(
        lambda ps: jax.ShapeDtypeStruct(ps.shape, dtype), schema,
        is_leaf=lambda x: isinstance(x, ParamSpec))


def num_params(schema: Schema) -> int:
    return int(sum(
        np.prod(ps.shape) for ps in jax.tree_util.tree_leaves(
            schema, is_leaf=lambda x: isinstance(x, ParamSpec))))
