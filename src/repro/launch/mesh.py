"""Mesh factories.

Defined as FUNCTIONS (not module constants) so importing this module never
touches jax device state — device count is locked on first jax init, and the
smoke tests must keep seeing 1 CPU device while the dry-run sees 512.

Every mesh is built with ``AxisType.Auto`` on every axis: the model code
annotates activations with ``with_sharding_constraint`` (``shard_act``),
which only accepts Auto axes, while ``jax.make_mesh`` defaults to Explicit.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """The one mesh constructor: ``jax.make_mesh`` with Auto axes."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single-pod (256 chips) or 2×16×16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Trivial mesh over the actually-present devices (tests / examples)."""
    n = len(jax.devices())
    assert n % model == 0
    return make_mesh((n // model, model), ("data", "model"))
