"""Mesh construction: the one place this repo builds a ``jax.sharding.Mesh``.

FUNCTIONS (not module-level constants) so importing this module never
touches jax device state — callers decide when devices are materialized.
Production target: TPU v5e, 256 chips/pod (16x16), 2 pods for multi-pod.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple, axes: tuple):
    """``jax.make_mesh`` with Auto axis types.  JAX 0.7+ defaults to
    Explicit axes, under which ``with_sharding_constraint`` (``constrain``)
    and the shard_map MoE specs are rejected."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Tiny mesh over the real local devices (tests / CPU smoke)."""
    n = len(jax.devices())
    model = min(model, n)
    return make_mesh((n // model, model), ("data", "model"))
