"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (required so tests/benches see 1 CPU device while the
dry-run sees its 512 placeholder devices)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_local_mesh(model_parallel=1):
    """Whatever this host offers (tests / CPU examples)."""
    n = len(jax.devices())
    mp = model_parallel
    while n % mp:
        mp //= 2
    return _make_mesh((n // mp, mp), ("data", "model"))


def mesh_from_plan(plan):
    """Build a mesh from an ft.failure.MeshPlan (elastic restart path)."""
    return _make_mesh(plan.shape, plan.axes)
