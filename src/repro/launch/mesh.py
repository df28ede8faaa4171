"""Production mesh construction.

Single pod: 16×16 = 256 chips, axes (data, model).
Multi-pod:  2×16×16 = 512 chips, axes (pod, data, model) — the pod axis is
the DCN dimension; gradient reductions cross it once per step, everything
else stays on intra-pod ICI.

Every axis is ``AxisType.Auto``: the model code places activations with
``with_sharding_constraint`` and leaves the rest to XLA's propagation,
which is the implicit style (``jax.make_mesh`` defaults to Explicit axes).

A function, not a module constant: importing this module must never touch
jax device state (the dry-run sets XLA_FLAGS before the first jax use).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """A small mesh over the first ``data * model`` devices."""
    return _auto_mesh((data, model), ("data", "model"))
