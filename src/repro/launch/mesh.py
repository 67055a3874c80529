"""Production mesh construction.

A function (not a module-level constant) so importing never touches jax
device state. Single pod: 256 chips as (data=16, model=16). Multi-pod:
2 pods x 256 chips as (pod=2, data=16, model=16) — the pod axis carries
pure data parallelism (gradient all-reduce over DCI), `model` carries
tensor/expert parallelism inside a pod's ICI domain.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    # the sharding rules (repro.models.sharding) are written for Auto
    # axes; jax.make_mesh defaults to Explicit ones
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """1-device mesh for CPU smoke/examples (same axis names)."""
    return _auto_mesh((1, 1), ("data", "model"))
