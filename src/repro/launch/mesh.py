"""Production mesh construction (multi-pod dry-run §e).

Defined as FUNCTIONS so importing this module never touches jax device
state; ``dryrun.py`` sets XLA_FLAGS before any jax import to get 512
placeholder host devices.
"""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Single-pod (16, 16) = 256 chips; multi-pod (2, 16, 16) = 512."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(model_axis: int = 1):
    """Whatever devices this process has (CPU tests, one chip or four).

    Auto axes: the planes place their arrays with ``NamedSharding`` and
    ``with_sharding_constraint`` and let GSPMD propagate the rest; under
    jax's default explicit axes the sharded prefill update would be a
    sharding-type error."""
    n = len(jax.devices())
    data = n // model_axis
    return jax.make_mesh((data, model_axis), ("data", "model"),
                         axis_types=(AxisType.Auto, AxisType.Auto))


def dp_axes(mesh) -> Tuple[str, ...]:
    """The pure-data-parallel axes of a mesh ('pod' is data-parallel)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis(mesh) -> str:
    return "model" if "model" in mesh.axis_names else mesh.axis_names[-1]
