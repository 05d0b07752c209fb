"""The one device-mesh constructor.

JAX 0.9's ``jax.make_mesh`` defaults every axis to ``AxisType.Explicit``,
under which a gather on a model-sharded table (the parameter server's
cyclic ``n_wk`` rows) must name its output sharding.  Every mesh in this
repo is built here, with ``Auto`` axes: the compiler propagates shardings
and ``shard_map`` bodies see plain per-device arrays.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Optional[Sequence] = None) -> jax.sharding.Mesh:
    """A mesh of ``shape`` over ``devices`` (default: all of them)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)
