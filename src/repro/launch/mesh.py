"""Mesh construction for the production pods.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state).  Single pod: (data=16, model=16) = 256 TPU v5e chips;
multi-pod: (pod=2, data=16, model=16) = 512.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax

from repro.sharding.mesh import make_mesh
from repro.sharding.specs import MeshCtx


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def ctx_for(mesh) -> MeshCtx:
    """MeshCtx with dp = every non-model axis."""
    axes = tuple(mesh.axis_names)
    dp = tuple(a for a in axes if a != "model")
    model = "model" if "model" in axes else None
    return MeshCtx(mesh, dp, model)


def make_host_mesh(model: int = 1, data: Optional[int] = None):
    """Small mesh over whatever local devices exist (tests/examples)."""
    n = jax.device_count()
    data = data or (n // model)
    return make_mesh((data, model), ("data", "model"))
