"""Mesh construction: every mesh in the repo is built here.

``jax.make_mesh`` defaults to Explicit axes, under which
``with_sharding_constraint`` (``models.common.logical_constraint``) and the
partitioner's automatic propagation refuse to run.  :func:`make_mesh`
builds every mesh with Auto axes instead.  Meshes are made by functions,
never at import time, so importing this module touches no device state —
the dry-run sets ``XLA_FLAGS=--xla_force_host_platform_device_count=512``
*before* any jax initialisation and only then builds meshes.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Tuple[int, ...], axis_names: Tuple[str, ...],
              devices: Optional[Sequence] = None) -> Mesh:
    """A mesh of ``shape`` with Auto axes, over ``devices`` (an array of
    that shape, or a flat list) or JAX's default device order."""
    types = (AxisType.Auto,) * len(axis_names)
    if devices is None:
        return jax.make_mesh(tuple(shape), tuple(axis_names),
                             axis_types=types)
    return Mesh(np.asarray(devices).reshape(shape), tuple(axis_names),
                axis_types=types)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """The assignment's production mesh: 16×16 single-pod (256 chips) or
    2×16×16 multi-pod (512 chips)."""
    if multi_pod:
        return make_mesh((2, 16, 16), ("pod", "data", "model"))
    return make_mesh((16, 16), ("data", "model"))


def mesh_for(kind: str) -> Mesh:
    if kind in ("single", "single_pod"):
        return make_production_mesh(multi_pod=False)
    if kind in ("multi", "multi_pod"):
        return make_production_mesh(multi_pod=True)
    if kind == "host":  # whatever the host actually has (tests/examples)
        return make_mesh((1, len(jax.devices())), ("data", "model"))
    raise ValueError(f"unknown mesh kind {kind!r}")
