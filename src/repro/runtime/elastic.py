"""Elastic scaling: move a checkpoint between meshes of different shape.

When a pod (or any data-parallel slice) is lost, training resumes on a
smaller mesh: parameters keep their logical axes, so resharding is just
re-resolving logical→mesh specs on the new mesh and ``device_put``-ing the
host checkpoint through the new shardings.  EP degree changes re-bucket
experts automatically because the expert dimension is a logical axis like
any other.  The reverse (scale-up) works identically.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax

from repro.models.common import resolve_spec, use_mesh
from jax.sharding import NamedSharding

Tree = Any


def reshard(tree: Tree, spec_tree: Tree, new_mesh) -> Tree:
    """Re-distribute `tree` onto `new_mesh` using the P-spec tree (the same
    declaration used at init — single source of truth for layouts)."""
    from repro.models.common import P

    def mk(p, leaf):
        spec = resolve_spec(leaf.shape if hasattr(leaf, "shape") else p.shape,
                            _axes_for(p, leaf), new_mesh)
        return jax.device_put(leaf, NamedSharding(new_mesh, spec))

    def _axes_for(p, leaf):
        axes = tuple(p.axes)
        extra = len(leaf.shape) - len(axes)
        return (("layers",) * extra) + axes   # stacked scan dims lead

    return jax.tree.map(mk, spec_tree, tree,
                        is_leaf=lambda x: isinstance(x, P))


def shrink_mesh(mesh, lost_axis: str = "pod",
                lost_index: Optional[int] = None):
    """Mesh minus one slice of `lost_axis` (node-failure simulation).

    ``lost_index`` selects WHICH slice is lost (default: the last) — the
    serving-side elastic coordinator shrinks the specific EP rank that
    failed, not necessarily the tail one."""
    names = list(mesh.axis_names)
    shape = list(mesh.devices.shape)
    i = names.index(lost_axis)
    if shape[i] <= 1:
        raise ValueError(f"cannot shrink axis {lost_axis} below 1")
    lost = shape[i] - 1 if lost_index is None else int(lost_index)
    if not 0 <= lost < shape[i]:
        raise ValueError(f"lost_index {lost} out of [0, {shape[i]})")
    keep = mesh.devices.take([j for j in range(shape[i]) if j != lost],
                             axis=i)
    from repro.launch.mesh import make_mesh
    return make_mesh(keep.shape, tuple(names), keep)
