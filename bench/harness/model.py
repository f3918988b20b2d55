"""The configuration as the program runs it: the parameter layout check,
the mesh of a multi-chip cell and the engine the window drives."""
from __future__ import annotations

from typing import Optional

import jax


def check_layout(cfg, params) -> None:
    """The drawn tree has the program's parameter layout, leaf for leaf."""
    from repro.models import transformer as tf
    want = jax.tree.map(lambda s: (tuple(s.shape), str(s.dtype)),
                        tf.abstract_model(cfg))
    got = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), params)
    if want != got:
        raise ValueError(f"drawn parameters do not match the program's "
                         f"layout:\n want {want}\n got {got}")


def mesh_of(chips: int):
    """The ``(1, chips)`` ``("data", "model")`` mesh of a multi-chip cell
    (EP over every chip); None on one chip."""
    if chips == 1:
        return None
    from repro.launch.mesh import make_mesh
    return make_mesh((1, chips), ("data", "model"))


def shardings(cfg) -> Optional[dict]:
    """Each parameter's sharding under the active mesh, as the program lays
    it out; None without a mesh."""
    from repro.models import transformer as tf
    from repro.models.common import current_mesh
    if current_mesh() is None:
        return None
    return jax.tree.map(lambda s: s.sharding, tf.abstract_model(cfg))


def make_engine(cfg, params, eng: dict, clock):
    from repro.configs.base import ReaLBConfig
    from repro.serving.engine import Engine
    return Engine(cfg, params, ReaLBConfig(), max_slots=eng["max_slots"],
                  max_len=eng["max_len"],
                  prefill_budget=eng["prefill_budget"],
                  virtual_ep=eng["virtual_ep"], clock=clock)
