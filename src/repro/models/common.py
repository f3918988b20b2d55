"""Shared model machinery: parameter specs, logical-axis sharding, norms, RoPE.

Parameter system
----------------
Models declare parameters as trees of :class:`P` leaves (shape + logical
axis names + init).  From one declaration we derive:

* concrete initialisation (``init_params``),
* abstract ``ShapeDtypeStruct`` trees for ``jax.eval_shape``/dry-run
  (``abstract_params``),
* ``NamedSharding`` trees via logical→mesh rules (``tree_shardings``).

Logical→mesh resolution is *shape aware*: a mesh axis is only used if it
divides the dimension, and never twice within one array (left-to-right
priority), which automatically resolves e.g. expert(model) vs ffn(model)
conflicts on expert weights.
"""
from __future__ import annotations

import dataclasses
import math
import threading
from typing import Any, Callable, Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

Tree = Any

# --------------------------------------------------------------------------
# logical axis rules
# --------------------------------------------------------------------------
# logical name -> mesh axes to try, in order; tuples try the full product
# first, then prefixes.
DEFAULT_RULES: Dict[str, Tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "seq": ("model",),           # sequence parallelism of activations
    "kv_seq": ("data", "model"),  # decode KV cache sequence dim
    "vocab": ("model",),
    "embed": ("data",),           # FSDP on d_model dims of weights
    "ffn": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "expert": ("model",),
    "d_inner": ("model",),        # mamba inner dim
    "layers": (),                 # stacked scan dim: never sharded
    "rank": (),                   # MLA low-rank dims: replicated
}


@dataclasses.dataclass(frozen=True)
class P:
    """Declaration of one parameter."""

    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"          # normal | zeros | ones | embed | scaled
    scale: float = 1.0            # stddev multiplier for normal/scaled
    dtype: Optional[str] = None   # override the model param dtype

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


class _MeshCtx(threading.local):
    def __init__(self):
        self.mesh: Optional[Mesh] = None
        self.rules: Dict[str, Tuple[str, ...]] = dict(DEFAULT_RULES)


_CTX = _MeshCtx()


class use_mesh:
    """Context manager activating a mesh (+ optional rule overrides)."""

    def __init__(self, mesh: Optional[Mesh], rules: Optional[Dict] = None):
        self.mesh, self.rules = mesh, rules
        self._saved: Tuple = ()

    def __enter__(self):
        self._saved = (_CTX.mesh, _CTX.rules)
        _CTX.mesh = self.mesh
        if self.rules is not None:
            _CTX.rules = {**DEFAULT_RULES, **self.rules}
        return self.mesh

    def __exit__(self, *exc):
        _CTX.mesh, _CTX.rules = self._saved
        return False


def current_mesh() -> Optional[Mesh]:
    return _CTX.mesh


def current_rules() -> Dict[str, Tuple[str, ...]]:
    return _CTX.rules


# --------------------------------------------------------------------------
# logical -> PartitionSpec resolution
# --------------------------------------------------------------------------
def _axis_sizes(mesh: Mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def resolve_spec(shape: Sequence[int],
                 axes: Sequence[Optional[str]],
                 mesh: Mesh,
                 rules: Optional[Dict] = None) -> PartitionSpec:
    """Shape-aware logical→mesh PartitionSpec with conflict resolution."""
    rules = rules if rules is not None else current_rules()
    sizes = _axis_sizes(mesh)
    used: set = set()
    entries = []
    for dim, name in zip(shape, axes):
        if name is None or name not in rules:
            entries.append(None)
            continue
        cand = [a for a in rules[name] if a in sizes and a not in used]
        # longest prefix of candidate axes whose product divides dim
        chosen: Tuple[str, ...] = ()
        prod = 1
        for a in cand:
            if dim % (prod * sizes[a]) == 0:
                prod *= sizes[a]
                chosen = chosen + (a,)
            else:
                break
        if chosen:
            used.update(chosen)
            entries.append(chosen if len(chosen) > 1 else chosen[0])
        else:
            entries.append(None)
    return PartitionSpec(*entries)


def named_sharding(shape, axes, mesh=None, rules=None) -> Optional[NamedSharding]:
    mesh = mesh if mesh is not None else current_mesh()
    if mesh is None:
        return None
    return NamedSharding(mesh, resolve_spec(shape, axes, mesh, rules))


def logical_constraint(x: jax.Array, axes: Sequence[Optional[str]]) -> jax.Array:
    """``with_sharding_constraint`` under the active mesh; no-op without one."""
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = resolve_spec(x.shape, axes, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# --------------------------------------------------------------------------
# parameter trees
# --------------------------------------------------------------------------
def _is_leaf(x) -> bool:
    return isinstance(x, P)


def _leaf_dtype(p: P, default_dtype: str) -> jnp.dtype:
    return jnp.dtype(p.dtype or default_dtype)


def _init_leaf(p: P, key, default_dtype: str, stack: int = 0) -> jax.Array:
    shape = (stack, *p.shape) if stack else p.shape
    dt = _leaf_dtype(p, default_dtype)
    if p.init == "zeros":
        return jnp.zeros(shape, dt)
    if p.init == "ones":
        return jnp.ones(shape, dt)
    if p.init == "embed":
        std = p.scale
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dt)
    # normal / scaled: fan-in scaled init on the second-to-last dim
    fan_in = p.shape[-2] if len(p.shape) >= 2 else p.shape[-1]
    std = p.scale / math.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dt)


def init_params(tree: Tree, key: jax.Array, default_dtype: str = "float32",
                stack: int = 0) -> Tree:
    """Initialise a tree of :class:`P`; ``stack`` adds a leading scan dim."""
    leaves, treedef = jax.tree.flatten(tree, is_leaf=_is_leaf)
    keys = jax.random.split(key, len(leaves))
    out = [_init_leaf(p, k, default_dtype, stack) for p, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, out)


def abstract_params(tree: Tree, default_dtype: str = "float32",
                    stack: int = 0) -> Tree:
    """ShapeDtypeStruct tree (with shardings if a mesh is active)."""
    mesh = current_mesh()

    def mk(p: P):
        shape = (stack, *p.shape) if stack else p.shape
        axes = (("layers",) + tuple(p.axes)) if stack else tuple(p.axes)
        sh = named_sharding(shape, axes, mesh) if mesh is not None else None
        return jax.ShapeDtypeStruct(shape, _leaf_dtype(p, default_dtype),
                                    sharding=sh)

    return jax.tree.map(mk, tree, is_leaf=_is_leaf)


def tree_shardings(tree: Tree, mesh: Optional[Mesh] = None, stack: int = 0,
                   rules: Optional[Dict] = None) -> Tree:
    """NamedSharding tree matching a P-tree."""
    mesh = mesh if mesh is not None else current_mesh()

    def mk(p: P):
        shape = (stack, *p.shape) if stack else p.shape
        axes = (("layers",) + tuple(p.axes)) if stack else tuple(p.axes)
        return NamedSharding(mesh, resolve_spec(shape, axes, mesh, rules))

    return jax.tree.map(mk, tree, is_leaf=_is_leaf)


def tree_bytes(tree: Tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


# --------------------------------------------------------------------------
# numerics
# --------------------------------------------------------------------------
def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    w = (1.0 + scale.astype(jnp.float32)).reshape(
        (1,) * (x.ndim - 1) + (-1,))    # explicit: rank promotion raises
    return (y * w).astype(x.dtype)


def activation_fn(name: str) -> Callable[[jax.Array], jax.Array]:
    return {"swiglu": jax.nn.silu, "geglu": jax.nn.gelu,
            "gelu": jax.nn.gelu}[name]


# RoPE ---------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., S, H, D] (or D rotary slice); positions: broadcastable to [..., S]."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                       # [d/2]
    freqs = freqs.reshape((1,) * positions.ndim + (-1,))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., S, d/2]
    # insert head axis
    angles = angles[..., None, :]                      # [..., S, 1, d/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def causal_mask(q_len: int, kv_len: int, q_offset: Union[int, jax.Array] = 0):
    """Boolean [q_len, kv_len] mask, True = attend."""
    q_pos = jnp.arange(q_len)[:, None] + q_offset
    kv_pos = jnp.arange(kv_len)[None, :]
    return kv_pos <= q_pos


def length_mask(kv_len: int, valid: jax.Array):
    """[..., kv_len] mask from per-example valid lengths."""
    return jnp.arange(kv_len)[None, :] < valid[..., None]
