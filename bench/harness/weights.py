"""The benchmark's weights, drawn from ``--seed`` on the device in one
jitted call, in the parameter layout of the configuration's architecture
(``layout`` of ``bench/archs/<arch>.py``), which the serving engine takes.
On several chips the call writes each parameter straight into its shards;
the values are those of the one-device draw, bit for bit.

The same function makes the program's weights before the window and the
reference's after it, so the reference takes nothing the program made.

Routing is given structure (see the configuration's
``assumed.structured_router``): every vocabulary id has a seeded set of
``top_k`` preferred experts, vision ids (the upper half of the vocabulary)
drawing it from a Zipf order along expert ids and text ids uniformly.  The
router's columns are orthonormal expert directions and each embedding row
carries the directions of its set, so the top-k choice stands far above
bf16 rounding, and under the identity placement the hottest vision experts
share the first EP rank.
"""
from __future__ import annotations

import math
import zlib
from functools import partial
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (the driver's exceed 32 bits)."""
    seed = int(seed) % (1 << 64)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def _nest(flat: Dict[str, jax.Array]) -> dict:
    tree: dict = {}
    for path, v in flat.items():
        node = tree
        *parts, leaf = path.split("/")
        for part in parts:
            node = node.setdefault(part, {})
        node[leaf] = v
    return tree


def expert_sets(a, key: jax.Array) -> jax.Array:
    """[vocab, experts] 0/1 membership of each id's preferred experts."""
    e = a.n_experts
    vis = jnp.arange(a.vocab) >= a.vocab // 2
    zipf = -a.zipf_a * jnp.log(jnp.arange(1, e + 1, dtype=F32))
    scores = jnp.where(vis[:, None], zipf[None, :], 0.0) \
        + jax.random.gumbel(key, (a.vocab, e), F32)
    _, sets = jax.lax.top_k(scores, a.top_k)
    return jax.nn.one_hot(sets, e, dtype=F32).sum(1)


def _build(layout: Callable, a, key: jax.Array, replicated=None) -> dict:
    """Every parameter of ``layout(a)``; ``replicated`` (a sharding) keeps
    the structured draw whole on every chip, so that a sharded draw
    computes it as one device does."""
    whole = (lambda v: v) if replicated is None \
        else (lambda v: jax.lax.with_sharding_constraint(v, replicated))
    k_dir, k_set, k_noise, k_rest = jax.random.split(key, 4)
    d = a.d_model
    dirs, _ = jnp.linalg.qr(jax.random.normal(k_dir, (d, a.n_experts), F32))
    dirs = whole(dirs)
    member = whole(expert_sets(a, k_set))
    struct = (member @ dirs.T) * math.sqrt(d / a.top_k)      # row RMS 1
    s = math.sqrt(a.id_noise_share)
    embed = whole(a.embed_rms * (
        math.sqrt(1.0 - s * s) * whole(struct)
        + s * jax.random.normal(k_noise, struct.shape, F32)))
    flat = {}
    for path, (shape, dtype, std) in layout(a).items():
        if path == "embed":
            flat[path] = embed.astype(dtype)
        elif path.endswith("moe/router"):
            flat[path] = jnp.broadcast_to(dirs, shape).astype(dtype)
        elif std == 0.0:
            flat[path] = jnp.zeros(shape, dtype)
        else:
            k = jax.random.fold_in(k_rest, zlib.crc32(path.encode()))
            flat[path] = (jax.random.normal(k, shape, F32) * std
                          ).astype(dtype)
    return _nest(flat)


_draw = jax.jit(_build, static_argnums=(0, 1))


def draw(mod, a, seed: int, shardings: Optional[dict] = None) -> dict:
    """All parameters for ``seed`` in the layout of architecture module
    ``mod``: on the default device, or, given ``shardings`` (a tree of
    ``NamedSharding`` like the parameters'), straight into those shards."""
    if shardings is None:
        return _draw(mod.layout, a, seed_key(seed))
    mesh = jax.tree.leaves(shardings)[0].mesh
    rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    return jax.jit(partial(_build, mod.layout, a, replicated=rep),
                   out_shardings=shardings)(seed_key(seed))
