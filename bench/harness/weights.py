"""The benchmark's weights, drawn from ``--seed`` on the device in one
jitted call, in the parameter layout the serving engine takes.

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
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from harness.arch import Arch

F32 = jnp.float32


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (the driver's exceed 32 bits)."""
    seed = int(seed) % (1 << 64)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def layout(a: Arch) -> Dict[str, Tuple[Tuple[int, ...], str, float]]:
    """path -> (shape, dtype, std) of every parameter; std 0 = zeros,
    std < 0 = the structured draw."""
    d, h, kv, hd = a.d_model, a.n_heads, a.n_kv_heads, a.head_dim
    res = 1.0 / math.sqrt(2 * a.n_layers)
    p = a.param_dtype
    out: Dict[str, Tuple[Tuple[int, ...], str, float]] = {}

    def attn(pre, lead):
        out[pre + "norm1"] = (lead + (d,), p, 0.0)
        out[pre + "attn/wq"] = (lead + (d, h, hd), p, d ** -0.5)
        out[pre + "attn/wk"] = (lead + (d, kv, hd), p, d ** -0.5)
        out[pre + "attn/wv"] = (lead + (d, kv, hd), p, d ** -0.5)
        out[pre + "attn/wo"] = (lead + (h, hd, d), p, (h * hd) ** -0.5 * res)
        out[pre + "norm2"] = (lead + (d,), p, 0.0)

    def ffn(pre, lead, f):
        out[pre + "w_gate"] = (lead + (d, f), p, d ** -0.5)
        out[pre + "w_up"] = (lead + (d, f), p, d ** -0.5)
        out[pre + "w_down"] = (lead + (f, d), p, f ** -0.5 * res)

    out["embed"] = ((a.vocab, d), p, -1.0)
    out["final_norm"] = ((d,), p, 0.0)
    out["unembed"] = ((d, a.vocab), p, d ** -0.5)
    for i in range(a.n_dense):
        attn(f"prefix/{i}/", ())
        ffn(f"prefix/{i}/ffn/", (), a.d_ff)
    n = (a.n_moe,)
    attn("blocks/layer0/", n)
    e, fe = a.n_experts, a.d_expert
    out["blocks/layer0/moe/router"] = (n + (d, e), "float32", -1.0)
    out["blocks/layer0/moe/w_gate"] = (n + (e, d, fe), p, d ** -0.5)
    out["blocks/layer0/moe/w_up"] = (n + (e, d, fe), p, d ** -0.5)
    out["blocks/layer0/moe/w_down"] = (n + (e, fe, d), p, fe ** -0.5 * res)
    if a.n_shared:
        ffn("blocks/layer0/shared/", n, fe * a.n_shared)
    return out


def _nest(flat: Dict[str, jax.Array]) -> dict:
    tree: dict = {}
    for path, v in flat.items():
        node = tree
        *parts, leaf = path.split("/")
        for part in parts:
            node = node.setdefault(part, {})
        node[leaf] = v
    return tree


def expert_sets(a: Arch, key: jax.Array) -> jax.Array:
    """[vocab, experts] 0/1 membership of each id's preferred experts."""
    e = a.n_experts
    vis = jnp.arange(a.vocab) >= a.vocab // 2
    zipf = -a.zipf_a * jnp.log(jnp.arange(1, e + 1, dtype=F32))
    scores = jnp.where(vis[:, None], zipf[None, :], 0.0) \
        + jax.random.gumbel(key, (a.vocab, e), F32)
    _, sets = jax.lax.top_k(scores, a.top_k)
    return jax.nn.one_hot(sets, e, dtype=F32).sum(1)


@partial(jax.jit, static_argnums=0)
def _draw(a: Arch, key: jax.Array) -> dict:
    k_dir, k_set, k_noise, k_rest = jax.random.split(key, 4)
    d = a.d_model
    dirs, _ = jnp.linalg.qr(jax.random.normal(k_dir, (d, a.n_experts), F32))
    member = expert_sets(a, k_set)
    struct = (member @ dirs.T) * math.sqrt(d / a.top_k)      # row RMS 1
    s = math.sqrt(a.id_noise_share)
    embed = a.embed_rms * (math.sqrt(1.0 - s * s) * struct
                           + s * jax.random.normal(k_noise, struct.shape, F32))
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


def draw(a: Arch, seed: int) -> dict:
    """All parameters for ``seed``, made on the default device."""
    return _draw(a, seed_key(seed))
