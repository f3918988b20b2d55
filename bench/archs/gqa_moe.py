"""Architecture ``gqa_moe``: a decoder of GQA attention blocks with RoPE,
leading dense SwiGLU layers, then MoE layers (softmax top-k router with
renormalized gates, SwiGLU routed experts, optional shared experts).

What the benchmark needs of one architecture (``bench/harness/arch.py``):
its sizes, the program's ``ModelConfig``, the parameter layout, the plain
float32 reference forward, and the operation and byte counts the readers
use.  A configuration file with no ``"arch"`` key runs this one.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harness.flops import BF16, FP4_BYTES
from harness.reference import (F32, HI, a4, mm, nvfp4_weights, rms,
                               rope)


# ---------------------------------------------------------------- sizes
@dataclass(frozen=True)
class Arch:
    d_model: int
    n_layers: int
    n_dense: int               # leading dense-FFN layers
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int                  # dense FFN width (0 when no dense layer)
    n_experts: int
    top_k: int
    d_expert: int
    n_shared: int
    vocab: int
    rope_theta: float
    norm_eps: float
    capacity_factor: float
    param_dtype: str
    zipf_a: float
    embed_rms: float
    id_noise_share: float

    @property
    def n_moe(self) -> int:
        return self.n_layers - self.n_dense


def sizes(conf: dict) -> Arch:
    """Read a configuration file's published keys (HF names) plus its
    ``assumed`` block."""
    a = conf["assumed"]
    d = int(conf["hidden_size"])
    heads = int(conf["num_attention_heads"])
    n_dense = int(conf.get("first_k_dense_replace", 0))
    n_experts = int(conf.get("n_routed_experts", conf.get("num_experts", 0)))
    router = a["structured_router"]
    return Arch(
        d_model=d, n_layers=int(conf["num_hidden_layers"]), n_dense=n_dense,
        n_heads=heads,
        n_kv_heads=int(conf.get("num_key_value_heads", heads)),
        head_dim=int(a.get("head_dim", d // heads)),
        d_ff=int(conf["intermediate_size"]) if n_dense else 0,
        n_experts=n_experts, top_k=int(conf["num_experts_per_tok"]),
        d_expert=int(conf.get("moe_intermediate_size",
                              conf["intermediate_size"])),
        n_shared=int(conf.get("n_shared_experts", 0)),
        vocab=int(conf["vocab_size"]),
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]),
        capacity_factor=float(a["capacity_factor"]),
        param_dtype=a["param_dtype"],
        zipf_a=float(router["zipf_a"]), embed_rms=float(router["embed_rms"]),
        id_noise_share=float(router["id_noise_share"]))


def model_config(name: str, a: Arch):
    """The configuration as the program runs it."""
    from repro.configs.base import ModelConfig, MoEConfig
    return ModelConfig(
        name=name, family="moe", n_layers=a.n_layers, d_model=a.d_model,
        n_heads=a.n_heads, n_kv_heads=a.n_kv_heads, head_dim=a.head_dim,
        d_ff=a.d_ff, vocab_size=a.vocab,
        moe=MoEConfig(num_experts=a.n_experts, top_k=a.top_k,
                      d_ff=a.d_expert, n_shared_experts=a.n_shared,
                      capacity_factor=a.capacity_factor),
        n_dense_layers=a.n_dense, layer_pattern="attn", activation="swiglu",
        rope_theta=a.rope_theta, norm_eps=a.norm_eps,
        param_dtype=a.param_dtype)


# ---------------------------------------------------------------- layout
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


# ---------------------------------------------------------------- reference
def _f(tree):
    return jax.tree.map(lambda v: v.astype(F32), tree)


@partial(jax.jit, static_argnames=("a", "mode"))
def attention(p, x, *, a: Arch, mode: str):
    p = _f(p)
    t = x.shape[0]
    h = rms(x, p["norm1"], a.norm_eps)
    at = p["attn"]
    q = mm("td,dhe->the", h, at["wq"], mode)
    k = mm("td,dhe->the", h, at["wk"], mode)
    v = mm("td,dhe->the", h, at["wv"], mode)
    pos = jnp.arange(t)
    q, k = rope(q, pos, a.rope_theta), rope(k, pos, a.rope_theta)
    rep = a.n_heads // a.n_kv_heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = mm("qhe,khe->hqk", q, k, mode) / math.sqrt(a.head_dim)
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    o = mm("hqk,khe->qhe", jax.nn.softmax(s, axis=-1), v, mode)
    return x + mm("qhe,hed->qd", o, at["wo"], mode)


def _swiglu(p, h, mode):
    g = mm("td,df->tf", h, p["w_gate"], mode)
    u = mm("td,df->tf", h, p["w_up"], mode)
    return mm("tf,fd->td", jax.nn.silu(g) * u, p["w_down"], mode)


@partial(jax.jit, static_argnames=("a", "mode"))
def dense_ffn(p, x, *, a: Arch, mode: str):
    p = _f(p)
    return x + _swiglu(p["ffn"], rms(x, p["norm2"], a.norm_eps), mode)


@partial(jax.jit, static_argnames=("a", "mode", "fp4"))
def moe(p, x, fp4_ranks, *, a: Arch, mode: str, fp4: bool):
    """``fp4_ranks [T, ep]``: per position, the EP ranks whose routed
    experts ran in FP4.  Rank ``r`` holds experts ``r*E/ep`` to
    ``(r+1)*E/ep - 1`` and quantizes its own stack (one NVFP4 global scale
    a rank); on one chip ``ep`` is 1 and a flag is the whole layer."""
    p = _f(p)
    h = rms(x, p["norm2"], a.norm_eps)
    m = p["moe"]
    probs = jax.nn.softmax(mm("td,de->te", h, m["router"], mode), axis=-1)
    top, idx = jax.lax.top_k(probs, a.top_k)
    gates = (jax.nn.one_hot(idx, a.n_experts, dtype=F32)
             * (top / top.sum(-1, keepdims=True))[..., None]).sum(1)
    g = mm("td,edf->tef", h, m["w_gate"], mode)
    u = mm("td,edf->tef", h, m["w_up"], mode)
    if fp4:
        ep = fp4_ranks.shape[1]
        on = jnp.repeat(fp4_ranks, a.n_experts // ep, axis=1)    # [T, E]
        y = mm("tef,efd->td", jax.nn.silu(g) * u
               * jnp.where(on, 0.0, gates)[..., None], m["w_down"], mode)
        xq = a4(h)
        g4 = jnp.einsum("td,edf->tef", xq, nvfp4_weights(m["w_gate"], ep),
                        precision=HI)
        u4 = jnp.einsum("td,edf->tef", xq, nvfp4_weights(m["w_up"], ep),
                        precision=HI)
        y = y + jnp.einsum("tef,efd->td", a4(jax.nn.silu(g4) * u4)
                           * jnp.where(on, gates, 0.0)[..., None],
                           nvfp4_weights(m["w_down"], ep), precision=HI)
    else:
        y = mm("tef,efd->td", jax.nn.silu(g) * u * gates[..., None],
               m["w_down"], mode)
    if "shared" in p:
        y = y + _swiglu(p["shared"], h, mode)
    return x + y


@partial(jax.jit, static_argnames=("a", "mode"))
def head(final_norm, unembed, x, rows, *, a: Arch, mode: str):
    """Logits at positions ``rows``."""
    hx = rms(x[rows], final_norm.astype(F32), a.norm_eps)
    return mm("td,dv->tv", hx, unembed.astype(F32), mode)


@partial(jax.jit, static_argnames=("a",))
def routes(p, x, *, a: Arch):
    """The experts each position's router picks, ``[T, top_k]``."""
    h = rms(x, p["norm2"].astype(F32), a.norm_eps)
    logits = mm("td,de->te", h, p["moe"]["router"].astype(F32), "f32")
    return jax.lax.top_k(logits, a.top_k)[1]


def forward(params, a: Arch, tokens: np.ndarray, fp4: np.ndarray,
            rows: np.ndarray, pad_to: int, mode: str = "f32",
            picks: list = None):
    """Logits ``[len(rows), vocab]`` at positions ``rows`` of one sequence,
    one layer at a time.

    ``fp4 [T, n_moe, ep]`` marks, per position and MoE layer, the EP ranks
    that ran their experts in FP4; the sequence is padded to ``pad_to``
    (causal, so padding after the last token changes nothing before it).
    ``picks``, if given, collects each MoE layer's ``routes``."""
    t = len(tokens)
    tok = np.zeros(pad_to, np.int32)
    tok[:t] = tokens
    flags = np.zeros((pad_to,) + fp4.shape[1:], bool)
    flags[:t] = fp4
    x = params["embed"][jnp.asarray(tok)].astype(F32)

    def part(p, *keys):
        return {k: p[k] for k in keys if k in p}

    for i in range(a.n_dense):
        p = params["prefix"][str(i)]
        x = attention(part(p, "norm1", "attn"), x, a=a, mode=mode)
        x = dense_ffn(part(p, "norm2", "ffn"), x, a=a, mode=mode)
    blocks = params["blocks"]["layer0"]
    for layer in range(a.n_moe):
        p = jax.tree.map(lambda v: v[layer], blocks)
        x = attention(part(p, "norm1", "attn"), x, a=a, mode=mode)
        if picks is not None:
            picks.append(np.asarray(routes(part(p, "norm2", "moe"), x,
                                           a=a))[:t])
        x = moe(part(p, "norm2", "moe", "shared"), x,
                jnp.asarray(flags[:, layer]), a=a, mode=mode,
                fp4=bool(flags[:, layer].any()))
    return head(params["final_norm"], params["unembed"], x,
                jnp.asarray(rows, jnp.int32), a=a, mode=mode)


# ---------------------------------------------------------------- counts
# Two operations per multiply-add, SwiGLU = three matrices (as
# ``repro.obs.ledger``); padding rows, capacity padding and the pad slot
# count nothing.
def attention_params(a: Arch) -> int:
    return a.d_model * a.head_dim * (2 * a.n_heads + 2 * a.n_kv_heads)


def active_params(a: Arch) -> int:
    """Parameters one token multiplies, embedding and LM head left out."""
    dense = a.n_dense * (attention_params(a) + 3 * a.d_model * a.d_ff)
    per_moe = (attention_params(a)
               + (a.top_k + a.n_shared) * 3 * a.d_model * a.d_expert
               + a.d_model * a.n_experts)
    return dense + a.n_moe * per_moe


def token_flops(a: Arch, pos: int) -> float:
    """Model operations of one prompt token at position ``pos``: two per
    active parameter, plus attention over its own context."""
    ctx = 4.0 * a.n_heads * a.head_dim * (pos + 1) * a.n_layers
    return 2.0 * active_params(a) + ctx


def prefill_flops(a: Arch, start: int, take: int) -> float:
    """``take`` prompt tokens from position ``start``."""
    n = take
    ctx_sum = n * start + n * (n + 1) / 2.0     # sum of (pos + 1)
    return 2.0 * active_params(a) * n \
        + 4.0 * a.n_heads * a.head_dim * a.n_layers * ctx_sum


def fp4_ffn_work(a: Arch, rows: float, experts: int):
    """(operations, bytes) of the grouped FP4 expert FFN over ``rows``
    routed assignments to ``experts`` non-empty experts of one layer."""
    flops = rows * 2.0 * 3.0 * a.d_model * a.d_expert
    weights = experts * 3.0 * a.d_model * a.d_expert * FP4_BYTES
    acts = rows * a.d_model * BF16 * 2.0        # rows in, rows out
    return flops, weights + acts


def quantize_work(a: Arch, experts: int) -> float:
    """Bytes of one layer's BF16 -> FP4 transformation of ``experts``
    experts: their three stacks read in bf16, codes and scales written."""
    return 3.0 * experts * a.d_model * a.d_expert * (BF16 + FP4_BYTES)
