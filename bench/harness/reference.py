"""Plain float32 reference of the served model, and its lower-precision
control.  Imports nothing of the program.

One causal forward over a request's prompt and served tokens, layer by
layer, with every matrix product at ``HIGHEST`` precision: embedding,
RMSNorm (scale ``1 + w``), attention with RoPE over the whole head, dense
SwiGLU, softmax top-k router with renormalized gates, routed experts over
all experts weighted by their gates, shared experts, final norm and LM
head.  Where the program ran a step's MoE layer in FP4, the reference runs
that position's routed experts as the program applies FP4: NVFP4 weights
(E2M1 codes, E4M3 group-16 scales along the contraction axis, one global
scale per expert stack) and per-group-16 dynamic activation fake-quant of
the expert input and of ``h``.

``mode="fp8"`` is the control: the same forward with both operands of
every matrix product rounded to float8 e4m3 (per-row activation scales,
per-output-column weight scales), the nearest precision below the
configuration's bfloat16.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from harness.arch import Arch

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
FP4_MIDS = (0.25, 0.75, 1.25, 1.75, 2.5, 3.5, 5.0)
FP4_LEVELS = (0.0, 0.5, 1.0, 1.5, 2.0, 3.0, 4.0, 6.0)
E4M3_MAX = 448.0
GROUP = 16


# ---------------------------------------------------------------- numerics
def q8(x, axes):
    """Round to float8 e4m3 with one scale per slice over ``axes``."""
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    s = jnp.maximum(amax / E4M3_MAX, 1e-30)
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def mm(spec: str, a, b, mode: str):
    """``einsum(spec, a, b)`` in f32; in fp8 mode both operands are first
    rounded over their contracted axes."""
    if mode == "fp8":
        lhs, out = spec.split("->")
        sa, sb = lhs.split(",")
        a = q8(a, tuple(i for i, c in enumerate(sa) if c not in out))
        b = q8(b, tuple(i for i, c in enumerate(sb) if c not in out))
    return jnp.einsum(spec, a, b, precision=HI)


def fp4_level(mag):
    idx = sum((mag > m).astype(jnp.int32) for m in FP4_MIDS)
    return jnp.asarray(FP4_LEVELS, F32)[idx]


def a4(x):
    """Dynamic NVFP4 fake-quant in groups of 16 along the last axis."""
    g = x.reshape(x.shape[:-1] + (x.shape[-1] // GROUP, GROUP))
    s = jnp.maximum(jnp.max(jnp.abs(g), -1, keepdims=True) / 6.0, 1e-20)
    q = jnp.sign(g / s) * fp4_level(jnp.abs(g / s)) * s
    return q.reshape(x.shape)


def e4m3(x):
    return jnp.minimum(x, E4M3_MAX).astype(jnp.float8_e4m3fn).astype(F32)


def nvfp4_weights(w):
    """Quantize-dequantize an expert stack ``[E, K, N]`` along K."""
    gs = jnp.maximum(jnp.max(jnp.abs(w)) / (6.0 * E4M3_MAX), 1e-20)
    e, k, n = w.shape
    g = w.reshape(e, k // GROUP, GROUP, n)
    amax = jnp.max(jnp.abs(g), axis=2, keepdims=True)
    s = jnp.maximum(e4m3(amax * jnp.float32(1.0 / 6.0) / gs), 2.0 ** -9)
    v = g / (s * gs)
    return (jnp.sign(v) * fp4_level(jnp.abs(v)) * s * gs).reshape(w.shape)


def rms(x, w, eps):
    scale = (1.0 + w.astype(F32)).reshape((1,) * (x.ndim - 1) + (-1,))
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def rope(x, pos, theta):
    d = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = (pos[:, None].astype(F32) * freqs[None, :])[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


# ---------------------------------------------------------------- layers
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
def moe(p, x, fp4_rows, *, a: Arch, mode: str, fp4: bool):
    """``fp4_rows [T]``: positions whose routed experts ran in FP4."""
    p = _f(p)
    h = rms(x, p["norm2"], a.norm_eps)
    m = p["moe"]
    probs = jax.nn.softmax(mm("td,de->te", h, m["router"], mode), axis=-1)
    top, idx = jax.lax.top_k(probs, a.top_k)
    gates = (jax.nn.one_hot(idx, a.n_experts, dtype=F32)
             * (top / top.sum(-1, keepdims=True))[..., None]).sum(1)
    g = mm("td,edf->tef", h, m["w_gate"], mode)
    u = mm("td,edf->tef", h, m["w_up"], mode)
    y = mm("tef,efd->td", jax.nn.silu(g) * u * gates[..., None],
           m["w_down"], mode)
    if fp4:
        xq = a4(h)
        g4 = jnp.einsum("td,edf->tef", xq, nvfp4_weights(m["w_gate"]),
                        precision=HI)
        u4 = jnp.einsum("td,edf->tef", xq, nvfp4_weights(m["w_up"]),
                        precision=HI)
        y4 = jnp.einsum("tef,efd->td", a4(jax.nn.silu(g4) * u4)
                        * gates[..., None], nvfp4_weights(m["w_down"]),
                        precision=HI)
        y = jnp.where(fp4_rows[:, None], y4, y)
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


# ---------------------------------------------------------------- forward
def forward(params, a: Arch, tokens: np.ndarray, fp4: np.ndarray,
            rows: np.ndarray, pad_to: int, mode: str = "f32",
            picks: list = None):
    """Logits ``[len(rows), vocab]`` at positions ``rows`` of one sequence.

    ``fp4 [T, n_moe]`` marks the positions and MoE layers the program ran
    in FP4; the sequence is padded to ``pad_to`` (causal, so padding after
    the last token changes nothing before it).  ``picks``, if given,
    collects each MoE layer's ``routes``."""
    t = len(tokens)
    tok = np.zeros(pad_to, np.int32)
    tok[:t] = tokens
    flags = np.zeros((pad_to, a.n_moe), bool)
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


@jax.jit
def _gaps(ref, served, ctrl):
    """Per position: the reference's best logit minus its logit at the
    served token, and at the control's first token."""
    best = ref.max(-1)
    at = jnp.take_along_axis(ref, served[:, None], -1)[:, 0]
    ctop = jnp.argmax(ctrl, -1)
    at_c = jnp.take_along_axis(ref, ctop[:, None], -1)[:, 0]
    return best - at, best - at_c


def check_sequences(params, a: Arch, seqs: List[Dict], pad_to: int,
                    control: bool = False) -> Dict[str, np.ndarray]:
    """Widest gaps over ``seqs`` (each: prompt, served tokens, fp4 flags).

    Returns per served token the gap of the program's token and, with
    ``control``, of the fp8 control's first token."""
    gap, gap_c = [], []
    with jax.default_matmul_precision("highest"):
        for s in seqs:
            toks = np.concatenate([s["prompt"], s["served"][:-1]])
            n_p = len(s["prompt"])
            rows = np.arange(n_p - 1, n_p - 1 + len(s["served"]))
            ref = forward(params, a, toks, s["fp4"], rows, pad_to)
            ctrl = forward(params, a, toks, s["fp4"], rows, pad_to,
                           mode="fp8") if control else ref
            g, gc = _gaps(ref, jnp.asarray(s["served"], jnp.int32), ctrl)
            gap.append(np.asarray(g))
            gap_c.append(np.asarray(gc))
    out = {"gap": np.concatenate(gap)}
    if control:
        out["gap_control"] = np.concatenate(gap_c)
    return out
