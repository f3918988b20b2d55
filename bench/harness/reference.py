"""The plain float32 reference's shared numerics and its lower-precision
control.  Imports nothing of the program.

Each architecture's reference forward (``bench/archs/<arch>.py``) is built
from these: every matrix product at ``HIGHEST`` precision through
:func:`mm`, RMSNorm (scale ``1 + w``), RoPE over the whole head, and, where
the program ran an EP rank's experts in FP4, the program's NVFP4 rounding:
NVFP4 weights (E2M1 codes, E4M3 group-16 scales along the contraction axis,
one global scale per rank's expert stack) and per-group-16 dynamic
activation fake-quant of the expert input and of ``h``.

``mode="fp8"`` is the control: the same forward with both operands of
every matrix product rounded to float8 e4m3 (per-row activation scales,
per-output-column weight scales), the nearest precision below the
configuration's bfloat16.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

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


def nvfp4_weights(w, ranks: int = 1):
    """Quantize-dequantize an expert stack ``[E, K, N]`` along K, as
    ``ranks`` EP ranks hold it: each rank's ``E / ranks`` experts under one
    global scale of their own."""
    e, k, n = w.shape
    g = w.reshape(ranks, e // ranks, k // GROUP, GROUP, n)
    gs = jnp.maximum(jnp.max(jnp.abs(g), axis=(1, 2, 3, 4), keepdims=True)
                     / (6.0 * E4M3_MAX), 1e-20)
    amax = jnp.max(jnp.abs(g), axis=3, keepdims=True)
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


@jax.jit
def _gaps(ref, served, ctrl):
    """Per position: the reference's best logit minus its logit at the
    served token, and at the control's first token."""
    best = ref.max(-1)
    at = jnp.take_along_axis(ref, served[:, None], -1)[:, 0]
    ctop = jnp.argmax(ctrl, -1)
    at_c = jnp.take_along_axis(ref, ctop[:, None], -1)[:, 0]
    return best - at, best - at_c


def check_sequences(forward, params, a, seqs: List[Dict], pad_to: int,
                    control: bool = False) -> Dict[str, np.ndarray]:
    """Gaps over ``seqs`` (each: prompt, served tokens, fp4 flags), with
    the architecture's reference ``forward`` (``bench/archs/<arch>.py``).

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
