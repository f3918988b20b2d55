"""Single-source NVFP4 (E2M1 + E4M3 group scales) numerics.

Every implementation of the FP4 grid in this repo — the jnp oracle in
``repro.core.quant``, the Pallas quantize kernel
(``repro.kernels.quantize_fp4``) and the grouped expert-FFN kernel
(``repro.kernels.grouped_fp4_ffn``) — imports the helpers below instead of
re-implementing the level table or the packed storage format.  Everything here is pure ``jnp`` vector
math (compare-select, no gathers) so the same functions trace both inside
Pallas kernel bodies and in ordinary jitted code, and the kernels cannot
drift from the oracle (``tests/test_nvfp4.py`` pins identity and bitwise
parity against the explicit level table).

Format recap (paper Appendix E): values quantize to E2M1
``{0, ±0.5, ±1, ±1.5, ±2, ±3, ±4, ±6}``; symmetric min-max per group of 16
along the contraction dim with local scale ``amax/6`` rounded to FP8 E4M3;
one global f32 scale per tensor keeps local scales inside E4M3 range.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

GROUP = 16
FP4_MAX = 6.0
INV_FP4_MAX = float(jnp.float32(1.0) / jnp.float32(6.0))
E4M3_MAX = 448.0
# round-to-nearest decision boundaries between consecutive E2M1 levels
FP4_MIDPOINTS = (0.25, 0.75, 1.25, 1.75, 2.5, 3.5, 5.0)


def fp4_index(mag: jax.Array) -> jax.Array:
    """Level index in [0,7] for a non-negative magnitude (int32)."""
    idx = jnp.zeros(mag.shape, jnp.int32)
    for mid in FP4_MIDPOINTS:
        idx = idx + (mag > mid).astype(jnp.int32)
    return idx


def fp4_level(idx: jax.Array) -> jax.Array:
    """E2M1 magnitude for a level index, via compare-select (no gather).

    levels [0, .5, 1, 1.5, 2, 3, 4, 6] == idx/2 for idx<4, idx-2 for
    idx in {4,5,6}, and 6 for idx==7.  Bitwise identical to a
    ``FP4_LEVELS[idx]`` table gather (all values exact in f32).
    """
    idxf = idx.astype(jnp.float32)
    hi = jnp.where(idxf == 7.0, 6.0, idxf - 2.0)
    return jnp.where(idxf < 4.0, 0.5 * idxf, hi)


def fp4_round(x: jax.Array) -> jax.Array:
    """Round to the nearest E2M1-representable value. Any shape, f32 math."""
    xf = x.astype(jnp.float32)
    return jnp.sign(xf) * fp4_level(fp4_index(jnp.abs(xf)))


def fp4_code(x: jax.Array) -> jax.Array:
    """4-bit code: bit3 = sign, bits0..2 = level index. int32 in [0,15]."""
    xf = x.astype(jnp.float32)
    idx = fp4_index(jnp.abs(xf))
    sign = (xf < 0).astype(jnp.int32)
    return sign * 8 + idx


def decode_level(code: jax.Array) -> jax.Array:
    """Signed E2M1 value from a 4-bit code (f32)."""
    code = code.astype(jnp.int32)
    sign = 1.0 - 2.0 * ((code >> 3) & 1).astype(jnp.float32)
    return sign * fp4_level(code & 7)


def e4m3_round(x: jax.Array) -> jax.Array:
    """Round-to-nearest-even onto FP8 E4M3 (±448, denormals at 2^-9).

    The binade exponent is read from the f32 bit pattern and the ulp is
    built from bits, so every backend (XLA, Mosaic) computes the same
    exact powers of two.
    """
    xf = x.astype(jnp.float32)
    mag = jnp.minimum(jnp.abs(xf), E4M3_MAX)
    e = (jax.lax.bitcast_convert_type(mag, jnp.int32) >> 23) - 127
    e = jnp.clip(e, -6, 8)                     # denormal floor at 2^-6
    # 3 mantissa bits: ulp = 2^(e-3); scale by its exact reciprocal
    inv_ulp = jax.lax.bitcast_convert_type((130 - e) << 23, jnp.float32)
    ulp = jax.lax.bitcast_convert_type((e + 124) << 23, jnp.float32)
    q = jnp.round(mag * inv_ulp) * ulp
    # rounding up may bump the exponent (e.g. 1.9375 -> 2.0): representable.
    q = jnp.where(mag == 0.0, 0.0, jnp.minimum(q, E4M3_MAX))
    return jnp.sign(xf) * q


# --------------------------------------------------------------------------
# storage format: weights [..., K, N] grouped and packed along K (axis -2)
# --------------------------------------------------------------------------
# The contraction axis K is second-to-last and the output axis N last, so
# every group, pack and unpack step splits only the sublane axis and never
# the 128-wide lane axis.  Within each run of ``2·group`` rows, row ``j``
# (low nibble) pairs with row ``j + group`` (high nibble): packed row
# ``c·group + j`` holds codes of rows ``2c·group + j`` and
# ``(2c+1)·group + j``.  The functions below are the one definition of
# that format; the Pallas kernels call them on VMEM tiles and the jnp
# oracle on whole tensors.
def pack_rows(codes: jax.Array, group: int = GROUP) -> jax.Array:
    """int codes ``[..., K, N]`` -> uint8 ``[..., K/2, N]``."""
    *lead, k, n = codes.shape
    c = codes.astype(jnp.int32).reshape(*lead, k // (2 * group), 2, group,
                                        n)
    packed = c[..., 0, :, :] | (c[..., 1, :, :] << 4)
    return packed.reshape(*lead, k // 2, n).astype(jnp.uint8)


def unpack_rows(packed: jax.Array, group: int = GROUP) -> jax.Array:
    """Inverse of :func:`pack_rows`: uint8 ``[..., K/2, N]`` -> int32
    codes ``[..., K, N]``."""
    *lead, k2, n = packed.shape
    p = packed.astype(jnp.int32).reshape(*lead, k2 // group, 1, group, n)
    codes = jnp.concatenate([p & 0xF, (p >> 4) & 0xF], axis=-3)
    return codes.reshape(*lead, 2 * k2, n)


def quantize_rows(w: jax.Array, global_scale: jax.Array,
                  group: int = GROUP):
    """NVFP4-quantize ``w [..., K, N]`` in groups of ``group`` along K.

    Returns ``(packed u8 [..., K/2, N], scales f32 [..., K/group, N])``;
    ``K`` must divide by ``2·group``.  Local scale = amax/6 rounded to
    E4M3, relative to the per-tensor ``global_scale``.
    """
    *lead, k, n = w.shape
    wg = w.astype(jnp.float32).reshape(*lead, k // group, group, n)
    amax = jnp.max(jnp.abs(wg), axis=-2)                  # [..., K/g, N]
    # multiply by the f32 reciprocal (not /6.0): XLA rewrites constant
    # divisions to reciprocal multiplies, so this keeps every backend on
    # the same expression
    s_local = e4m3_round(amax * INV_FP4_MAX / global_scale)
    s_local = jnp.maximum(s_local, 2.0 ** -9)             # avoid /0
    codes = fp4_code(wg / (s_local * global_scale)[..., None, :])
    return pack_rows(codes.reshape(*lead, k, n), group), s_local


def dequant_rows(packed: jax.Array, scales: jax.Array,
                 global_scale: jax.Array) -> jax.Array:
    """``[..., K/2, N]`` packed + ``[..., K/group, N]`` scales -> f32
    ``[..., K, N]``, multiplying ``(level · local) · global``."""
    group = 2 * packed.shape[-2] // scales.shape[-2]
    vals = decode_level(unpack_rows(packed, group))       # [..., K, N]
    *lead, k, n = vals.shape
    w = vals.reshape(*lead, k // group, group, n) * scales[..., None, :]
    return (w * global_scale).reshape(*lead, k, n)


def fake_quant_a4(x: jax.Array, group: int = GROUP,
                  axis: int = -1) -> jax.Array:
    """Activation NVFP4 fake-quant with *dynamic* per-group scales.

    Groups of ``group`` along ``axis`` (-1, or -2 for a transposed tile
    inside a kernel, where grouping along lanes would need a lane-splitting
    reshape); local scale = amax/6 kept in exact f32 (activations are
    quantized on the fly, so there is no E4M3 storage constraint — this is
    not the PTQ weight recipe).  Returns f32; callers cast as needed.
    Works for any leading shape; the grouped axis must divide by ``group``.
    """
    xf = x.astype(jnp.float32)
    shape = xf.shape
    if axis == -1:
        xg = xf.reshape(shape[:-1] + (shape[-1] // group, group))
    elif axis == -2:
        xg = xf.reshape(shape[:-2] + (shape[-2] // group, group, shape[-1]))
    else:
        raise ValueError(f"axis must be -1 or -2, got {axis}")
    amax = jnp.max(jnp.abs(xg), axis=axis, keepdims=True)
    gs = jnp.maximum(amax / FP4_MAX, 1e-20)       # dynamic per-group scale
    q = jnp.sign(xg / gs) * fp4_level(fp4_index(jnp.abs(xg / gs))) * gs
    return q.reshape(shape)
