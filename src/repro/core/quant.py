"""NVFP4 quantization recipe (paper Appendix E), pure-jnp reference.

Weights & activations in FP4 E2M1 ({0,±0.5,±1,±1.5,±2,±3,±4,±6}), symmetric
min-max per group of 16 along the contraction dim; local scale = amax/6
stored in FP8 E4M3; one global FP32 scale per tensor aligns magnitudes so
local scales fit E4M3 range.  These functions are the numerical oracle for
the Pallas kernels in ``repro/kernels`` and the accuracy-measurement path
of the benchmarks (the simulated dequantized values are bit-identical to
what an NVFP4 GEMM consumes).

Weights are stored as ``[..., K, N]`` (contraction second-to-last, output
last) and grouped along K; the packed layout is defined once, in
:mod:`repro.kernels.nvfp4`, for this oracle and the kernels alike.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels import nvfp4

# Explicit level table kept for tests/inspection; the rounding math is
# single-sourced in repro.kernels.nvfp4 (compare-select, bitwise identical
# to a table gather) so the Pallas kernels and this oracle cannot drift.
FP4_LEVELS = jnp.array([0.0, 0.5, 1.0, 1.5, 2.0, 3.0, 4.0, 6.0], jnp.float32)
# decision boundaries between consecutive levels (round-to-nearest)
FP4_MIDPOINTS = jnp.array(nvfp4.FP4_MIDPOINTS, jnp.float32)
FP4_MAX = nvfp4.FP4_MAX
INV_FP4_MAX = nvfp4.INV_FP4_MAX
E4M3_MAX = nvfp4.E4M3_MAX
GROUP = nvfp4.GROUP

fp4_round = nvfp4.fp4_round
fp4_code = nvfp4.fp4_code
fp4_decode = nvfp4.decode_level
e4m3_round = nvfp4.e4m3_round


class QTensor(NamedTuple):
    """Group-quantized NVFP4 weight, grouped and packed along K (axis -2)."""

    packed: jax.Array        # uint8 [..., K/2, N]
    scales: jax.Array        # f32 (e4m3-valued) [..., K/GROUP, N]
    global_scale: jax.Array  # f32 scalar

    @property
    def k(self) -> int:
        return self.packed.shape[-2] * 2


def global_scale_for(w: jax.Array) -> jax.Array:
    """Per-tensor scale aligning group amaxes into E4M3 range (precomputed
    at PTQ calibration time in the paper; an input to the runtime kernel)."""
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)))
    return jnp.maximum(amax / (FP4_MAX * E4M3_MAX), 1e-20).astype(jnp.float32)


def quantize_fp4(w: jax.Array, group: int = GROUP,
                 global_scale: jax.Array | None = None) -> QTensor:
    """NVFP4 group quantization of ``w [..., K, N]`` along K
    (``K % (2·group) == 0``)."""
    k = w.shape[-2]
    assert k % (2 * group) == 0, (w.shape, group)
    gscale = global_scale_for(w) if global_scale is None \
        else jnp.asarray(global_scale, jnp.float32)
    packed, scales = nvfp4.quantize_rows(w, gscale, group)
    return QTensor(packed, scales, gscale.astype(jnp.float32))


def dequantize_fp4(q: QTensor, dtype=jnp.float32) -> jax.Array:
    """``QTensor`` -> ``[..., K, N]`` in ``dtype``."""
    return nvfp4.dequant_rows(q.packed, q.scales,
                              q.global_scale).astype(dtype)


def fp4_sim(x: jax.Array, group: int = GROUP, axis: int = -1) -> jax.Array:
    """Fake-quantize (quantize+dequantize) along ``axis`` (-1 or -2), same
    dtype.

    Gradient-transparent (straight-through) so it can sit in train graphs.
    """
    xs = x.swapaxes(-1, -2) if axis == -1 else x
    q = quantize_fp4(jax.lax.stop_gradient(xs), group)
    dq = dequantize_fp4(q, jnp.float32)
    dq = dq.swapaxes(-1, -2) if axis == -1 else dq
    xf = x.astype(jnp.float32)
    return (xf + jax.lax.stop_gradient(dq - xf)).astype(x.dtype)


def quant_error(w: jax.Array, group: int = GROUP) -> jax.Array:
    """Relative Frobenius error of the NVFP4 round-trip (accuracy proxy)."""
    wf = w.astype(jnp.float32)
    dq = dequantize_fp4(quantize_fp4(wf, group))
    return jnp.linalg.norm(dq - wf) / jnp.maximum(jnp.linalg.norm(wf), 1e-20)


# --------------------------------------------------------------------------
# quantized matmul references (the numerics the kernels must match)
# --------------------------------------------------------------------------
def matmul_w4a16(x: jax.Array, qw: QTensor) -> jax.Array:
    """x [M,K] @ dequant(qw) [K,N]."""
    w = dequantize_fp4(qw, jnp.float32)                       # [K,N]
    return (x.astype(jnp.float32) @ w).astype(x.dtype)


def matmul_w4a4(x: jax.Array, qw: QTensor, group: int = GROUP) -> jax.Array:
    """NVFP4 W4A4 GEMM simulation: both operands fake-quantized per group-K."""
    xq = fp4_sim(x.astype(jnp.float32), group)
    w = dequantize_fp4(qw, jnp.float32)
    return (xq @ w).astype(x.dtype)
