"""Pallas TPU kernel: on-the-fly NVFP4 (E2M1 + E4M3 group scales) quantization.

This is the paper's "Precision Transformation (T)" stage (§4.3) as a TPU
kernel: a BF16 expert weight stack resident in HBM is streamed through
VMEM one ``(K, block_n)`` column tile of one expert at a time, quantized
per group of 16 along the contraction axis K, and written back as packed
4-bit codes + E4M3-valued scales.  The per-tensor ``global_scale`` is
computed once over the whole stack (an input, as the paper's precomputed
scaling factor).

Layout (:mod:`repro.kernels.nvfp4`): ``w [G, K, N]`` → ``packed u8
[G, K/2, N]``, ``scales f32 [G, K/16, N]``.  Each block holds all of K, so
groups and nibble pairs never straddle a block, and the kernel body is
``nvfp4.quantize_rows`` — the oracle's own function — on the tile.  N
blocks are 128 lanes wide (or all of N when N is not a multiple of 128):
at K = 2048 the BF16 tile is 512 KiB and its f32 temporaries stay well
inside the default scoped VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.nvfp4 import GROUP, quantize_rows

LANES = 128


def _quantize_kernel(gscale_ref, w_ref, packed_ref, scales_ref, *,
                     group: int):
    packed, scales = quantize_rows(w_ref[0], gscale_ref[0, 0], group)
    packed_ref[0] = packed
    scales_ref[0] = scales


@functools.partial(jax.jit, static_argnames=("group", "interpret"))
def quantize_fp4_kernel(w: jax.Array, global_scale: jax.Array, *,
                        group: int = GROUP, interpret: bool = False):
    """w [G,K,N] bf16/f32 → (packed u8 [G,K/2,N], scales f32 [G,K/group,N])."""
    g, k, n = w.shape
    assert k % (2 * group) == 0, (w.shape, group)
    block_n = LANES if n % LANES == 0 else n
    return pl.pallas_call(
        functools.partial(_quantize_kernel, group=group),
        grid=(g, n // block_n),
        in_specs=[
            pl.BlockSpec((1, 1), lambda e, j: (0, 0)),
            pl.BlockSpec((1, k, block_n), lambda e, j: (e, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, k // 2, block_n), lambda e, j: (e, 0, j)),
            pl.BlockSpec((1, k // group, block_n), lambda e, j: (e, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((g, k // 2, n), jnp.uint8),
            jax.ShapeDtypeStruct((g, k // group, n), jnp.float32),
        ],
        interpret=interpret,
        name="quantize_fp4",
    )(jnp.asarray(global_scale, jnp.float32).reshape(1, 1), w)
