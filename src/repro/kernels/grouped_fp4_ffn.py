"""Pallas TPU kernel: grouped NVFP4 expert FFN over the slot dimension.

This is the serving hot loop's expert compute (``_grouped_ffn_fp4`` in
``repro.core.ep_moe``) as ONE fused ragged-GEMM pipeline instead of
dequantize → ``ragged_dot`` × 3:

* tokens arrive sorted by local expert slot (``xs [M, D]``), with per-slot
  counts ``gs [G]``; the prefix-sum offsets and a skip map over empty
  slots are **scalar-prefetched** so BlockSpec index maps can steer weight
  DMA before the grid step runs;
* packed E2M1 codes + E4M3-valued group-16 scales stream HBM→VMEM and are
  dequantized on VMEM tiles by ``nvfp4.dequant_rows`` — the oracle's own
  function (compare-select decode, no gathers);
* the activation fake-quant (a4) of ``xs`` is row-local, so it runs once
  over all rows before the kernel; the a4 of ``h``, the SwiGLU
  ``act(x·Wg) ⊙ (x·Wu)`` stage and the down projection all happen on the
  same VMEM-resident tiles, so ``h [M, d_ff]`` never round-trips HBM and
  the dequantized weights never exist outside a VMEM tile.

Weights use the ``[G, K/2, N]`` / ``[G, K/16, N]`` layout of
:mod:`repro.kernels.nvfp4` (contraction axis second-to-last), so every
tile is a plain ``[rows, lanes]`` operand of an ``x @ W`` matmul and no
step splits the lane axis; the ``h`` a4 groups run along lanes, so the
kernel transposes the ``[bm, bf]`` tile and groups along sublanes.

Grid ``(M/bm, G, F/bf)``: token-block outermost so the f32 output
accumulator (VMEM scratch, zeroed at ``g==f==0``, flushed at the last
``(g, f)`` step) is revisited only on consecutive steps.  A slot with no
tokens (or no row overlap with the current token block) skips all compute
via ``pl.when``; its weight-block index is remapped to the last non-empty
slot at or before it (``gmap``), so consecutive grid steps see the same
block index and Pallas elides the DMA.

``bf`` is 256 or 128 — whichever first divides d_ff (all of d_ff when
none does); at Moonlight widths (D=2048, d_ff=1408) it is 128.  VMEM per
step there: x 512 KiB + acc 1 MiB + packed gate/up/down 3·128 KiB +
scales 3·64 KiB, double-buffered, plus two dequantized f32 weight tiles
of 1 MiB each.  On CPU the same kernel runs under ``interpret=True`` for
oracle parity (see ``repro.kernels.ops.ffn_backend``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.nvfp4 import GROUP, dequant_rows, fake_quant_a4


def _ffn_kernel(offs_ref, gmap_ref, x_ref, gsc_ref,
                wgp_ref, wgs_ref, wup_ref, wus_ref, wdp_ref, wds_ref,
                o_ref, acc_ref, *, group, act, n_g, n_f, block_m):
    i = pl.program_id(0)
    g = pl.program_id(1)
    f = pl.program_id(2)

    @pl.when((g == 0) & (f == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    r0 = offs_ref[g]
    r1 = offs_ref[g + 1]
    row0 = i * block_m

    # Skip empty slots and token blocks with no rows in this slot.
    @pl.when((r1 > r0) & (row0 < r1) & (row0 + block_m > r0))
    def _compute():
        xq = x_ref[...]                                       # [bm, D]
        dtype = xq.dtype
        rows = row0 + jax.lax.broadcasted_iota(
            jnp.int32, (block_m, 1), 0)
        mask = (rows >= r0) & (rows < r1)
        xq = jnp.where(mask, xq, jnp.zeros_like(xq))

        wg = dequant_rows(wgp_ref[0], wgs_ref[0],             # [D, bf]
                          gsc_ref[0, 0]).astype(dtype)
        wu = dequant_rows(wup_ref[0], wus_ref[0],
                          gsc_ref[0, 1]).astype(dtype)
        # gate, up and h stay f32 up to the a4 of h, as in the oracle
        gate = jnp.dot(xq, wg, preferred_element_type=jnp.float32)
        up = jnp.dot(xq, wu, preferred_element_type=jnp.float32)
        h = act(gate) * up                                    # [bm, bf]
        # a4 groups run along d_ff: transpose so they split sublanes
        hq = fake_quant_a4(h.T, group, axis=-2).T.astype(dtype)

        wd = dequant_rows(wdp_ref[0], wds_ref[0],             # [bf, D]
                          gsc_ref[0, 2]).astype(dtype)
        acc_ref[...] += jnp.dot(hq, wd, preferred_element_type=jnp.float32)

    @pl.when((g == n_g - 1) & (f == n_f - 1))
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _pick_block_f(f: int) -> int:
    """Largest lane-aligned divisor of d_ff from (256, 128), else
    all of d_ff (a full-dimension block is always legal)."""
    for cand in (256, 128):
        if f % cand == 0:
            return cand
    return f


@functools.partial(jax.jit,
                   static_argnames=("group", "act", "block_m",
                                    "interpret", "out_dtype"))
def grouped_fp4_ffn_kernel(xs: jax.Array, gs: jax.Array,
                           gate_packed: jax.Array, gate_scales: jax.Array,
                           up_packed: jax.Array, up_scales: jax.Array,
                           down_packed: jax.Array, down_scales: jax.Array,
                           global_scales: jax.Array, *,
                           group: int = GROUP, act=jax.nn.silu,
                           block_m: int = 128, interpret: bool = False,
                           out_dtype=None) -> jax.Array:
    """Fused grouped FP4 SwiGLU FFN: ``xs [M, D]`` sorted by slot → ``[M, D]``.

    ``gs [G]`` int32 token counts per slot (``sum(gs) == M``);
    gate/up quantized along D (``packed [G, D/2, F]``, ``scales
    [G, D/group, F]``), down along F (``[G, F/2, D]``, ``[G, F/group, D]``);
    ``global_scales [3]`` f32 per-tensor scales (gate, up, down).
    Rows are padded to a multiple of ``block_m`` internally — callers pass
    real ``M``.
    """
    m, d = xs.shape
    n_groups = gs.shape[0]
    f = gate_packed.shape[-1]
    assert d % (2 * group) == 0 and f % (2 * group) == 0, (d, f)

    out_dtype = out_dtype or xs.dtype
    # a4 of the input is row-local: once over all rows, as the oracle does
    xs = fake_quant_a4(xs, group).astype(xs.dtype)
    mp = -(-m // block_m) * block_m
    if mp != m:
        xs = jnp.pad(xs, ((0, mp - m), (0, 0)))
    block_f = _pick_block_f(f)
    grid = (mp // block_m, n_groups, f // block_f)

    gs = gs.astype(jnp.int32)
    offs = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(gs)])
    # empty-slot skip map: index of the last non-empty slot at or before g
    # (0 if none yet) — consecutive grid steps then reuse the same weight
    # block and the DMA is elided.
    nz = gs > 0
    gmap = jnp.maximum(
        jax.lax.cummax(jnp.where(nz, jnp.arange(n_groups, dtype=jnp.int32),
                                 -1)), 0)

    kernel = functools.partial(_ffn_kernel, group=group, act=act,
                               n_g=n_groups, n_f=grid[2], block_m=block_m)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_m, d), lambda i, g, f, offs, gmap: (i, 0)),
                pl.BlockSpec((1, 3), lambda i, g, f, offs, gmap: (0, 0)),
                pl.BlockSpec((1, d // 2, block_f),
                             lambda i, g, f, offs, gmap: (gmap[g], 0, f)),
                pl.BlockSpec((1, d // group, block_f),
                             lambda i, g, f, offs, gmap: (gmap[g], 0, f)),
                pl.BlockSpec((1, d // 2, block_f),
                             lambda i, g, f, offs, gmap: (gmap[g], 0, f)),
                pl.BlockSpec((1, d // group, block_f),
                             lambda i, g, f, offs, gmap: (gmap[g], 0, f)),
                pl.BlockSpec((1, block_f // 2, d),
                             lambda i, g, f, offs, gmap: (gmap[g], f, 0)),
                pl.BlockSpec((1, block_f // group, d),
                             lambda i, g, f, offs, gmap: (gmap[g], f, 0)),
            ],
            out_specs=pl.BlockSpec((block_m, d),
                                   lambda i, g, f, offs, gmap: (i, 0)),
            scratch_shapes=[pltpu.VMEM((block_m, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((mp, d), out_dtype),
        interpret=interpret,
    )(offs, gmap, xs,
      jnp.asarray(global_scales, jnp.float32).reshape(1, 3),
      gate_packed, gate_scales, up_packed, up_scales,
      down_packed, down_scales)
    return out[:m]
