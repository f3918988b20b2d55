"""Operations and bytes the algorithm needs, from shapes and counted rows,
and the chip peaks they are held against.

The shape arithmetic follows ``repro.obs.ledger`` (two operations per
multiply-add, SwiGLU = three matrices), with FP4 counted as the engine
stores it: 4-bit codes plus one float32 scale per group of 16, 6 bits a
weight.  Padding rows, capacity padding and the pad slot count nothing.
"""
from __future__ import annotations

from harness.arch import Arch

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm": 819e9},
    "TPU v5e": {"flops": 197e12, "hbm": 819e9},
}
FP4_BYTES = 0.5 + 4.0 / 16.0          # code nibble + f32 scale per 16
BF16 = 2.0


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


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


def quantize_work(a: Arch) -> float:
    """Bytes of one layer's BF16 -> FP4 transformation: the three expert
    stacks read in bf16, their codes and scales written."""
    return 3.0 * a.n_experts * a.d_model * a.d_expert * (BF16 + FP4_BYTES)


def roofline_share(flops: float, nbytes: float, seconds: float,
                   pk: dict) -> float:
    """Per cent of the kernel's time the chip's bound would need."""
    return 100.0 * max(flops / pk["flops"], nbytes / pk["hbm"]) / seconds
