"""The chip peaks that operation and byte counts are held against, and
what every architecture counts alike.  Each architecture's own counts
(``active_params``, ``prefill_flops``, ``fp4_ffn_work``, ...) live in its
module, ``bench/archs/<arch>.py``.

FP4 is counted as the engine stores it: 4-bit codes plus one float32 scale
per group of 16, 6 bits a weight.
"""
from __future__ import annotations

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


def roofline_share(flops: float, nbytes: float, seconds: float,
                   pk: dict) -> float:
    """Per cent of the kernel's time the chip's bound would need."""
    return 100.0 * max(flops / pk["flops"], nbytes / pk["hbm"]) / seconds
