"""Serving entry points for the Pallas kernels + the FP4 FFN backend switch.

The serving hot loop (``repro.core.ep_moe``) picks its FP4 expert-FFN
implementation through :func:`ffn_backend`.  The platform decides:

* ``"pallas"``    — the Pallas kernels, compiled natively (on TPU);
* ``"jnp"``       — the dequantize + ``ragged_dot`` jnp oracle (elsewhere:
  fast enough to serve, numerically the reference).

Tests may pin a backend with :func:`set_ffn_backend`, including
``"interpret"`` — the same kernels under the Pallas interpreter, for
oracle parity on CPU.  The choice is read at *trace* time: already-compiled
functions keep the backend they were traced with.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.core.quant import QTensor, global_scale_for
from repro.kernels.grouped_fp4_ffn import grouped_fp4_ffn_kernel
from repro.kernels.quantize_fp4 import quantize_fp4_kernel

FFN_BACKENDS = ("pallas", "interpret", "jnp")
_ffn_backend_override: Optional[str] = None


def ffn_backend() -> str:
    """Resolve the FP4 expert-FFN backend for the serving hot loop."""
    if _ffn_backend_override is not None:
        return _ffn_backend_override
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


def set_ffn_backend(name: Optional[str]) -> str:
    """Override the backend ("pallas" | "interpret" | "jnp"); ``None`` or
    ``"auto"`` restores the platform default.  Returns the active backend.
    Takes effect for functions traced *after* the call."""
    global _ffn_backend_override
    if name is None or name == "auto":
        _ffn_backend_override = None
    else:
        if name not in FFN_BACKENDS:
            raise ValueError(f"unknown ffn backend {name!r}; "
                             f"expected one of {FFN_BACKENDS} or 'auto'")
        _ffn_backend_override = name
    return ffn_backend()


def ffn_fused() -> bool:
    """True when the hot loop runs the fused grouped kernel (either mode),
    i.e. FP4 weights stream packed and ``h`` stays in VMEM — the ledger /
    costmodel should then drop the BF16 dequant HBM round-trip."""
    return ffn_backend() != "jnp"


def _interpret(interpret: bool | None) -> bool:
    return (ffn_backend() != "pallas") if interpret is None else interpret


def quantize_experts_fp4(w: jax.Array, *, group: int = 16, pad: int = 0,
                         interpret: bool | None = None) -> QTensor:
    """Quantize a ``[G, K, N]`` expert weight stack along K via the Pallas
    kernel.  Bitwise-identical to ``quant.quantize_fp4`` (same global
    scale over the whole stack, same per-group recipe); ``pad`` trailing
    experts repeat the first ``pad``."""
    gscale = global_scale_for(w)
    packed, scales = quantize_fp4_kernel(w, gscale, group=group, pad=pad,
                                         interpret=_interpret(interpret))
    return QTensor(packed, scales, gscale)


def grouped_fp4_ffn(xs: jax.Array, gs: jax.Array,
                    wq: Dict[str, QTensor], *, group: int = 16,
                    act=jax.nn.silu,
                    interpret: bool | None = None) -> jax.Array:
    """Fused grouped FP4 SwiGLU FFN over slot-sorted tokens (see
    ``repro.kernels.grouped_fp4_ffn``).  ``wq`` holds ``w_gate``/``w_up``
    ``[G, D, F]`` and ``w_down`` ``[G, F, D]``, each quantized along its
    contraction axis, exactly as the EP layer's FP4 branch produces them."""
    qg, qu, qd = wq["w_gate"], wq["w_up"], wq["w_down"]
    gscales = jnp.stack([
        jnp.asarray(qg.global_scale, jnp.float32).reshape(()),
        jnp.asarray(qu.global_scale, jnp.float32).reshape(()),
        jnp.asarray(qd.global_scale, jnp.float32).reshape(())])
    return grouped_fp4_ffn_kernel(
        xs, gs, qg.packed, qg.scales, qu.packed, qu.scales,
        qd.packed, qd.scales, gscales, group=group, act=act,
        interpret=_interpret(interpret))
