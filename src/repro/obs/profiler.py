"""Hot-loop profiler: per-phase time attribution + costmodel drift.

Three jobs, one object:

1. **Phase attribution.**  Every recorded engine iteration feeds
   :meth:`Profiler.observe_iter` with the realized routing stats and the
   iteration's measured forward seconds (virtual-clock charge on the
   bench, wall seconds on hardware).  The :class:`~repro.obs.ledger.
   FlopByteLedger` turns the stats into analytic per-phase seconds; the
   measured iteration time is attributed to phases proportionally to
   those predictions.  The attribution is exhaustive by construction —
   ``sum(phase seconds) == forward seconds`` is the reconciliation
   invariant ``benchmarks/profile_report.py`` enforces (the same
   accounting-integrity discipline as ``trace_report.py``).  Real
   per-phase device times come from a profiler trace, whose operations
   carry the ``jax.named_scope`` phases (``bench/run.py --trace 1``).
2. **MFU / roofline gauges.**  Cumulative ledger flops over cumulative
   measured forward seconds against the single-sourced
   :data:`repro.configs.hw.PEAK_BF16`, plus the compute-vs-memory-vs-
   collective roofline fraction — pushed into the shared
   :class:`~repro.obs.metrics.MetricsRegistry` (``mfu``,
   ``roofline_fraction``) so ``Telemetry.summary()`` and every arm's
   ``BENCH_serve.json`` carry them.
3. **Costmodel drift.**  ``time_scale()`` is the EWMA of measured-over-
   predicted iteration seconds — the calibration factor the replan cost
   gates (``ReplanCostGate.time_scale``) multiply predicted savings by.
   Per-phase drift ratios (cumulative measured / predicted) land in the
   ``costmodel_drift`` gauge.

Disabled profiling follows the null-object discipline:
:data:`NULL_PROFILER` is a shared no-op singleton — no stats
conversion, no clock reads, bitwise-identical engine outputs (pinned by
``tests/test_profiler.py``).
"""
from __future__ import annotations

import json
from typing import Any, Callable, Dict, Optional

import numpy as np

from repro.configs.hw import PEAK_BF16
from repro.obs.ledger import PHASES, FlopByteLedger, IterLedger

PROFILE_SCHEMA = "repro.profile.v1"


class NullProfiler:
    """Shared no-op: the engine's default when no profiler is wired."""
    enabled = False

    def observe_iter(self, *a, **kw) -> None:
        pass

    def time_scale(self) -> float:
        return 1.0

    def mfu(self) -> float:
        return 0.0

    def span_args(self) -> Dict[str, Any]:
        return {}


NULL_PROFILER = NullProfiler()


class Profiler:
    """Per-iteration phase/FLOP/drift accounting around a ledger.

    ``registry`` (optional): a :class:`~repro.obs.metrics.MetricsRegistry`
    — pass the telemetry's so gauges surface in ``summary()``.
    ``clock`` is unused for attribution (the engine passes measured
    ``fwd_s`` explicitly) but stamped into written profiles.
    ``ewma_alpha`` smooths ``time_scale`` and per-phase drift.
    """
    enabled = True

    def __init__(self, ledger: FlopByteLedger,
                 registry=None, clock: Optional[Callable[[], float]] = None,
                 ewma_alpha: float = 0.25):
        self.ledger = ledger
        self.registry = registry
        self.clock = clock
        self.alpha = float(ewma_alpha)
        self.n_iters = 0
        self.fwd_s_total = 0.0
        self.model_flops_total = 0.0
        self.flops_total = 0.0
        # fractional bytes are correct here: FP4 weights price at 4.25
        # bits/elem (ledger BYTES_FP4 = 0.53125), so per-iter HBM totals
        # are analytic floats, not buffer sizes
        self.hbm_bytes_total = 0.0  # repro-lint: disable=RPL006
        self.ici_bytes_total = 0.0  # repro-lint: disable=RPL006
        self._meas_s = {ph: 0.0 for ph in PHASES}
        self._pred_s = {ph: 0.0 for ph in PHASES}
        self._scale_ewma: Optional[float] = None
        self.last: Optional[IterLedger] = None
        if registry is not None:
            self._g_mfu = registry.gauge(
                "mfu", "model flops / (measured s * peak bf16)")
            self._g_roof = registry.gauge(
                "roofline_fraction", "compute share of the roofline bound")
            self._g_scale = registry.gauge(
                "costmodel_time_scale", "EWMA measured/predicted iter s")
            self._g_drift = registry.gauge(
                "costmodel_drift", "cumulative measured/predicted per phase",
                labels=("phase",))
            self._c_flops = registry.counter(
                "model_flops", "cumulative useful model flops")
            self._c_phase = registry.counter(
                "phase_seconds", "measured seconds attributed per phase",
                labels=("phase",))
            self._c_pred = registry.counter(
                "phase_seconds_pred", "ledger-predicted seconds per phase",
                labels=("phase",))

    # --------------------------------------------------------------------
    def observe_iter(self, *, moe_stats, fp4_layers: float, tokens: float,
                     batch_tokens: float, fwd_s: float,
                     phase: str = "decode",
                     measured_phases: Optional[Dict[str, float]] = None
                     ) -> IterLedger:
        """Account one recorded iteration.

        ``fwd_s`` is the measured forward time (virtual-clock charge or
        wall seconds).  Without ``measured_phases`` the iteration time is
        attributed to phases by the ledger's predicted shares (exhaustive
        by construction); an instrumented caller may pass real per-phase
        seconds instead and they are rescaled to sum to ``fwd_s`` so the
        reconciliation invariant holds either way.
        """
        led = self.ledger.account(moe_stats, fp4_layers, tokens,
                                  batch_tokens)
        self.last = led
        self.n_iters += 1
        fwd_s = max(float(fwd_s), 0.0)
        self.fwd_s_total += fwd_s
        self.model_flops_total += led.model_flops
        self.flops_total += led.flops_total
        self.hbm_bytes_total += led.hbm_total
        self.ici_bytes_total += led.ici_total

        weights = dict(measured_phases) if measured_phases else led.pred_s
        wtot = sum(max(v, 0.0) for v in weights.values())
        for ph in PHASES:
            self._pred_s[ph] += led.pred_s[ph]
            share = (max(weights.get(ph, 0.0), 0.0) / wtot) if wtot > 0 \
                else 1.0 / len(PHASES)
            self._meas_s[ph] += fwd_s * share

        pred_total = led.pred_total
        if pred_total > 0 and fwd_s > 0:
            r = fwd_s / pred_total
            self._scale_ewma = r if self._scale_ewma is None else (
                self.alpha * r + (1.0 - self.alpha) * self._scale_ewma)

        if self.registry is not None:
            self._g_mfu.set(self.mfu())
            self._g_roof.set(self.roofline_fraction())
            self._g_scale.set(self.time_scale())
            self._c_flops.inc(led.model_flops)
            for ph in PHASES:
                self._c_phase.inc(fwd_s * (
                    (max(weights.get(ph, 0.0), 0.0) / wtot) if wtot > 0
                    else 1.0 / len(PHASES)), phase=ph)
                if led.pred_s[ph] > 0:
                    self._c_pred.inc(led.pred_s[ph], phase=ph)
                if self._pred_s[ph] > 0:
                    self._g_drift.set(
                        self._meas_s[ph] / self._pred_s[ph], phase=ph)
        return led

    # -- derived quantities ----------------------------------------------
    def mfu(self) -> float:
        if self.fwd_s_total <= 0:
            return 0.0
        return self.model_flops_total / (self.fwd_s_total * PEAK_BF16)

    def roofline_fraction(self) -> float:
        from repro.launch.roofline import roofline_terms
        if self.flops_total <= 0:
            return 0.0
        return roofline_terms(self.flops_total, self.hbm_bytes_total,
                              self.ici_bytes_total)["roofline_fraction"]

    def time_scale(self) -> float:
        """EWMA of measured/predicted iteration seconds (1.0 until the
        first observation) — the cost gates' savings-side calibration."""
        return 1.0 if self._scale_ewma is None else float(self._scale_ewma)

    def phase_seconds(self) -> Dict[str, float]:
        return dict(self._meas_s)

    def phase_seconds_pred(self) -> Dict[str, float]:
        return dict(self._pred_s)

    def drift(self) -> Dict[str, float]:
        """Cumulative measured/predicted ratio per phase (1.0 when the
        phase never carried predicted time)."""
        return {ph: (self._meas_s[ph] / self._pred_s[ph]
                     if self._pred_s[ph] > 0 else 1.0) for ph in PHASES}

    def span_args(self) -> Dict[str, Any]:
        """Per-iteration metadata for the engine's ``iter`` trace span."""
        if self.last is None:
            return {}
        return {"mfu": round(self.mfu(), 6),
                "model_flops": self.last.model_flops,
                "pred_s": round(self.last.pred_total, 9)}

    def summary(self) -> Dict[str, Any]:
        return {
            "n_iters": self.n_iters,
            "mfu": self.mfu(),
            "roofline_fraction": self.roofline_fraction(),
            "time_scale": self.time_scale(),
            "model_flops_total": self.model_flops_total,
            "flops_total": self.flops_total,
            "hbm_bytes_total": self.hbm_bytes_total,
            "ici_bytes_total": self.ici_bytes_total,
            "forward_s_total": self.fwd_s_total,
            "phase_seconds": self.phase_seconds(),
            "phase_seconds_pred": self.phase_seconds_pred(),
            "drift": self.drift(),
        }

    def write(self, path: str, metadata: Optional[Dict[str, Any]] = None
              ) -> Dict[str, Any]:
        """Write the profile JSON ``profile_report.py`` consumes."""
        doc = {
            "schema": PROFILE_SCHEMA,
            "metadata": dict(metadata or {}),
            "n_iters": self.n_iters,
            "phases": {ph: {"measured_s": self._meas_s[ph],
                            "predicted_s": self._pred_s[ph]}
                       for ph in PHASES},
            "totals": {
                "forward_s": self.fwd_s_total,
                "predicted_s": sum(self._pred_s.values()),
                "model_flops": self.model_flops_total,
                "flops": self.flops_total,
                "hbm_bytes": self.hbm_bytes_total,
                "ici_bytes": self.ici_bytes_total,
                "mfu": self.mfu(),
                "roofline_fraction": self.roofline_fraction(),
                "time_scale": self.time_scale(),
            },
            "drift": self.drift(),
        }
        with open(path, "w") as f:
            json.dump(doc, f, indent=1)
        return doc
