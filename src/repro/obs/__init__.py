"""repro.obs — observability for the serving stack.

Three cooperating pieces, all host-side and dependency-light:

- :mod:`repro.obs.trace` — one nested-span API over engine / manager /
  executor / elastic activity per iteration.  Every span is a
  ``jax.profiler`` annotation (``host.engine.<name>``), so a profiler
  trace puts the host stages on the device trace's clock; a recording
  :class:`~repro.obs.trace.Tracer` also exports Chrome-trace
  (Perfetto-loadable) JSON.  The default tracer records nothing, reads
  no clock and builds no span args unless the profiler collects:
  bitwise-identical engine outputs.
- :mod:`repro.obs.metrics` — a typed metrics registry (counters /
  gauges / histograms with labels) that ``serving.telemetry`` is built
  on, plus the per-layer per-rank expert-load heatmap recorder and the
  predicted-vs-realized peak-rank-load accuracy tracker.
- :mod:`repro.obs.audit` — the replan-decision audit log: every
  ``ReplanDiscipline`` verdict (cadence, warmup, min-gain, churn
  budget, cost gate, must-plan) as one structured event, queryable
  after a run.
- :mod:`repro.obs.ledger` / :mod:`repro.obs.profiler` — the hot-loop
  FLOP/byte ledger (exact per-layer per-rank flops and HBM/ICI bytes
  from the realized routing stats) and the per-phase profiler feeding
  ``mfu`` / ``roofline_fraction`` / costmodel-drift gauges into the
  registry; disabled profiling is a shared no-op singleton.
"""
from repro.obs.audit import ReplanAudit
from repro.obs.ledger import PHASES, FlopByteLedger, IterLedger
from repro.obs.metrics import (Counter, Gauge, HeatmapRecorder, Histogram,
                               MetricsRegistry, PredictionTracker)
from repro.obs.profiler import NULL_PROFILER, NullProfiler, Profiler
from repro.obs.trace import (DEFAULT_TRACER, ProfilerTracer, Tracer,
                             validate_chrome_trace)

__all__ = [
    "Counter", "DEFAULT_TRACER", "FlopByteLedger", "Gauge",
    "HeatmapRecorder", "Histogram", "IterLedger", "MetricsRegistry",
    "NULL_PROFILER", "NullProfiler", "PHASES", "PredictionTracker",
    "Profiler", "ProfilerTracer", "ReplanAudit", "Tracer",
    "validate_chrome_trace",
]
