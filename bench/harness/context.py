"""What a per-layer metric's reader gets: one run's window, step records,
reduced trace and the chip's peaks.  A reader returns a number, or None
where the run gave it nothing to read."""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional

from harness import policy
from harness.arch import Arch
from harness.serve import StepRecord, Window
from harness.stats import Track
from harness.trace import Device, Trace


@dataclass
class Run:
    arch: Arch
    chips: int
    peaks: dict
    window: Window
    due: List[Track]                 # requests due in the window
    steps: List[StepRecord]          # every step program call
    iter_stats: list                 # the engine's IterStats
    virtual_ep: int
    trace: Optional[Trace] = None
    traced_steps: List[StepRecord] = field(default_factory=list)

    def device(self) -> Optional[Device]:
        if self.trace is None or not self.trace.devices:
            return None
        return self.trace.devices[min(self.trace.devices)]

    @staticmethod
    def fired(step: StepRecord) -> List[bool]:
        """Per MoE layer: did this step run it in FP4."""
        return policy.layer_flags(step.aux["moe_stats"], step.m_in)[0]

    @staticmethod
    def layer_rows(step: StepRecord) -> List[float]:
        """Per MoE layer: routed assignments of real tokens."""
        ms = step.aux["moe_stats"]
        return [float(ms[i, 0].sum()) for i in range(ms.shape[0])]

    @staticmethod
    def layer_experts(step: StepRecord) -> List[int]:
        """Per MoE layer: experts that received at least one token."""
        es = step.aux["expert_stats"]
        return [int((es[i, 0] > 0).sum()) for i in range(es.shape[0])]
