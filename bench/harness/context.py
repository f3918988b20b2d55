"""What a per-layer metric's reader gets: one run's window, step records,
reduced trace, the chip's peaks and the architecture's counts.  A reader
returns a number, or None where the run gave it nothing to read.

Device-trace readers time the lowest device id; under the identity
placement that is EP rank 0, so the counts held against its time are rank
0's: its experts, its routed rows and its FP4 flag (on one chip, the
whole layer)."""
from __future__ import annotations

from dataclasses import dataclass, field
from types import ModuleType
from typing import List, Optional

from harness import policy
from harness.serve import StepRecord, Window
from harness.stats import Track
from harness.trace import Device, Trace


@dataclass
class Run:
    arch: object                     # the architecture's sizes
    counts: ModuleType               # bench/archs/<arch>.py: its counts
    chips: int
    peaks: dict
    window: Window
    due: List[Track]                 # requests due in the window
    steps: List[StepRecord]          # every step program call
    iter_stats: list                 # the engine's IterStats
    policy_ranks: int                # the policy's EP group (virtual: 1 chip)
    ep: int = 1                      # physical EP ranks, one a chip
    trace: Optional[Trace] = None
    traced_steps: List[StepRecord] = field(default_factory=list)

    def device(self) -> Optional[Device]:
        if self.trace is None or not self.trace.devices:
            return None
        return self.trace.devices[min(self.trace.devices)]

    def fired(self, step: StepRecord) -> List[bool]:
        """Per MoE layer: did rank 0 run it in FP4."""
        flags = policy.rank_flags(step.aux["moe_stats"], step.m_in, self.ep)
        return [bool(f) for f in flags[:, 0]]

    def layer_rows(self, step: StepRecord) -> List[float]:
        """Per MoE layer: routed assignments of real tokens to rank 0's
        experts."""
        ms = step.aux["moe_stats"]
        per = ms.shape[-1] // self.ep
        return [float(ms[i, 0, :, :per].sum()) for i in range(ms.shape[0])]

    def layer_experts(self, step: StepRecord) -> List[int]:
        """Per MoE layer: rank 0's experts that received at least one
        token."""
        es = step.aux["expert_stats"]
        per = es.shape[-1] // self.ep
        return [int((es[i, 0, :per] > 0).sum()) for i in range(es.shape[0])]
