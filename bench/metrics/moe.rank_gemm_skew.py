"""How far the slowest EP rank's expert GEMMs lag the mean, in per cent
(100 = every rank alike): per chip, the device time of the expert GEMMs
per ``chunk_step`` execution (``moe.expert_gemm_ms``'s rule: leaf ops
under ``expert_gemm`` and XLA's ``ragged-dot`` kernel), the largest over
the chips over their mean.  The imbalance ReaLB is meant to shrink.  None
on one chip."""
from harness import trace

BF16_GEMM = ("ragged-dot",)


def read(run):
    if run.trace is None or len(run.trace.devices) < 2:
        return None
    per = []
    for dev in run.trace.devices.values():
        ex = trace.executions(dev, "jit_chunk_step")
        if not ex:
            return None
        per.append(trace.op_seconds(dev, "jit_chunk_step", "expert_gemm",
                                    names=BF16_GEMM) / len(ex))
    mean = sum(per) / len(per)
    return 100.0 * max(per) / mean if mean > 0.0 else None
