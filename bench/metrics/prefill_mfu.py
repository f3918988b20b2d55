"""Model FLOP utilization of prefill, in per cent: the model operations of
the real prompt tokens the traced window prefilled (two per active
non-embedding parameter plus attention over each token's own context;
padding counts nothing) over the summed ``chunk_step`` device time times
the chips' bf16 peak."""
from harness import flops, trace


def read(run):
    dev = run.device()
    if dev is None or run.peaks is None:
        return None
    secs = sum(trace.executions(dev, "jit_chunk_step"))
    work = sum(flops.prefill_flops(run.arch, start, take)
               for s in run.traced_steps if s.kind == "chunk"
               for _, start, take in s.rows)
    if secs <= 0.0 or work <= 0.0:
        return None
    return 100.0 * work / (secs * run.peaks["flops"] * run.chips)
