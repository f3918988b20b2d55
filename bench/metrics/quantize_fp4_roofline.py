"""Roofline share of the BF16 -> FP4 quantize kernel in the traced window,
on the timed chip (EP rank 0): the bytes of rank 0's three expert stacks
(every expert on one chip) read in bf16 and written as codes and scales
in each layer it ran in FP4, at HBM bandwidth, over the kernel's summed
device time (Pallas calls under ``quantize_fp4`` in ``chunk_step``)."""
from harness import flops, trace


def read(run):
    dev = run.device()
    if dev is None or run.peaks is None:
        return None
    secs = trace.op_seconds(dev, "jit_chunk_step", "quantize_fp4",
                            custom_call=True)
    held = run.arch.n_experts // run.ep
    nbytes = sum(run.counts.quantize_work(run.arch, held)
                 for s in run.traced_steps if s.kind == "chunk"
                 for fired in run.fired(s) if fired)
    if secs <= 0.0 or nbytes <= 0.0:
        return None
    return flops.roofline_share(0.0, nbytes, secs, run.peaks)
