"""Roofline share of the BF16 -> FP4 quantize kernel in the traced window:
the bytes of every FP4 layer's three expert stacks read in bf16 and
written as codes and scales, at HBM bandwidth, over the kernel's summed
device time (Pallas calls under ``quantize_fp4`` in ``chunk_step``)."""
from harness import flops, trace


def read(run):
    dev = run.device()
    if dev is None or run.peaks is None:
        return None
    secs = trace.op_seconds(dev, "jit_chunk_step", "quantize_fp4",
                            custom_call=True)
    nbytes = sum(flops.quantize_work(run.arch)
                 for s in run.traced_steps if s.kind == "chunk"
                 for fired in run.fired(s) if fired)
    if secs <= 0.0 or nbytes <= 0.0:
        return None
    return flops.roofline_share(0.0, nbytes, secs, run.peaks)
