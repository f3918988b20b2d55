"""Roofline share of the grouped FP4 expert FFN kernel in the traced
window, on the timed chip (EP rank 0): the least time the chip needs for
the work (the rows routed to rank 0's experts in every MoE layer it ran in
FP4 times 2*3*D*F operations; those layers' non-empty experts of rank 0
read once as codes and scales, plus the rows in and out) over the
kernel's summed device time (Pallas calls under ``expert_gemm`` in
``chunk_step``).  On one chip rank 0 holds every expert."""
from harness import flops, trace


def read(run):
    dev = run.device()
    if dev is None or run.peaks is None:
        return None
    secs = trace.op_seconds(dev, "jit_chunk_step", "expert_gemm",
                            custom_call=True)
    ops = nbytes = 0.0
    for s in run.traced_steps:
        if s.kind != "chunk":
            continue
        for fired, rows, experts in zip(run.fired(s), run.layer_rows(s),
                                        run.layer_experts(s)):
            if fired:
                f, b = run.counts.fp4_ffn_work(run.arch, rows, experts)
                ops += f
                nbytes += b
    if secs <= 0.0 or ops <= 0.0:
        return None
    return flops.roofline_share(ops, nbytes, secs, run.peaks)
