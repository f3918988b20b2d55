"""Device milliseconds of the EP MoE layer's expert GEMMs per
``chunk_step`` execution (all MoE layers of the step): the leaf ops under
the ``expert_gemm`` scope (the grouped FP4 kernel and what surrounds it),
and XLA's grouped-GEMM kernel for the BF16 path (``lax.ragged_dot``,
``ragged-dot-*`` in the trace), whose ops carry no name path."""
from harness import trace

BF16_GEMM = ("ragged-dot",)


def read(run):
    dev = run.device()
    ex = trace.executions(dev, "jit_chunk_step") if dev else []
    if not ex:
        return None
    return 1e3 * trace.op_seconds(dev, "jit_chunk_step", "expert_gemm",
                                  names=BF16_GEMM) / len(ex)
