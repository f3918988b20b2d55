"""Mean device milliseconds of one ``chunk_step`` execution (the batched
prefill chunk), from the trace's XLA Modules line."""
from harness import trace


def read(run):
    dev = run.device()
    ex = trace.executions(dev, "jit_chunk_step") if dev else []
    return 1e3 * sum(ex) / len(ex) if ex else None
