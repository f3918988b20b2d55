"""Mean device milliseconds of one ``decode`` execution, from the trace's
XLA Modules line."""
from harness import trace


def read(run):
    dev = run.device()
    ex = trace.executions(dev, "jit_decode") if dev else []
    return 1e3 * sum(ex) / len(ex) if ex else None
