"""Per cent of the traced window in which no operation ran on the device
(one minus the union of the XLA op intervals over the window), averaged
over the chips."""
from harness import trace


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    lo, hi = run.trace.window
    if hi <= lo:
        return None
    busy = [trace.busy_seconds(d) for d in run.trace.devices.values()]
    return 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo))
