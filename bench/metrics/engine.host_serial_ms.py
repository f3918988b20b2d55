"""Device idle milliseconds per engine step that the host holds the chip
back: the traced window's device idle time (the complement of the union
of op intervals, after ``trace.align``) inside the program's
``host.engine.iter`` spans, over the number of those spans in the
window.  Idle outside the steps (waiting for requests) is left out.
None where the program writes no such spans."""
from harness import trace

ITER = "host.engine.iter"


def _clipped(run, label, lo, hi):
    return [(max(a, lo), min(b, hi)) for name, a, b in run.trace.host
            if name == label and b > lo and a < hi]


def _overlap(xs, ys):
    """Seconds in both of two sorted lists of disjoint intervals."""
    tot, i, j = 0.0, 0, 0
    while i < len(xs) and j < len(ys):
        tot += max(0.0, min(xs[i][1], ys[j][1]) - max(xs[i][0], ys[j][0]))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return tot


def idle_ms_per_step(run, label):
    """Device idle ms inside ``label`` spans per ``host.engine.iter`` span
    of the traced window; None without a trace or without iter spans."""
    dev = run.device()
    if dev is None:
        return None
    lo, hi = run.trace.window
    steps = _clipped(run, ITER, lo, hi)
    if not steps:
        return None
    spans = trace.union(_clipped(run, label, lo, hi))
    busy = trace.union([(o.start, o.end) for o in dev.ops])
    idle = sum(b - a for a, b in spans) - _overlap(spans, busy)
    return 1e3 * idle / len(steps)


def read(run):
    return idle_ms_per_step(run, ITER)
