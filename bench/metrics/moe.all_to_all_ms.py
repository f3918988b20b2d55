"""Device milliseconds of the EP layer's all-to-alls per ``chunk_step``
execution, the mean over the chips: the leaf ops of ``chunk_step`` under
the ``dispatch`` and ``combine`` phases whose HLO name or name path is an
all-to-all (the tokens and their expert ids out, the results back).
None on one chip, where the layer has no exchange."""
from harness import trace

PHASES = ("dispatch", "combine")


def is_all_to_all(op) -> bool:
    return op.name.startswith("all-to-all") \
        or trace.label(op).startswith(("all_to_all", "all-to-all"))


def seconds(dev) -> float:
    tot = 0.0
    for o in dev.ops:
        if o.container or o.module != "jit_chunk_step":
            continue
        parts = trace.scope_of(o.path).split("/")
        if is_all_to_all(o) and any(p in parts for p in PHASES):
            tot += o.end - o.start
    return tot


def read(run):
    if run.trace is None or len(run.trace.devices) < 2:
        return None
    per = []
    for dev in run.trace.devices.values():
        ex = trace.executions(dev, "jit_chunk_step")
        if not ex:
            return None
        per.append(seconds(dev) / len(ex))
    ms = 1e3 * sum(per) / len(per)
    return ms if ms > 0.0 else None
