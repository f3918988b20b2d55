"""Reduction of a profiler trace (``.xplane.pb``) to device intervals.

Read with :mod:`harness.xspace`: each chip is a plane named
``/device:TPU:<n>``; its ``XLA Modules`` line holds one event per program
execution (``jit_chunk_step``, ``jit_decode``, ...) and its ``XLA Ops``
line one event per operation, whose metadata's ``tf_op`` stat is the
operation's JAX name path (``jit(chunk_step)/.../moe/expert_gemm/...``),
which carries the ``jax.named_scope`` phases, and whose ``hlo_category``
is ``custom-call`` for the Pallas kernels.  Host threads are the ``/host:CPU`` plane,
where the harness's ``TraceAnnotation`` spans label what the host was
doing in each device idle gap.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from harness import xspace

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
SCOPES = ("attention", "ffn", "moe", "route", "weight_gather",
          "quantize_fp4", "dispatch", "expert_gemm", "combine")
HOST_LABEL = re.compile(r"^(host|harness)\.")
WINDOW_SPAN = "harness.traced"        # spans the whole traced interval
SHORT_GAP = 1e-4      # idle gaps shorter than this lie between ops of a program
LAUNCH = ("host.chunk_dispatch", "host.decode_dispatch")  # host program calls


@dataclass
class Op:
    start: float          # seconds on the trace's clock
    end: float
    name: str             # HLO operation name
    path: str             # JAX name path (tf_op), "" when absent
    module: str = ""      # the program execution that holds it
    kernel: bool = False  # a custom call: the Pallas kernels
    container: bool = False   # holds other ops (a while loop, a cond)


@dataclass
class Device:
    ops: List[Op] = field(default_factory=list)
    modules: List[Tuple[str, float, float]] = field(default_factory=list)


@dataclass
class Trace:
    devices: Dict[int, Device]
    host: List[Tuple[str, float, float]]   # (label, start, end)

    @property
    def window(self) -> Tuple[float, float]:
        """The traced interval on the trace's clock."""
        spans = [(a, b) for name, a, b in self.host if name == WINDOW_SPAN]
        if spans:
            return spans[0]
        ends = [x for d in self.devices.values() for o in d.ops
                for x in (o.start, o.end)]
        return (min(ends), max(ends)) if ends else (0.0, 0.0)


def module_name(name: str) -> str:
    """``jit_chunk_step(123)`` -> ``jit_chunk_step``."""
    return name.split("(")[0].strip()


def load(path: str, launch: Tuple[str, ...] = LAUNCH) -> Trace:
    """The trace at ``path``, device times moved onto the host clock (see
    :func:`align`)."""
    devices: Dict[int, Device] = {}
    host: List[Tuple[str, float, float]] = []
    for plane in xspace.load(path):
        meta = plane.event_metadata
        m = DEVICE_PLANE.match(plane.name)
        is_host = plane.name.startswith("/host:")
        if not (m or is_host):
            continue
        dev = devices.setdefault(int(m.group(1)), Device()) if m else None
        for line in plane.lines:
            base = line.timestamp_ns * 1000
            for ev in line.events:
                name, disp, st = meta.get(ev.metadata_id, ("", "", {}))
                s = (base + ev.offset_ps) * 1e-12
                e = s + ev.duration_ps * 1e-12
                if dev is not None and line.name == "XLA Modules":
                    dev.modules.append((module_name(name), s, e))
                elif dev is not None and line.name == "XLA Ops":
                    dev.ops.append(Op(s, e, disp or name,
                                      str(st.get("tf_op", "")),
                                      kernel=st.get("hlo_category")
                                      == "custom-call"))
                elif is_host and HOST_LABEL.match(name):
                    host.append((name, s, e))
    for dev in devices.values():
        _assign_modules(dev)
        align(dev, [a for name, a, _ in host if name in launch])
    return Trace(devices, host)


def align(dev: Device, launches: List[float]) -> float:
    """Shift a device's times onto the host clock.  The profiler's device
    clock can sit a millisecond or more off the host's (a TPU program was
    seen to start before the host call that launched it); the k-th program
    execution is paired with the k-th host launch span and the median lag
    is taken out.  Returns the shift (0 when the counts differ)."""
    starts = [a for _, a, _ in dev.modules]
    if not starts or len(starts) != len(launches):
        return 0.0
    lags = sorted(d - h for d, h in zip(starts, sorted(launches)))
    shift = lags[len(lags) // 2]
    dev.modules = [(n, a - shift, b - shift) for n, a, b in dev.modules]
    for o in dev.ops:
        o.start -= shift
        o.end -= shift
    return shift


def _assign_modules(dev: Device) -> None:
    mods = sorted(dev.modules, key=lambda m: m[1])
    dev.modules = mods
    ops = sorted(dev.ops, key=lambda o: (o.start, -o.end))
    dev.ops = ops
    # ops of one core run one after another; an op that another starts
    # inside of is a loop or conditional holding them
    for a, b in zip(ops, ops[1:]):
        a.container = b.start < a.end - 1e-12
    j = 0
    for op in ops:
        while j < len(mods) and mods[j][2] < op.start:
            j += 1
        if j < len(mods) and mods[j][1] <= op.start <= mods[j][2]:
            op.module = mods[j][0]


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_seconds(dev: Device) -> float:
    return sum(e - s for s, e in union([(o.start, o.end) for o in dev.ops]))


def _parts(path: str) -> List[str]:
    return [p.rstrip(":") for p in path.split("/")]


def scope_of(path: str) -> str:
    """The named-scope phases along a JAX name path, joined by ``/``."""
    parts = [p for p in _parts(path) if p in SCOPES]
    return "/".join(parts)


def executions(dev: Device, module: str) -> List[float]:
    """Seconds of each execution of ``module``."""
    return [e - s for name, s, e in dev.modules if name == module]


def label(o: Op) -> str:
    """The op's last name-path part, or, where it has no path, its HLO
    name without the instance number (``ragged-dot-none.3`` ->
    ``ragged-dot-none``)."""
    return _parts(o.path)[-1] if o.path else o.name.split(".")[0]


def op_seconds(dev: Device, module: str, scope: str,
               custom_call: Optional[bool] = None,
               names: Tuple[str, ...] = ()) -> float:
    """Device seconds of the leaf ops of ``module`` under ``scope`` (a
    phase name anywhere on the path), and of those whose :func:`label`
    starts with one of ``names`` (ops whose path lost the scope);
    ``custom_call`` keeps only (True) or drops (False) Pallas kernel
    calls."""
    tot = 0.0
    for o in dev.ops:
        if o.container or o.module != module or not (
                scope in _parts(o.path) or label(o).startswith(names)):
            continue
        if custom_call is not None and is_kernel(o) != custom_call:
            continue
        tot += o.end - o.start
    return tot


def is_kernel(o: Op) -> bool:
    """A Pallas kernel: an op of HLO category ``custom-call``."""
    return o.kernel


def top_ops(dev: Device, n: int = 10) -> List[list]:
    """Leaf-op device seconds by program and phase (or the op's own name
    outside the phases), the largest ``n``."""
    tot: Dict[str, float] = defaultdict(float)
    for o in dev.ops:
        if o.container:
            continue
        key = (o.module or "?") + ":" + (scope_of(o.path)
                                         or "other/" + label(o))
        if is_kernel(o):
            key += ":kernel"
        tot[key] += o.end - o.start
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])
            ][:n]


def idle_gaps(dev: Device, host: List[Tuple[str, float, float]],
              lo: float, hi: float, n: int = 10) -> List[list]:
    """Device idle seconds within ``[lo, hi]``, by the innermost labelled
    host span around each gap's midpoint (gaps under ``SHORT_GAP`` count
    as ``device.between_ops``); the largest ``n`` labels."""
    busy = union([(o.start, o.end) for o in dev.ops])
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    tot: Dict[str, float] = defaultdict(float)
    spans = sorted((h for h in host if h[0] != WINDOW_SPAN),
                   key=lambda h: h[2] - h[1])
    for s, e in zip(edges[0::2], edges[1::2]):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if e - s < SHORT_GAP:
            tot["device.between_ops"] += e - s
            continue
        mid = 0.5 * (s + e)
        label = next((name for name, a, b in spans if a <= mid <= b),
                     "host.unlabelled")
        tot[label] += e - s
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])
            ][:n]


def describe(t: Trace) -> str:
    """One line on what the trace holds (devices, programs, ops, spans)."""
    parts = []
    for n, dev in sorted(t.devices.items()):
        mods: Dict[str, int] = defaultdict(int)
        for name, _, _ in dev.modules:
            mods[name] += 1
        parts.append(
            f"TPU:{n} modules {dict(mods)} ops {len(dev.ops)} with path "
            f"{sum(1 for o in dev.ops if o.path)} kernels "
            f"{sum(1 for o in dev.ops if is_kernel(o))} first op at "
            f"{dev.ops[0].start if dev.ops else None}")
    first_host = min((a for _, a, _ in t.host), default=None)
    return "; ".join(parts) + f"; host spans {len(t.host)} from {first_host}"
