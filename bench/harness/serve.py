"""The measured window: offers a mix's requests to ``Engine`` on the wall
clock and stamps what comes back.

``Engine.submit`` gets each request at its due time with ``arrival_time``
set to it; the loop calls ``Engine.step()`` until the window closes and
sleeps only while the engine is idle.  A token is stamped after the
``step()`` that produced it (every step ends in the engine's sampling
sync).  The :class:`Recorder` keeps, per step program call, what the
reference and the readers need: the requests and positions it served,
the AIMD state it started from, its routing counters and its dropped
share (device arrays, read after the window so the window gains no host
sync).
"""
from __future__ import annotations

import time
from collections import deque
from contextlib import nullcontext
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np

from harness.stats import Track

AUX_KEYS = ("moe_stats", "expert_stats", "fp4_ranks", "drop_frac")


@dataclass
class StepRecord:
    kind: str                        # "chunk" | "decode"
    t_call: float                    # host clock at the call
    rows: List[tuple]                # chunk: (uid, start, take); decode: (uid, pos)
    m_in: object                     # AIMD state the step started from
    aux: Dict[str, object]           # routing counters (device, then host)


class Recorder:
    """Wraps the engine's step programs and host stages; ``annotate`` puts
    a profiler span around each host stage for the trace's idle gaps."""

    def __init__(self, eng, annotate: bool = False):
        import jax
        self.eng = eng
        self.steps: List[StepRecord] = []
        self._plan: List[tuple] = []
        self._span = (lambda name: jax.profiler.TraceAnnotation(name)) \
            if annotate else (lambda name: nullcontext())
        plan, chunk, decode = eng._plan_chunks, eng._chunk, eng._decode
        sample, record = eng._sample, eng._record

        def plan_chunks():
            with self._span("host.plan_chunks"):
                out = plan()
            act = eng.scheduler.active
            self._plan = [(act[s].uid, act[s].prefill_pos, take)
                          for s, take in out]
            return out

        def chunk_call(*args):
            with self._span("host.chunk_dispatch"):
                out = chunk(*args)
            self.steps.append(StepRecord(
                "chunk", time.perf_counter(), self._plan, args[2],
                {k: out[3][k] for k in AUX_KEYS}))
            return out

        def decode_call(*args):
            ready = eng.decode_ready & eng.active_mask
            act = eng.scheduler.active
            rows = [(act[s].uid, int(eng.pos[s])) for s in np.flatnonzero(ready)]
            with self._span("host.decode_dispatch"):
                out = decode(*args)
            self.steps.append(StepRecord(
                "decode", time.perf_counter(), rows, args[2],
                {k: out[3][k] for k in AUX_KEYS}))
            return out

        def sample_call(logits):
            with self._span("host.sample_sync"):
                return sample(logits)

        def record_call(**kw):
            with self._span("host.record_stats"):
                return record(**kw)

        eng._plan_chunks, eng._chunk, eng._decode = \
            plan_chunks, chunk_call, decode_call
        eng._sample, eng._record = sample_call, record_call

    def to_host(self) -> None:
        """Pull the device counters (after the window)."""
        for s in self.steps:
            s.m_in = np.asarray(s.m_in, np.float64)
            s.aux = {k: np.asarray(v, np.float64) for k, v in s.aux.items()}


def _request(spec, due_abs: float):
    from repro.serving.scheduler import Request
    return Request(uid=spec.uid, tokens=spec.tokens, modality=spec.modality,
                   max_new_tokens=spec.max_new,
                   decode_modality=spec.decode_vision, arrival_time=due_abs)


@dataclass
class Window:
    t0: float
    close: float
    tracks: List[Track]
    requests: Dict[int, object]          # uid -> engine Request
    late: List[float] = field(default_factory=list)   # submit - due


class _Loop:
    def __init__(self, eng, clock, boundary):
        self.eng, self.clock, self.boundary = eng, clock, boundary
        self.tracks: List[Track] = []
        self.requests: Dict[int, object] = {}
        self.live: List[Track] = []
        self.late: List[float] = []

    def submit(self, spec, due_abs: float) -> Track:
        tr = Track(uid=spec.uid, due=due_abs, max_new=spec.max_new)
        req = _request(spec, due_abs)
        tr.submit = self.clock()
        self.late.append(tr.submit - due_abs)
        try:
            with self.boundary.span("harness.submit"):
                self.eng.submit(req)
        except (AssertionError, ValueError):
            tr.failed = True
        else:
            self.requests[spec.uid] = req
            self.live.append(tr)
        self.tracks.append(tr)
        return tr

    def step(self) -> List[Track]:
        """One engine step; stamps; returns the tracks that finished."""
        with self.boundary.span("harness.engine_step"):
            self.eng.step()
        t = self.clock()
        finished = []
        for tr in self.live:
            req = self.requests[tr.uid]
            if tr.admit is None and req.slot >= 0:
                tr.admit = t
            new = len(req.generated) - len(tr.tokens)
            tr.tokens.extend([t] * new)
            if tr.done:
                finished.append(tr)
        if finished:
            self.live = [tr for tr in self.live if not tr.done]
        return finished


class Boundary:
    """Called between steps with the window's elapsed seconds; the trace
    starts and stops here.  ``span`` labels host stages."""

    def at(self, elapsed: float) -> None:
        pass

    def span(self, name: str):
        return nullcontext()


def open_window(eng, specs, seconds: float, clock: Callable[[], float],
                boundary: Optional[Boundary] = None) -> Window:
    boundary = boundary or Boundary()
    lp = _Loop(eng, clock, boundary)
    pending = deque(sorted(specs, key=lambda s: s.due))
    t0 = clock()
    while True:
        el = clock() - t0
        if el >= seconds:
            break
        boundary.at(el)
        while pending and pending[0].due <= el:
            s = pending.popleft()
            lp.submit(s, t0 + s.due)
        if eng.scheduler.idle:
            nxt = min(pending[0].due if pending else seconds, seconds)
            with boundary.span("harness.idle_wait"):
                time.sleep(max(0.0, nxt - (clock() - t0)))
            continue
        lp.step()
    return Window(t0, t0 + seconds, lp.tracks, lp.requests, lp.late)


def closed_window(eng, clients, seconds: float, think_s: float,
                  clock: Callable[[], float],
                  boundary: Optional[Boundary] = None) -> Window:
    """``clients[c]`` is caller ``c``'s list of requests, sent in order."""
    boundary = boundary or Boundary()
    lp = _Loop(eng, clock, boundary)
    nxt = [0] * len(clients)
    by_uid = {}
    t0 = clock()
    due = deque((t0, c) for c in range(len(clients)))
    while True:
        now = clock()
        if now - t0 >= seconds:
            break
        boundary.at(now - t0)
        while due and due[0][0] <= now:
            t_due, c = due.popleft()
            spec = clients[c][nxt[c]]
            nxt[c] += 1
            by_uid[spec.uid] = c
            lp.submit(spec, t_due)
        if eng.scheduler.idle:
            with boundary.span("harness.idle_wait"):
                time.sleep(max(0.0, min(due[0][0] if due else now + 1e-3,
                                        t0 + seconds) - clock()))
            continue
        for tr in lp.step():
            c = by_uid[tr.uid]
            if nxt[c] < len(clients[c]):
                due.append((tr.tokens[-1] + think_s, c))
        due = deque(sorted(due))
    return Window(t0, t0 + seconds, lp.tracks, lp.requests, lp.late)
