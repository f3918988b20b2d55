"""The engine-layer readers: the engine's own request stamps
(``admit_time``, ``token_times``) and its ``host.engine.*`` profiler
spans against the device's idle time.  A run of a program without the
stamps or the spans reads None."""
from types import SimpleNamespace

import numpy as np
import pytest

import _paths  # noqa: F401
from harness import spec
from harness import trace as tr
from harness.serve import Window
from harness.stats import Track
from harness.trace import Device, Op, Trace

NEW = ("sched.queue_wait_p90_s", "engine.first_token_hold_p50_ms",
       "engine.host_serial_ms", "engine.stats_pull_ms")


def _req(**stamps):
    return SimpleNamespace(arrival_time=stamps.pop("arrival", None),
                           **stamps)


def _stamped_run():
    """Window 0..10 s; five requests due, one failed at submit."""
    tracks = [Track(uid=0, due=1.0, max_new=2, tokens=[2.5, 3.0]),
              Track(uid=1, due=2.0, max_new=2, tokens=[4.0]),
              Track(uid=2, due=3.0, max_new=2),          # admitted late
              Track(uid=3, due=4.0, max_new=2),          # never admitted
              Track(uid=4, due=5.0, max_new=2, failed=True),
              Track(uid=5, due=-1.0, max_new=2, tokens=[-0.5, 0.5])]
    reqs = {0: _req(arrival=1.0, admit_time=1.5, token_times=[2.4, 2.9]),
            1: _req(arrival=2.0, admit_time=2.25, token_times=[3.7]),
            2: _req(arrival=3.0, admit_time=11.0, token_times=[]),
            3: _req(arrival=4.0, admit_time=None, token_times=[]),
            5: _req(arrival=-1.0, admit_time=-0.9,
                    token_times=[-0.6, 0.4])}
    w = Window(0.0, 10.0, tracks, reqs)
    return SimpleNamespace(window=w, due=[t for t in tracks if t.due >= 0],
                           trace=None, device=lambda: None)


def test_queue_wait_reads_the_engine_admission_stamp():
    # 0.5, 0.25; admitted after the close and never admitted count at
    # close - arrival (7, 6); the failed request at close - due (5)
    want = np.percentile([0.5, 0.25, 7.0, 6.0, 5.0], 90)
    got = spec.metric_reader("sched.queue_wait_p90_s")(_stamped_run())
    assert got == pytest.approx(want)


def test_first_token_hold_is_harness_stamp_less_engine_stamp():
    # uid 0 held 0.1 s, uid 1 0.3 s; uid 5's first token fell before t0
    got = spec.metric_reader("engine.first_token_hold_p50_ms")(
        _stamped_run())
    assert got == pytest.approx(200.0)


def _traced_run(host):
    """Device busy 1-2, 3-3.5 and 6-9 s of a 0-10 s traced window."""
    dev = Device(ops=[Op(1.0, 2.0, "fusion.1", ""),
                      Op(3.0, 3.5, "fusion.2", ""),
                      Op(6.0, 9.0, "fusion.3", "")])
    t = Trace({0: dev}, [("harness.traced", 0.0, 10.0)] + host)
    return SimpleNamespace(trace=t, device=lambda: dev)


STEPS = [("host.engine.iter", 0.5, 4.0), ("host.engine.iter", 5.0, 9.5),
         ("host.engine.stats.pull", 2.0, 2.6),
         ("host.engine.stats.pull", 9.0, 9.4),
         ("host.record_stats", 1.9, 2.7)]


def test_host_serial_counts_idle_inside_steps_only():
    # idle 0.5-1, 2-3, 3.5-4 in the first step, 5-6 and 9-9.5 in the
    # second (3.5 s over 2 steps); 0-0.5, 4-5 and 9.5-10 lie outside
    got = spec.metric_reader("engine.host_serial_ms")(_traced_run(STEPS))
    assert got == pytest.approx(1750.0)


def test_stats_pull_counts_idle_inside_the_pull_spans():
    # 2-2.6 and 9-9.4 are idle (the op ends at 9): 1 s over 2 steps
    got = spec.metric_reader("engine.stats_pull_ms")(_traced_run(STEPS))
    assert got == pytest.approx(500.0)


def test_spans_are_clipped_to_the_traced_window():
    host = [("host.engine.iter", -2.0, 0.5),
            ("host.engine.iter", 9.5, 12.0),
            ("host.engine.iter", 11.0, 12.0)]
    # 0-0.5 and 9.5-10 idle; two steps reach into the window
    got = spec.metric_reader("engine.host_serial_ms")(_traced_run(host))
    assert got == pytest.approx(500.0)


@pytest.mark.parametrize("name", NEW)
def test_reads_none_without_stamps_or_spans(name):
    """The parent program: no stamps on its requests, no engine spans in
    its trace (only the harness's own)."""
    tracks = [Track(uid=0, due=1.0, max_new=1, tokens=[2.0])]
    w = Window(0.0, 10.0, tracks, {0: SimpleNamespace(arrival_time=1.0)})
    run = _traced_run([("host.record_stats", 2.0, 2.6),
                       ("host.sample_sync", 3.0, 3.4)])
    run.window, run.due = w, tracks
    assert spec.metric_reader(name)(run) is None


@pytest.mark.parametrize("name", NEW[2:])
def test_span_readers_read_none_without_a_trace(name):
    assert spec.metric_reader(name)(_stamped_run()) is None


def test_idle_gaps_go_to_the_engine_spans():
    """The engine's spans nest inside the harness's wrappers, so the
    trace's idle gaps go to them."""
    run = _traced_run(STEPS)
    lo, hi = run.trace.window
    gaps = dict(tr.idle_gaps(run.device(), run.trace.host, lo, hi))
    assert gaps["host.engine.stats.pull"] == pytest.approx(1.0)
    assert "host.record_stats" not in gaps
