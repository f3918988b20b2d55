"""Observability layer (repro.obs): span tracer + Chrome-trace export,
typed metrics registry, expert-load heatmap, prediction-accuracy
tracker, replan-decision audit log — and their wiring through
Telemetry, the managers and the engine (trace/accounting
reconciliation, exactly-one-audit-event-per-maybe_replan, bitwise
parity with tracing disabled)."""
import json
import pathlib

import numpy as np
import pytest

from repro.configs import (PlacementConfig, ReaLBConfig, ReplicationConfig,
                           get_config, reduced)
from repro.obs import (DEFAULT_TRACER, Counter, Gauge, HeatmapRecorder,
                       Histogram, MetricsRegistry, PredictionTracker,
                       ReplanAudit, Tracer, validate_chrome_trace)
from repro.obs.trace import PROFILER_PREFIX, Annotation, load_trace
from repro.placement import PlacementManager
from repro.replication import ReplicaManager
from repro.serving.telemetry import Telemetry, percentile, summarize

SKEW = [10.0, 8, 1, 1, 1, 1, 1, 1]
FLAT = [1.0] * 8


def _skew_stats(skews, e=8):
    es = np.zeros((len(skews), 2, e))
    for l, row in enumerate(skews):
        es[l, 0] = row
        es[l, 1] = np.asarray(row) * 0.5
    return es


# --------------------------------------------------------------------------
# percentile / summarize
# --------------------------------------------------------------------------
def test_percentile_matches_numpy_linear():
    rng = np.random.default_rng(7)
    for n in (1, 2, 3, 17, 100, 513):
        xs = rng.normal(size=n).tolist()
        for q in (0, 10, 50, 90, 99, 100):
            assert percentile(xs, q) == pytest.approx(
                float(np.percentile(xs, q, method="linear")))
    with pytest.raises(ValueError):
        percentile([], 50)
    with pytest.raises(ValueError):
        percentile([1.0], 101)


def test_summarize_empty_and_keys():
    assert summarize([]) == {}
    s = summarize([1.0, 2.0, 3.0])
    assert set(s) == {"p50", "p90", "p99", "mean"}
    assert s["p50"] == 2.0 and s["mean"] == 2.0
    assert set(summarize([1.0], qs=(50, 90))) == {"p50", "p90", "mean"}


# --------------------------------------------------------------------------
# metrics registry
# --------------------------------------------------------------------------
def test_counter_semantics():
    c = Counter("bytes")
    assert c.value() == 0 and c.total() == 0
    c.inc(5)
    c.inc(3)
    assert c.value() == 8 and isinstance(c.value(), int)
    with pytest.raises(ValueError):
        c.inc(-1)
    lab = Counter("decisions", labels=("verdict",))
    lab.inc(verdict="staged")
    lab.inc(2, verdict="noop")
    assert lab.value(verdict="staged") == 1 and lab.total() == 3
    with pytest.raises(ValueError):
        lab.inc(wrong="x")
    assert lab.snapshot() == {"verdict=noop": 2, "verdict=staged": 1}


def test_gauge_and_histogram_semantics():
    g = Gauge("capacity")
    assert g.value() is None and g.value(default=1.0) == 1.0
    g.set(0.5)
    g.set(0.7)
    assert g.value() == 0.7
    h = Histogram("lat")
    assert h.summary() == {} and h.count() == 0
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    assert h.count() == 4 and h.summary()["p50"] == 2.5
    snap = h.snapshot()
    assert snap["count"] == 4 and snap["max"] == 4.0


def test_histogram_rolling_window_eviction():
    h = Histogram("w", window=3)
    for v in range(10):
        h.observe(float(v))
    assert h.values() == [7.0, 8.0, 9.0] and h.count() == 3


def test_registry_register_or_get_and_snapshot():
    reg = MetricsRegistry()
    c1 = reg.counter("n", "help")
    assert reg.counter("n") is c1                  # same object back
    with pytest.raises(ValueError):
        reg.gauge("n")                             # kind mismatch
    with pytest.raises(ValueError):
        reg.counter("n", labels=("x",))            # label mismatch
    c1.inc(2)
    reg.gauge("g").set(1.5)
    reg.histogram("h").observe(3.0)
    snap = reg.snapshot()
    assert snap["n"] == 2 and snap["g"] == 1.5
    assert snap["h"]["count"] == 1
    json.dumps(snap)                               # JSON-serializable
    assert reg.names() == ["g", "h", "n"]
    assert reg.get("missing") is None


# --------------------------------------------------------------------------
# heatmap recorder
# --------------------------------------------------------------------------
def test_heatmap_accumulates_and_summarizes():
    hr = HeatmapRecorder(every=2, keep=3)
    assert hr.summary() == {}
    hm = np.array([[3.0, 1.0], [1.0, 1.0]])
    for _ in range(4):
        hr.record(hm)
    s = hr.summary()
    assert s["layers"] == 2 and s["ranks"] == 2 and s["n_records"] == 4
    assert s["layer_peak_rank"] == [0, 0]
    assert s["layer_peak_share"][0] == pytest.approx(0.75)
    assert s["layer_peak_share"][1] == pytest.approx(0.5)
    assert s["imbalance_max"] == pytest.approx(1.5)   # 0.75 * 2 ranks
    assert s["n_snapshots"] == 2                       # every=2, 4 records
    np.testing.assert_allclose(np.sum(s["share"], axis=1), 1.0)


def test_heatmap_shape_change_resets():
    hr = HeatmapRecorder()
    hr.record(np.ones((2, 4)))
    hr.record(np.ones((3, 4)))                         # elastic resize
    assert hr.n_records == 1 and hr.summary()["layers"] == 3


# --------------------------------------------------------------------------
# prediction tracker
# --------------------------------------------------------------------------
def test_prediction_tracker_window_math():
    pt = PredictionTracker()
    assert pt.summary() == {}
    # window 1: prediction exactly right
    pt.open(0, np.array([[4.0, 1.0, 1.0]]))
    for _ in range(3):
        pt.record(np.array([[8.0, 2.0, 2.0]]))         # same shares
    # window 2 opens (closes window 1): prediction wrong rank
    pt.open(10, np.array([[1.0, 1.0, 4.0]]))
    pt.record(np.array([[4.0, 1.0, 1.0]]))
    s = pt.summary()                                   # virtually closes w2
    assert s["n_windows"] == 2 and s["n_iters_observed"] == 4
    assert s["rank_match_frac"] == pytest.approx(0.5)
    assert s["peak_share_abs_err"]["p50"] == pytest.approx(0.0)
    assert pt.summary() == s                           # non-destructive
    assert len(pt.windows) == 1                        # w2 still open
    pt.record(np.array([[4.0, 1.0, 1.0]]))             # still accumulating
    assert pt.summary()["n_iters_observed"] == 5


def test_prediction_tracker_shared_table_folds_layers():
    """A shared-table manager predicts one depth-aggregated [1, R] row;
    per-layer realized [L, R] loads fold to the same shape."""
    pt = PredictionTracker()
    pt.open(0, np.array([[4.0, 1.0, 1.0]]))
    pt.record(np.array([[3.0, 0.5, 0.5], [1.0, 0.5, 0.5]]))
    s = pt.summary()
    assert s["n_iters_observed"] == 1
    assert s["rank_match_frac"] == 1.0
    assert s["real_peak_share_mean"] == pytest.approx(4.0 / 6.0)


def test_prediction_tracker_guards():
    pt = PredictionTracker()
    pt.record(np.ones((2, 3)))                         # no open window: noop
    pt.open(0, None)                                   # None: just closes
    pt.record(np.ones((2, 3)))
    assert pt.summary() == {}
    pt.open(1, np.ones((2, 3)))
    pt.record(np.ones((4, 3)))                         # shape mismatch: skip
    assert pt.summary() == {}                          # nothing accumulated


# --------------------------------------------------------------------------
# tracer + Chrome-trace export
# --------------------------------------------------------------------------
def test_tracer_spans_instants_and_export(tmp_path):
    t = [0.0]
    tr = Tracer(clock=lambda: t[0])
    with tr.span("iter", cat="engine") as sp:
        t[0] = 0.5
        with tr.span("launch.chunk") as inner:
            t[0] = 2.0
            inner.set(tokens=128)
        sp.set(it=3).set(n_active=2)                   # set() merges
    tr.instant("table.commit", cat="migration", args={"layers": 1})
    tr.complete("migration.drain", 2.0, 1.5, args={"stall_s": 1.5})
    assert len(tr) == 4
    obj = tr.to_chrome(metadata={"arm": "x"})
    events = validate_chrome_trace(obj)
    assert obj["metadata"] == {"arm": "x"} \
        and obj["displayTimeUnit"] == "ms"
    xs = [e for e in events if e["ph"] == "X"]
    # inner span closed first (append order), times in microseconds
    assert xs[0]["name"] == "launch.chunk"
    assert xs[0]["ts"] == pytest.approx(0.5e6)
    assert xs[0]["dur"] == pytest.approx(1.5e6)
    assert xs[1]["args"] == {"it": 3, "n_active": 2}
    assert xs[2]["dur"] == pytest.approx(1.5e6)
    inst = [e for e in events if e["ph"] == "i"]
    assert inst[0]["s"] == "t" and inst[0]["args"] == {"layers": 1}
    # roundtrip through the file writer + validating loader
    p = tmp_path / "trace.json"
    tr.write(str(p), metadata={"arm": "x"})
    assert load_trace(str(p))["metadata"] == {"arm": "x"}


def test_validate_chrome_trace_rejects_malformed():
    with pytest.raises(ValueError, match="traceEvents"):
        validate_chrome_trace({"events": []})
    bad_ph = {"traceEvents": [{"ph": "Z", "name": "x", "ts": 0}]}
    with pytest.raises(ValueError, match="phase"):
        validate_chrome_trace(bad_ph)
    no_name = {"traceEvents": [{"ph": "i", "ts": 0}]}
    with pytest.raises(ValueError, match="name"):
        validate_chrome_trace(no_name)
    neg_dur = {"traceEvents": [{"ph": "X", "name": "x", "ts": 0,
                                "dur": -1}]}
    with pytest.raises(ValueError, match="dur"):
        validate_chrome_trace(neg_dur)


def test_null_tracer_is_inert_singletons():
    """The default tracer records nothing: one shared instance, its spans
    only profiler annotations, instants and completes no-ops, and no
    span args wanted while no profiler trace is collected."""
    assert DEFAULT_TRACER.enabled is False
    assert DEFAULT_TRACER.collecting is False
    sp = DEFAULT_TRACER.span("anything")
    assert isinstance(sp, Annotation)
    with sp as s:
        assert s.set(a=1) is s
    DEFAULT_TRACER.instant("x")
    DEFAULT_TRACER.complete("x", 0.0, 1.0)                # all no-ops


def _profiled_spans(path):
    """The ``host.engine.*`` spans of the profiler trace under ``path``,
    read from its host plane: (name less the prefix, start ns, end ns,
    args), in start order, outer spans first."""
    from jax.profiler import ProfileData
    f = sorted(pathlib.Path(path).rglob("*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(f)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PROFILER_PREFIX):
                    out.append((ev.name[len(PROFILER_PREFIX):], ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return sorted(out, key=lambda e: (e[1], -e[2]))


def test_default_tracer_spans_reach_the_profiler(tmp_path):
    """Under a profiler trace the default tracer's spans land on the host
    plane as ``host.engine.<name>``, nested, with their args; instants
    stay Chrome-only."""
    import jax
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert DEFAULT_TRACER.collecting is True
        with DEFAULT_TRACER.span("iter") as sp:
            sp.set(it=7)
            with DEFAULT_TRACER.span("sample"):
                pass
        DEFAULT_TRACER.instant("dispatch.policy")
    finally:
        jax.profiler.stop_trace()
    assert DEFAULT_TRACER.collecting is False
    spans = _profiled_spans(tmp_path)
    assert [n for n, *_ in spans] == ["iter", "sample"]
    (_, a0, b0, args), (_, a1, b1, _) = spans
    assert a0 <= a1 <= b1 <= b0 and args["it"] == 7


def test_recording_tracer_forwards_spans_to_the_profiler(tmp_path):
    """A recording Tracer keeps its Chrome events and also annotates the
    profiler with the same spans."""
    import jax
    tr = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("iter", cat="engine") as sp:
            with tr.span("stats.pull"):
                pass
            sp.set(it=2)
        tr.complete("migration.drain", 0.0, 1.0)     # Chrome-only
    finally:
        jax.profiler.stop_trace()
    xs = [e["name"] for e in tr.to_chrome()["traceEvents"]
          if e["ph"] == "X"]
    assert xs == ["stats.pull", "iter", "migration.drain"]
    spans = _profiled_spans(tmp_path)
    assert [n for n, *_ in spans] == ["iter", "stats.pull"]
    assert spans[0][3]["it"] == 2


# --------------------------------------------------------------------------
# telemetry on the registry (satellite 1: recovery percentiles +
# disambiguated migration counters)
# --------------------------------------------------------------------------
class _Stat:
    def __init__(self, **kw):
        self.phase = "decode"
        self.ib_global = 1.0
        self.gate_open = 0.0
        self.fp4_ranks = 0.0
        for k, v in kw.items():
            setattr(self, k, v)


def test_telemetry_counter_shims_and_summary():
    tel = Telemetry()
    tel.record_iter(_Stat(migration_bytes=100, migration_s=0.5,
                          migration_hidden_s=0.25))
    tel.record_iter(_Stat(migration_bytes=0, migration_s=0.0,
                          migration_hidden_s=0.0))
    tel.record_iter(_Stat(migration_bytes=50, migration_s=0.0,
                          migration_hidden_s=0.1))
    tel.record_plan_commit()
    assert tel.migration_bytes_total == 150
    assert isinstance(tel.migration_bytes_total, int)
    assert tel.migration_s_total == pytest.approx(0.5)
    assert tel.migration_hidden_s_total == pytest.approx(0.35)
    assert tel.n_migrations == 2                       # iterations, not plans
    assert tel.n_plans_committed == 1
    s = tel.summary()
    assert s["n_migration_iters"] == 2 == s["n_migrations"]
    assert s["n_plans_committed"] == 1
    assert tel.registry.snapshot()["migration_bytes"] == 150


def test_telemetry_recovery_percentiles_and_max_alias():
    tel = Telemetry()
    s = tel.summary()
    assert s["recovery_s"] is None and s["recovery"] == {}
    for r in (1.0, 3.0, 2.0):
        tel.record_recovery(r)
    assert tel.recoveries == [1.0, 3.0, 2.0]
    s = tel.summary()
    assert s["recovery_s"] == 3.0                      # legacy max alias
    assert s["n_recoveries"] == 3
    assert s["recovery"]["p50"] == 2.0
    assert s["recovery"]["mean"] == pytest.approx(2.0)


def test_telemetry_empty_phase_summaries():
    tel = Telemetry()
    s = tel.summary()
    assert s["ttft"] == {} and s["ib_global"] == {}
    assert s["gate_duty_prefill"] == 0.0 and s["fp4_duty"] == 0.0
    assert s["availability"] == 1.0
    assert s["expert_load_heatmap"] == {}
    assert s["prediction_accuracy"] == {}
    tel.record_iter(_Stat(phase="decode"))
    assert tel.summary()["ib_global_prefill"] == {}    # no prefill iters


def test_telemetry_heatmap_and_prediction_feeds():
    tel = Telemetry()
    tel.record_rank_heatmap(None)                      # None-safe
    tel.open_prediction_window(0, np.array([[2.0, 1.0]]))
    for _ in range(3):
        tel.record_rank_heatmap(np.array([[2.0, 1.0]]))
    s = tel.summary()
    assert s["expert_load_heatmap"]["n_records"] == 3
    assert s["prediction_accuracy"]["n_windows"] == 1
    assert s["prediction_accuracy"]["rank_match_frac"] == 1.0
    assert s["prediction_accuracy"]["peak_share_abs_err"]["p50"] \
        == pytest.approx(0.0)


# --------------------------------------------------------------------------
# replan audit: exactly one event per maybe_replan call, priced verdicts
# --------------------------------------------------------------------------
def _audited_mgr(cls, ccls, per_layer=False, **kw):
    cfgkw = dict(replan_every=2, warmup_iters=3, min_gain=0.0,
                 per_layer=per_layer, **kw)
    mgr = cls.from_geometry(8, ccls(**cfgkw), 4, bytes_per_expert=7,
                            n_layers=3 if per_layer else 1)
    mgr.audit = ReplanAudit()
    return mgr


@pytest.mark.parametrize("cls,ccls", [
    (PlacementManager, PlacementConfig),
    (ReplicaManager, ReplicationConfig)])
@pytest.mark.parametrize("per_layer", [False, True])
def test_audit_one_event_per_maybe_replan(cls, ccls, per_layer):
    mgr = _audited_mgr(cls, ccls, per_layer=per_layer)
    n_calls = 0
    for it in range(1, 9):
        mgr.observe(_skew_stats([SKEW, FLAT, SKEW[::-1]] if per_layer
                                else [SKEW]))
        plan = mgr.maybe_replan(it)
        n_calls += 1
        if plan is not None:
            mgr.commit(plan)
    assert len(mgr.audit) == n_calls                   # completeness
    assert [e["seq"] for e in mgr.audit.events] == list(range(n_calls))
    assert all(e["manager"] == mgr._kind for e in mgr.audit.events)
    # n_obs < warmup_iters=3 at iterations 1-2 (one observe per call);
    # past warmup every even iteration hits the replan_every=2 cadence
    assert mgr.audit.query(it=1)[0]["verdict"] == "warmup"
    assert mgr.audit.query(it=2)[0]["verdict"] == "warmup"
    assert mgr.audit.query(it=3)[0]["verdict"] == "no-cadence"
    hits = mgr.audit.cadence_hits()
    assert {e["it"] for e in hits} == {4, 6, 8}
    for e in hits:
        assert e["regime"] == "mixed"
    staged = mgr.audit.query(verdict="staged")
    assert staged, "the skewed load must stage at least one plan"
    for e in staged:
        assert e["migration_bytes"] > 0 and e["migration_s"] >= 0
        assert e["pred_gain"] > 0 and e["n_moved"] > 0
    counts = mgr.audit.counts()
    assert sum(counts.values()) == n_calls


def test_audit_cost_gate_rejection_is_priced():
    class VetoGate:
        def accept(self, old, new, moved):
            return False

        def accept_layers(self, old, new, moved):
            return False

    pcfg = PlacementConfig(replan_every=2, warmup_iters=1, min_gain=0.0)
    mgr = PlacementManager.from_geometry(8, pcfg, 4, bytes_per_expert=7,
                                         cost_gate=VetoGate())
    mgr.audit = ReplanAudit()
    mgr.observe(_skew_stats([SKEW]))
    assert mgr.maybe_replan(2) is None
    (ev,) = mgr.audit.query(verdict="cost-gate")
    assert ev["migration_bytes"] > 0 and "pred_gain" in ev
    assert mgr.audit.counts()["cost-gate"] == 1


def test_audit_jsonl_roundtrip(tmp_path):
    audit = ReplanAudit()
    audit.record(it=1, manager="placement", verdict="warmup")
    audit.record(it=2, manager="placement", verdict="staged",
                 regime="mixed", pred_gain=0.5, migration_bytes=100,
                 dropped=None)                         # None fields dropped
    p = tmp_path / "audit.jsonl"
    audit.to_jsonl(str(p))
    back = ReplanAudit.load_jsonl(str(p))
    assert back == audit.events
    assert "dropped" not in back[1]


def test_audit_disabled_by_default_no_overhead():
    pcfg = PlacementConfig(replan_every=2, warmup_iters=1, min_gain=0.0)
    mgr = PlacementManager.from_geometry(8, pcfg, 4, bytes_per_expert=7)
    assert mgr.audit is None and mgr.tracer is DEFAULT_TRACER
    mgr.observe(_skew_stats([SKEW]))
    assert mgr.maybe_replan(2) is not None             # planning unaffected


# --------------------------------------------------------------------------
# manager rank heatmaps ([L, R] from the scan's expert/slot stats)
# --------------------------------------------------------------------------
def test_placement_rank_heatmap_folds_tables():
    pcfg = PlacementConfig(replan_every=2, warmup_iters=1, min_gain=0.0,
                           per_layer=True)
    mgr = PlacementManager.from_geometry(8, pcfg, 4, bytes_per_expert=7,
                                         n_layers=2)
    es = _skew_stats([SKEW, FLAT])
    hm = mgr.rank_heatmap(es)
    assert hm.shape == (2, 4)
    np.testing.assert_allclose(hm.sum(axis=1), es[:, 0, :].sum(axis=1))
    # identity-ish layout: rank r owns experts 2r, 2r+1
    np.testing.assert_allclose(hm[1], [2.0, 2.0, 2.0, 2.0])


def test_replication_rank_heatmap_prefers_slot_stats():
    rcfg = ReplicationConfig(replan_every=2, warmup_iters=1, min_gain=0.0,
                             spare_per_rank=1)
    mgr = ReplicaManager.from_geometry(8, rcfg, 4, bytes_per_expert=7)
    es = _skew_stats([SKEW])
    hm = mgr.rank_heatmap(es)
    assert hm.shape == (1, 4) and hm.sum() == pytest.approx(es[0, 0].sum())
    # exact post-split loads come from slot stats when provided
    ss = np.zeros((1, 2, mgr.n_slots))
    ss[0, 0, :] = 1.0
    hm2 = mgr.rank_heatmap(es, slot_stats=ss)
    np.testing.assert_allclose(hm2[0], np.full(4, mgr.slots_per_rank))


# --------------------------------------------------------------------------
# engine end-to-end (slow): trace reconciliation + disabled parity
# --------------------------------------------------------------------------
@pytest.fixture(scope="module")
def model():
    import jax

    from repro.models import transformer as tf
    cfg = reduced(get_config("olmoe-1b-7b"), n_layers=2)
    params = tf.init_model(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _reqs(cfg, n=6, p_len=12, new=4, seed=0):
    from repro.serving.scheduler import Request
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        toks = rng.integers(0, cfg.vocab_size, p_len).astype(np.int32)
        out.append(Request(uid=i, tokens=toks,
                           modality=np.full(p_len, bool(i % 2)),
                           max_new_tokens=new, arrival_time=0.0))
    return out


def _bias_routers_by_depth(params, biases):
    import jax.numpy as jnp
    out = dict(params)
    blocks = dict(out["blocks"])
    lp = dict(blocks["layer0"])
    moe = dict(lp["moe"])
    moe["router"] = moe["router"] + jnp.asarray(biases)[:, None, :]
    lp["moe"] = moe
    blocks["layer0"] = lp
    out["blocks"] = blocks
    return out


def _engine(cfg, params, tracer=None, migrate_async=False, budget=None):
    from repro.serving.engine import Engine
    from repro.workloads import IterationCostModel, VirtualClock
    mgr = PlacementManager(cfg, PlacementConfig(
        planner="least_loaded", replan_every=3, warmup_iters=2,
        min_gain=0.0, per_layer=True), 4)
    mgr.audit = ReplanAudit()
    tel = Telemetry()
    eng = Engine(cfg, params, ReaLBConfig(gate_gamma=4), max_slots=3,
                 max_len=32, placement=mgr, telemetry=tel,
                 clock=VirtualClock(), cost_model=IterationCostModel(),
                 migrate_async=migrate_async,
                 migrate_bytes_per_iter=budget, tracer=tracer)
    return eng, mgr, tel


@pytest.mark.slow
@pytest.mark.parametrize("migrate_async", [False, True])
def test_engine_trace_reconciles_migration_accounting(model, migrate_async,
                                                      tmp_path):
    """Acceptance invariant: summed migration.drain span durations equal
    migration_s_total + migration_hidden_s_total (sync and async)."""
    from repro.placement import migrate as pmigrate
    cfg, params = model
    b0 = np.array([3.0, 2.0, 1.0, 0.5, 0.0, 0.0, 0.0, 0.0])
    params = _bias_routers_by_depth(params, np.stack([b0, b0[::-1]]))
    budget = pmigrate.expert_bytes(cfg, 1) * cfg.moe.num_experts \
        if migrate_async else None
    eng, mgr, tel = _engine(cfg, params, tracer=Tracer(),
                            migrate_async=migrate_async, budget=budget)
    eng.tracer.clock = eng.clock                       # trace engine time
    for r in _reqs(cfg, n=12, seed=3):
        eng.submit(r)
    eng.run()
    eng.drain_migrations()
    assert mgr.n_migrations >= 1
    p = tmp_path / "trace.json"
    eng.tracer.write(str(p), metadata={
        "migration_s_total": eng.migration_stall_s,
        "migration_hidden_s_total": eng.migration_hidden_s})
    obj = load_trace(str(p))
    drains = [e for e in obj["traceEvents"]
              if e.get("ph") == "X" and e["name"] == "migration.drain"]
    assert drains, "migrations ran but no drain spans recorded"
    span_s = sum(e["dur"] for e in drains) / 1e6
    assert span_s == pytest.approx(
        eng.migration_stall_s + eng.migration_hidden_s, abs=1e-9)
    stall_s = sum(e["args"]["stall_s"] for e in drains)
    hidden_s = sum(e["args"]["hidden_s"] for e in drains)
    assert stall_s == pytest.approx(eng.migration_stall_s, abs=1e-9)
    assert hidden_s == pytest.approx(eng.migration_hidden_s, abs=1e-9)
    if migrate_async:
        assert hidden_s > 0
    else:
        assert hidden_s == 0.0 and stall_s > 0
    # the documented span vocabulary is present
    names = {e["name"] for e in obj["traceEvents"] if e.get("ph") == "X"}
    assert {"iter", "admit", "migrate", "migration.drain"} <= names
    assert names & {"launch.chunk", "launch.decode", "launch.prefill"}
    assert any(e["name"] == "replan.placement"
               for e in obj["traceEvents"])
    assert any(e["name"] == "table.commit"
               for e in obj["traceEvents"] if e.get("ph") == "i")
    assert any(e["name"] == "dispatch.policy"
               for e in obj["traceEvents"] if e.get("ph") == "i")
    # audit completeness rode along: plans committed => staged verdicts
    assert len(mgr.audit.query(verdict="staged")) >= mgr.n_migrations
    # prediction accuracy reached the telemetry summary (acceptance)
    acc = tel.summary()["prediction_accuracy"]
    assert acc and acc["n_windows"] >= 1
    assert tel.summary()["expert_load_heatmap"]["n_records"] > 0


@pytest.mark.slow
def test_engine_disabled_tracer_bitwise_parity(model):
    """An engine without a tracer produces bitwise-identical generations
    and identical accounting to one tracing every span."""
    cfg, params = model
    b0 = np.array([3.0, 2.0, 1.0, 0.5, 0.0, 0.0, 0.0, 0.0])
    params = _bias_routers_by_depth(params, np.stack([b0, b0[::-1]]))
    outs = []
    for tracer in (None, Tracer()):
        eng, mgr, tel = _engine(cfg, params, tracer=tracer)
        if tracer is not None:
            eng.tracer.clock = eng.clock
        for r in _reqs(cfg, n=8, seed=5):
            eng.submit(r)
        eng.run()
        eng.drain_migrations()
        outs.append((
            {r.uid: list(r.generated) for r in eng.scheduler.finished},
            eng.migration_bytes_moved, mgr.n_migrations,
            [list(t.e2r) for t in mgr.tables],
        ))
    base, traced = outs
    assert base[0] == traced[0]                        # same tokens, bitwise
    # same plans, bytes and final tables (stall *seconds* are measured
    # apply wall time — nondeterministic run-to-run with or without a
    # tracer — so they are excluded from the parity check)
    assert base[1:] == traced[1:]


#: The spans of the first step of ``_served``: three prompts complete in
#: one chunk, then the three slots decode; every span is a child of iter.
FIRST_STEP = ["admit", "chunk.inputs", "launch.chunk", "sample",
              "stats.pull", "stats.observe", "retire", "decode.inputs",
              "launch.decode", "sample", "stats.pull", "stats.observe",
              "retire"]


def _served(model, tracer=None):
    """A tiny chunked engine on the virtual clock, run to the end: three
    slots, six 12-token prompts, four new tokens each."""
    from repro.serving.engine import Engine
    from repro.workloads import IterationCostModel, VirtualClock
    cfg, params = model
    eng = Engine(cfg, params, ReaLBConfig(gate_gamma=4), max_slots=3,
                 max_len=32, clock=VirtualClock(),
                 cost_model=IterationCostModel(), tracer=tracer)
    for r in _reqs(cfg, n=6, seed=1):
        eng.submit(r)
    steps = 0
    while not eng.scheduler.idle:
        eng.step()
        steps += 1
    return eng, steps


def _children(spans, steps):
    """Group (name, start, end, args) spans under their iter span; every
    other span must lie inside exactly one iter."""
    iters = [sp for sp in spans if sp[0] == "iter"]
    assert len(iters) == steps
    kids = {i: [] for i in range(len(iters))}
    for sp in spans:
        if sp[0] == "iter":
            continue
        inside = [i for i, it in enumerate(iters)
                  if it[1] <= sp[1] and sp[2] <= it[2]]
        assert len(inside) == 1, sp
        kids[inside[0]].append(sp)
    return iters, kids


@pytest.fixture(scope="module")
def profiled(model, tmp_path_factory):
    """The default tracer's engine under a profiler trace."""
    import jax
    d = tmp_path_factory.mktemp("profile")
    jax.profiler.start_trace(str(d))
    try:
        eng, steps = _served(model)
    finally:
        jax.profiler.stop_trace()
    return eng, steps, _profiled_spans(d)


@pytest.mark.slow
def test_engine_spans_on_the_profiler_host_plane(profiled):
    """Without a recording tracer the engine's spans reach the profiler's
    host plane, one iter per step, the rest nested inside."""
    eng, steps, spans = profiled
    iters, kids = _children(spans, steps)
    assert [sp[0] for sp in kids[0]] == FIRST_STEP
    names = {sp[0] for sp in spans}
    assert names == set(FIRST_STEP) | {"iter"}
    # a decode-only step: no chunk spans
    assert [sp[0] for sp in kids[1]] == ["admit", "retire"] + FIRST_STEP[7:]


@pytest.mark.slow
def test_engine_profiler_span_args(profiled):
    """The iter span carries the step's rows; stats.pull counts its
    device-to-host transfers (moe_stats and five scalars)."""
    _, steps, spans = profiled
    iters, kids = _children(spans, steps)
    args = iters[0][3]
    assert (args["it"], args["admitted"], args["chunk_rows"],
            args["chunk_tokens"], args["decode_rows"]) == (1, 3, 3, 36, 3)
    pulls = [sp[3] for sp in spans if sp[0] == "stats.pull"]
    assert pulls and all(a["transfers"] == 6 for a in pulls)


@pytest.mark.slow
def test_engine_chrome_trace_span_vocabulary(model):
    """A recording Tracer's Chrome JSON holds the same spans as the
    profiler, nested the same way."""
    tracer = Tracer()
    eng, steps = _served(model, tracer)
    events = validate_chrome_trace(tracer.to_chrome())
    spans = sorted(((e["name"], e["ts"], e["ts"] + e["dur"],
                     e.get("args", {})) for e in events if e["ph"] == "X"),
                   key=lambda e: (e[1], -e[2]))
    iters, kids = _children(spans, steps)
    assert [sp[0] for sp in kids[0]] == FIRST_STEP
    assert iters[0][3]["decode_rows"] == 3


@pytest.mark.slow
def test_request_stamps_on_the_engine_clock(profiled):
    """Admission and per-token stamps: admitted before the first token,
    one stamp per token, the first the first-token time; the chunk's and
    the decode's sample of one step stamp apart."""
    eng, _, _ = profiled
    done = eng.scheduler.finished
    assert len(done) == 6
    for r in done:
        assert r.arrival_time <= r.admit_time <= r.token_times[0]
        assert len(r.token_times) == len(r.generated) == 4
        assert r.token_times[0] == r.first_token_time
        assert r.token_times == sorted(r.token_times)
        assert r.token_times[1] > r.token_times[0]
        assert r.finish_time >= r.token_times[-1]


@pytest.mark.slow
def test_elastic_events_traced_as_instants(model):
    """ElasticCoordinator events surface as elastic.* instants."""
    import tempfile

    from repro.checkpoint import ckpt as ckpt_lib
    from repro.replication import expand_moe_params
    from repro.runtime.fault_tolerance import FaultInjector
    from repro.serving.elastic import ElasticCoordinator
    from repro.serving.engine import Engine
    from repro.workloads import IterationCostModel, VirtualClock
    cfg, params = model
    rcfg = ReplicationConfig(replan_every=3, warmup_iters=2, min_gain=0.0,
                             spare_per_rank=1, per_layer=True)
    mgr = ReplicaManager(cfg, rcfg, ep=4)
    params = expand_moe_params(params, mgr.rsets)
    clock = VirtualClock()
    tel = Telemetry()
    with tempfile.TemporaryDirectory() as d:
        ckpt_lib.save(d, 0, {"serving": {"params": params},
                             mgr.ckpt_group: mgr.state_dict()})
        elastic = ElasticCoordinator(mgr, ckpt_dir=d, clock=clock,
                                     telemetry=tel)
        injector = FaultInjector([(4, "fail", 1), (12, "rejoin", 1)])
        tracer = Tracer(clock=clock)
        eng = Engine(cfg, params, ReaLBConfig(gate_gamma=4), max_slots=3,
                     max_len=32, placement=mgr, telemetry=tel, clock=clock,
                     cost_model=IterationCostModel(), elastic=elastic,
                     fault_injector=injector, migrate_async=True,
                     migrate_bytes_per_iter=4096, tracer=tracer)
        for r in _reqs(cfg, n=12, seed=3):
            eng.submit(r)
        eng.run()
        eng.drain_migrations()
    kinds = {e["kind"] for e in elastic.events}
    assert "fail" in kinds and "rejoin" in kinds
    obj = tracer.to_chrome()
    inst = [e["name"] for e in obj["traceEvents"] if e.get("ph") == "i"]
    for k in kinds:
        assert f"elastic.{k}" in inst
    validate_chrome_trace(obj)
