"""Hot-loop profiler (repro.obs.ledger / repro.obs.profiler): FLOP/byte
ledger math vs hand counts (BF16 + FP4 arms), costmodel formula pinning,
disabled-profiler engine parity, drift/reconciliation under the virtual
clock, cost-gate time_scale wiring, and profile_report exit codes."""
import json

import numpy as np
import pytest

from repro.configs import (PlacementConfig, ReaLBConfig, get_config,
                           reduced)
from repro.configs.hw import HBM_BW, PEAK_BF16, PEAK_INT8
from repro.obs import (NULL_PROFILER, PHASES, FlopByteLedger,
                       MetricsRegistry, Profiler)
from repro.obs.ledger import BYTES_BF16, BYTES_FP4, FIXED_US
from repro.serving.telemetry import Telemetry

EP = 4


@pytest.fixture(scope="module")
def lcfg():
    return reduced(get_config("olmoe-1b-7b"), n_layers=2)


def _stats(loads):
    """[L, 2, ep] moe_stats with the given [L, ep] routed loads."""
    loads = np.asarray(loads, np.float64)
    ms = np.zeros((loads.shape[0], 2, loads.shape[1]))
    ms[:, 0] = loads
    ms[:, 1] = loads * 0.5
    return ms


# --------------------------------------------------------------------------
# ledger vs hand counts
# --------------------------------------------------------------------------
def test_ledger_bf16_hand_counts(lcfg):
    led = FlopByteLedger(lcfg, ep=EP)
    loads = np.array([[6.0, 2.0, 1.0, 1.0], [2.5, 2.5, 2.5, 2.5]])
    tokens, batch = 10.0, 16.0
    it = led.account(_stats(loads), fp4_layers=0.0, tokens=tokens,
                     batch_tokens=batch)
    d, dff, E, k = led.d, led.d_ff, led.n_experts, led.top_k
    gemm_per_tok = 2.0 * led.mult * d * dff
    w_slab = led.e_loc * led.mult * d * dff
    L = loads.shape[0]

    assert it.flops["route"] == pytest.approx(L * tokens * d * E * 2.0)
    assert it.flops["expert_gemm"] == pytest.approx(
        loads.sum() * gemm_per_tok)
    assert it.flops_by_rate["int8"] == 0.0
    assert it.flops_by_rate["bf16"] == pytest.approx(
        loads.sum() * gemm_per_tok)
    # every rank streams its BF16 slab + its routed activations
    assert it.hbm_bytes["expert_gemm"] == pytest.approx(
        L * EP * w_slab * BYTES_BF16
        + loads.sum() * d * BYTES_BF16 * 4.0)
    assert it.hbm_bytes["quantize_fp4"] == 0.0
    assert it.pred_s["quantize_fp4"] == 0.0
    a2a = tokens * k / EP * (EP - 1) / EP * d * BYTES_BF16 * EP
    assert it.ici_bytes["dispatch"] == pytest.approx(L * a2a)
    assert it.ici_bytes["combine"] == pytest.approx(L * a2a)
    # MFU numerator: useful work at real (non-padded) tokens
    assert it.model_flops == pytest.approx(
        2.0 * lcfg.active_param_count() * tokens)
    assert it.tokens == tokens and it.batch_tokens == batch
    # exhaustive phase vocabulary, plain-float JSON-serializable
    assert set(it.pred_s) == set(PHASES) == set(it.flops)
    json.dumps([it.flops, it.hbm_bytes, it.ici_bytes, it.pred_s,
                it.flops_by_rate])
    # expert-GEMM predicted time is the straggler rank at BF16 rates
    worst = max(loads[l].max() for l in range(L))
    t_straggler = max(
        worst * gemm_per_tok / PEAK_BF16,
        (w_slab * BYTES_BF16 + worst * d * BYTES_BF16 * 4.0) / HBM_BW)
    assert it.pred_s["expert_gemm"] >= t_straggler - 1e-12


def test_ledger_fp4_hot_rank_attribution(lcfg):
    """fp4_layers=k attributes FP4 (int8-rate flops, 4.25-bit slabs,
    quantize traffic) to the k most-loaded ranks of each layer.  Runs
    fused (the kernel-wired hot loop): packed slabs stream with no BF16
    round-trip and the transformation hides inside the dispatch window."""
    from repro.configs.base import MIGRATION_BW_DEFAULT
    led = FlopByteLedger(lcfg, ep=EP, fused=True)
    loads = np.array([[6.0, 2.0, 1.0, 1.0]])
    it = led.account(_stats(loads), fp4_layers=1.0, tokens=10.0,
                     batch_tokens=16.0)
    gemm_per_tok = 2.0 * led.mult * led.d * led.d_ff
    w_slab = led.e_loc * led.mult * led.d * led.d_ff
    assert it.flops_by_rate["int8"] == pytest.approx(6.0 * gemm_per_tok)
    assert it.flops_by_rate["bf16"] == pytest.approx(4.0 * gemm_per_tok)
    # BF16-read + packed-write traffic is real either way; fusion only
    # changes the *visible seconds* (excess over the dispatch window)
    q_bytes = w_slab * (BYTES_BF16 + BYTES_FP4)
    assert it.hbm_bytes["quantize_fp4"] == pytest.approx(q_bytes)
    disp = led._dispatch_s(10.0 * led.top_k, MIGRATION_BW_DEFAULT)
    assert it.pred_s["quantize_fp4"] == pytest.approx(
        max(0.0, q_bytes / HBM_BW - disp))
    # the hot rank streams the packed slab, the cold ranks BF16
    assert it.hbm_bytes["expert_gemm"] == pytest.approx(
        3 * w_slab * BYTES_BF16 + w_slab * BYTES_FP4
        + loads.sum() * led.d * BYTES_BF16 * 4.0)
    # int8 MXU rate on the hot rank: all-FP4 predicted gemm is faster
    it_all = led.account(_stats(loads), fp4_layers=EP, tokens=10.0,
                         batch_tokens=16.0)
    assert it_all.flops_by_rate["bf16"] == 0.0
    assert it_all.pred_s["expert_gemm"] <= it.pred_s["expert_gemm"]
    assert PEAK_INT8 > PEAK_BF16


def test_ledger_unfused_charges_dequant_round_trip(lcfg):
    """fused=False (the jnp fallback): every FP4 rank pays the dequantized
    BF16 slab round-trip on expert_gemm, and the transformation is a fully
    visible standalone stage (bytes + per-stage launch overhead)."""
    loads = np.array([[6.0, 2.0, 1.0, 1.0]])
    kw = dict(fp4_layers=1.0, tokens=10.0, batch_tokens=16.0)
    led_f = FlopByteLedger(lcfg, ep=EP, fused=True)
    led_u = FlopByteLedger(lcfg, ep=EP)      # fused defaults to False
    assert led_f.fused and not led_u.fused
    it_f = led_f.account(_stats(loads), **kw)
    it_u = led_u.account(_stats(loads), **kw)
    w_slab = led_u.e_loc * led_u.mult * led_u.d * led_u.d_ff
    # exactly one FP4 rank -> exactly one slab's write+read round-trip
    assert (it_u.hbm_bytes["expert_gemm"] - it_f.hbm_bytes["expert_gemm"]
            ) == pytest.approx(w_slab * 2.0 * BYTES_BF16)
    assert it_u.pred_s["quantize_fp4"] == pytest.approx(
        led_u._quantize_s() + FIXED_US * 1e-6)
    assert it_u.pred_s["quantize_fp4"] > it_f.pred_s["quantize_fp4"]
    # the quantize traffic itself is identical — only visibility differs
    assert it_u.hbm_bytes["quantize_fp4"] == pytest.approx(
        it_f.hbm_bytes["quantize_fp4"])
    assert it_u.flops == it_f.flops and it_u.flops_by_rate == it_f.flops_by_rate


def test_ledger_mirrors_costmodel_formulas(lcfg):
    """The ledger's private per-phase predictors are formula-for-formula
    the benchmarks/costmodel.py public ones (same single-sourced hw
    constants) — the invariant that makes costmodel drift meaningful."""
    from benchmarks import costmodel as cm
    assert FIXED_US == cm.FIXED_US
    assert BYTES_BF16 == cm.BYTES_BF16 and BYTES_FP4 == cm.BYTES_FP4
    n_moe = sum(1 for f in lcfg.ffn_kinds() if f == "moe")
    g = cm.MoEGeometry(lcfg.name, lcfg.d_model, lcfg.moe.d_ff,
                       lcfg.moe.num_experts, lcfg.moe.top_k, n_moe)
    led = FlopByteLedger(lcfg, ep=EP)
    assert led.mult == 3  # olmoe is swiglu; costmodel hardcodes 3.0
    for fused in (False, True):
        led_x = FlopByteLedger(lcfg, ep=EP, fused=fused)
        for t in (0.0, 7.0, 513.0):
            for fp4 in (False, True):
                assert led_x._expert_gemm_s(t, fp4) == pytest.approx(
                    cm.expert_gemm_time(t, g, EP, fp4, fused=fused))
        for disp in (0.0, 3e-6, 1e-3):
            assert led_x._quantize_visible_s(disp) == pytest.approx(
                cm.quantize_visible_time(g, EP, disp, fused=fused))
    for t in (0.0, 7.0, 513.0):
        assert led._dispatch_s(t, cm.ICI_BW) == pytest.approx(
            cm.dispatch_time(t, EP, g.d_model))
        assert led._nongemm_s(t) == pytest.approx(cm.nongemm_time(t, g))
    assert led._quantize_s() == pytest.approx(cm.quantize_time(g, EP))
    # costmodel default fused=True == what the kernel-wired hot loop runs
    assert cm.expert_gemm_time(7.0, g, EP, True) == pytest.approx(
        FlopByteLedger(lcfg, ep=EP, fused=True)._expert_gemm_s(7.0, True))


def test_hw_constants_single_sourced():
    """roofline + costmodel compute/HBM rates come from repro.configs.hw;
    ICI_BW deliberately stays MIGRATION_BW_DEFAULT in the costmodel."""
    from benchmarks import costmodel as cm
    from repro.configs import hw
    from repro.configs.base import MIGRATION_BW_DEFAULT
    from repro.launch import roofline
    assert roofline.PEAK_FLOPS is hw.PEAK_FLOPS is hw.PEAK_BF16
    assert roofline.HBM_BW is hw.HBM_BW
    assert cm.PEAK_BF16 is hw.PEAK_BF16 and cm.PEAK_INT8 is hw.PEAK_INT8
    assert cm.HBM_BW is hw.HBM_BW
    assert cm.ICI_BW == MIGRATION_BW_DEFAULT


# --------------------------------------------------------------------------
# profiler accounting: attribution, EWMA drift, registry gauges
# --------------------------------------------------------------------------
def _profiler(lcfg, registry=None):
    return Profiler(FlopByteLedger(lcfg, ep=EP), registry=registry)


def test_profiler_exhaustive_attribution_and_time_scale(lcfg):
    reg = MetricsRegistry()
    prof = _profiler(lcfg, registry=reg)
    ms = _stats([[6.0, 2.0, 1.0, 1.0], [2.5, 2.5, 2.5, 2.5]])
    led = prof.ledger.account(ms, 0.0, 10.0, 16.0)
    fwd = 2.0 * led.pred_total        # constant measured/predicted ratio
    for _ in range(4):
        prof.observe_iter(moe_stats=ms, fp4_layers=0.0, tokens=10.0,
                          batch_tokens=16.0, fwd_s=fwd)
    # exhaustive attribution: phases partition the measured seconds
    assert sum(prof.phase_seconds().values()) == pytest.approx(
        prof.fwd_s_total)
    # EWMA of a constant ratio is the ratio, and every phase drifts by it
    assert prof.time_scale() == pytest.approx(2.0)
    for ph, r in prof.drift().items():
        if prof.phase_seconds_pred()[ph] > 0:
            assert r == pytest.approx(2.0)
    assert prof.mfu() == pytest.approx(
        4 * led.model_flops / (prof.fwd_s_total * PEAK_BF16))
    assert 0.0 < prof.roofline_fraction() <= 1.0
    # the registry carries what Telemetry.summary() will surface
    assert reg.gauge("mfu").value() == pytest.approx(prof.mfu())
    assert reg.gauge("costmodel_time_scale").value() == pytest.approx(2.0)
    assert reg.counter("model_flops").total() == pytest.approx(
        prof.model_flops_total)
    assert reg.counter("phase_seconds", labels=("phase",)).total() \
        == pytest.approx(prof.fwd_s_total)
    assert reg.gauge("costmodel_drift", labels=("phase",)).value(
        phase="expert_gemm") == pytest.approx(2.0)
    args = prof.span_args()
    assert args["model_flops"] == pytest.approx(led.model_flops)


def test_profiler_measured_phase_override_rescales(lcfg):
    """An instrumented caller's per-phase seconds are rescaled to sum to
    fwd_s so the attribution invariant survives unoverlapped timings."""
    prof = _profiler(lcfg)
    ms = _stats([[4.0, 2.0, 1.0, 1.0]])
    prof.observe_iter(moe_stats=ms, fp4_layers=0.0, tokens=8.0,
                      batch_tokens=8.0, fwd_s=0.01,
                      measured_phases={"route": 3.0, "dispatch": 1.0})
    ps = prof.phase_seconds()
    assert ps["route"] == pytest.approx(0.0075)
    assert ps["dispatch"] == pytest.approx(0.0025)
    assert sum(ps.values()) == pytest.approx(0.01)


def test_null_profiler_is_inert_singleton():
    assert NULL_PROFILER.enabled is False
    NULL_PROFILER.observe_iter(moe_stats=None, fwd_s=-1.0)
    assert NULL_PROFILER.time_scale() == 1.0
    assert NULL_PROFILER.mfu() == 0.0
    assert NULL_PROFILER.span_args() == {}


# --------------------------------------------------------------------------
# cost-gate calibration: time_scale scales the savings side
# --------------------------------------------------------------------------
def test_cost_gate_time_scale_scales_layer_seconds(lcfg):
    from benchmarks import costmodel as cm
    g = cm.MoEGeometry(lcfg.name, lcfg.d_model, lcfg.moe.d_ff,
                       lcfg.moe.num_experts, lcfg.moe.top_k, 2)
    kw = dict(horizon_iters=8, tokens_per_iter=256.0)
    base = cm.ReplanCostGate(g, EP, **kw)
    loads = np.array([100.0, 50.0, 25.0, 25.0])
    t1 = base.layer_seconds(loads)
    assert t1 > 0
    assert cm.ReplanCostGate(g, EP, time_scale=2.0, **kw).layer_seconds(
        loads) == pytest.approx(2.0 * t1)
    # callables (the profiler's bound EWMA method) work the same way
    assert cm.ReplanCostGate(g, EP, time_scale=lambda: 3.0,
                             **kw).layer_seconds(loads) \
        == pytest.approx(3.0 * t1)
    # the calibrated gate forwards its wired time_scale to the inner gate
    cal = cm.CalibratedReplanCostGate(g, EP, horizon_iters=8,
                                      default_tokens=256.0)
    assert cal.time_scale is None
    cal.time_scale = 2.0
    assert cal.layer_seconds(loads) == pytest.approx(2.0 * t1)


# --------------------------------------------------------------------------
# engine end-to-end (slow): parity, gate wiring, reconciliation, report
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


def _engine(cfg, params, profiler=None, cost_gate=None):
    from repro.placement import PlacementManager
    from repro.serving.engine import Engine
    from repro.workloads import IterationCostModel, VirtualClock
    mgr = PlacementManager(cfg, PlacementConfig(
        planner="least_loaded", replan_every=3, warmup_iters=2,
        min_gain=0.0, per_layer=True), EP, cost_gate=cost_gate)
    tel = Telemetry()
    eng = Engine(cfg, params, ReaLBConfig(gate_gamma=4), max_slots=3,
                 max_len=32, placement=mgr, telemetry=tel,
                 clock=VirtualClock(), cost_model=IterationCostModel(),
                 profiler=profiler)
    return eng, mgr, tel


@pytest.mark.slow
def test_engine_disabled_profiler_bitwise_parity(model):
    """An engine without a profiler produces bitwise-identical
    generations and identical plans/tables to one profiling every
    iteration (no cost gate wired, so nothing feeds back)."""
    cfg, params = model
    outs = []
    for profiled in (False, True):
        prof = Profiler(FlopByteLedger(cfg, ep=EP)) if profiled else None
        eng, mgr, tel = _engine(cfg, params, profiler=prof)
        assert (eng.profiler is NULL_PROFILER) == (not profiled)
        for r in _reqs(cfg, n=8, seed=5):
            eng.submit(r)
        eng.run()
        eng.drain_migrations()
        outs.append((
            {r.uid: list(r.generated) for r in eng.scheduler.finished},
            eng.migration_bytes_moved, mgr.n_migrations,
            [list(t.e2r) for t in mgr.tables],
        ))
    assert outs[0] == outs[1]


@pytest.mark.slow
def test_engine_profiler_reconciles_and_reports(model, tmp_path):
    """Virtual-clock run: the profiler's attribution reconciles, the
    drift EWMA is live, Telemetry.summary() surfaces mfu /
    model_flops_total / phase seconds, the profile JSON round-trips
    through profile_report with exit 0, and an injected drift (tampered
    phase seconds) exits 2."""
    from benchmarks import profile_report
    cfg, params = model
    tel_reg = MetricsRegistry()
    # share one registry between Telemetry and Profiler, like serve_bench
    tel = Telemetry(registry=tel_reg)
    prof = Profiler(FlopByteLedger(cfg, ep=EP), registry=tel_reg)
    eng, mgr, _ = _engine(cfg, params, profiler=prof)
    eng.telemetry = tel
    for r in _reqs(cfg, n=8, seed=5):
        eng.submit(r)
    eng.run()
    assert prof.n_iters > 0 and prof.fwd_s_total > 0
    assert sum(prof.phase_seconds().values()) == pytest.approx(
        prof.fwd_s_total)
    assert prof.time_scale() > 0 and prof.mfu() > 0

    s = tel.summary()
    assert s["mfu"] == pytest.approx(prof.mfu())
    assert s["model_flops_total"] == pytest.approx(prof.model_flops_total)
    assert s["costmodel_time_scale"] == pytest.approx(prof.time_scale())
    assert set(s["phase_seconds"]) <= set(PHASES)
    assert sum(s["phase_seconds"].values()) == pytest.approx(
        prof.fwd_s_total)
    # legacy keys untouched
    assert "ttft" in s and "migration_bytes_total" in s
    # an unprofiled telemetry grows no new keys
    assert "mfu" not in Telemetry().summary()

    p = tmp_path / "profile.json"
    doc = prof.write(str(p), metadata={"arm": "test"})
    assert doc["schema"] == "repro.profile.v1"
    assert profile_report.report(str(p)) == 0

    # injected drift: break the attribution invariant -> exit 2
    doc = json.loads(p.read_text())
    doc["phases"]["route"]["measured_s"] += 0.5
    p2 = tmp_path / "drift.json"
    p2.write_text(json.dumps(doc))
    assert profile_report.report(str(p2)) == 2

    # schema violation -> exit 1
    doc["schema"] = "bogus"
    p3 = tmp_path / "bad.json"
    p3.write_text(json.dumps(doc))
    assert profile_report.report(str(p3)) == 1


@pytest.mark.slow
def test_engine_wires_profiler_time_scale_into_cost_gate(model):
    """Engine init auto-wires the profiler's drift EWMA into an unwired
    cost gate (same idiom as the managers' bandwidth wiring); a gate the
    caller already calibrated is left alone."""
    from benchmarks import costmodel as cm
    cfg, params = model
    g = cm.MoEGeometry(cfg.name, cfg.d_model, cfg.moe.d_ff,
                       cfg.moe.num_experts, cfg.moe.top_k, 2)
    gate = cm.ReplanCostGate(g, EP, horizon_iters=3,
                             tokens_per_iter=64.0)
    assert gate.time_scale is None
    prof = Profiler(FlopByteLedger(cfg, ep=EP))
    eng, mgr, tel = _engine(cfg, params, profiler=prof, cost_gate=gate)
    assert gate.time_scale == prof.time_scale     # bound EWMA method
    assert gate._time_scale() == 1.0              # no observations yet
    # pre-calibrated gates are not overwritten
    gate2 = cm.ReplanCostGate(g, EP, horizon_iters=3,
                              tokens_per_iter=64.0, time_scale=1.5)
    _engine(cfg, params, profiler=prof, cost_gate=gate2)
    assert gate2.time_scale == 1.5
    # no profiler -> gate untouched
    gate3 = cm.ReplanCostGate(g, EP, horizon_iters=3,
                              tokens_per_iter=64.0)
    _engine(cfg, params, profiler=None, cost_gate=gate3)
    assert gate3.time_scale is None
