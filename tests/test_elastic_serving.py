"""Elastic serving: rank loss/rejoin as first-class serving events.

Host-side tests cover the ElasticCoordinator state machine, dead-rank-
masked planning, recovery-chunk priority, checkpoint re-materialization,
rejoin warm-up staged commit, the churn budget and weighted token
splitting; the slow engine tests drive the full event loop (fault
injection, degraded dispatch accounting, mid-recovery checkpoint
refusal) on a reduced model.
"""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

from repro.checkpoint import ckpt
from repro.configs import ReaLBConfig, get_config, reduced
from repro.configs.base import ReplicationConfig
from repro.launch.mesh import make_mesh
from repro.placement import PlacementManager
from repro.placement.migrate import MOE_WEIGHT_KEYS
from repro.replication import (ReplicaManager, ReplicaSet,
                               expand_moe_params, plan_replication)
from repro.runtime.fault_tolerance import FaultEvent, FaultInjector
from repro.serving.async_migrate import MigrationExecutor
from repro.serving.elastic import (STATE_DEGRADED, STATE_HEALTHY,
                                   STATE_SHRUNK, STATE_WARMING,
                                   ElasticCoordinator, zero_rank_slabs)
from repro.serving.telemetry import Telemetry

E, EP, SPR = 8, 4, 3          # 8 experts over 4 ranks, 1 spare slot each


def _rpcfg(**kw):
    base = dict(enabled=True, spare_per_rank=1, max_replicas=3,
                replan_every=1, warmup_iters=0, min_gain=0.0)
    base.update(kw)
    return ReplicationConfig(**base)


def _mgr(**kw):
    return ReplicaManager.from_geometry(E, _rpcfg(**kw), EP,
                                        bytes_per_expert=64)


def _params(rsets, d=4, n_layers=2, seed=0):
    """(logical tree, expanded tree) with stacked [L, S, d, d] weights."""
    rng = np.random.default_rng(seed)
    logical = {"blocks": {"layer0": {"moe": {
        k: rng.normal(size=(n_layers, E, d, d)).astype(np.float32)
        for k in MOE_WEIGHT_KEYS}}}}
    return logical, expand_moe_params(logical, rsets)


def _observe(mgr, load):
    mgr.observe(np.stack([np.asarray(load, np.float64),
                          np.zeros(E)])[None])


def _drain_all(mgr, co, plan, params):
    ex = MigrationExecutor(mgr, plan, bytes_per_iter=1 << 30,
                           priority_layers=co.recovery_layers(plan),
                           patch_fn=co.patch_params)
    while ex.draining:
        params, rep = ex.drain(params)
        co.on_layers_landed(plan, rep.layers)
    return params


def _save(mgr, params, tmp, step=0):
    ckpt.save(str(tmp), step, {
        "serving": {"params": params, "m_state": np.zeros((1, EP))},
        mgr.ckpt_group: mgr.state_dict()})


# --------------------------------------------------------------------------
# masked sets + dead-rank-aware planning
# --------------------------------------------------------------------------
def test_masked_set_drops_dead_replicas_and_reports_lost():
    rep_pos = np.zeros((E, 2), np.int32)
    for ex in range(E):
        rep_pos[ex] = (ex // 2) * SPR + (ex % 2)
    rep_pos[0, 1] = 2 * SPR + 2          # expert 0 replicated on rank 2
    n_rep = np.ones(E, np.int32)
    n_rep[0] = 2
    rs = ReplicaSet(rep_pos, n_rep, EP, SPR)

    alive = np.ones(EP, bool)
    alive[0] = False                     # rank 0 hosts experts 0, 1
    masked, lost = rs.masked(alive)
    # expert 0 survives on rank 2 (distinct-rank invariant), re-padded
    assert masked.n_rep[0] == 1
    assert masked.rep_pos[0, 0] == 2 * SPR + 2
    assert (masked.rep_pos[0] == 2 * SPR + 2).all()      # pad = primary
    # expert 1 was a singleton on rank 0: lost, row untouched
    assert lost.tolist() == [1]
    assert masked.rep_pos[1, 0] == rep_pos[1, 0]
    # everyone else untouched
    for ex in range(2, E):
        assert masked.n_rep[ex] == 1
        assert masked.rep_pos[ex, 0] == rep_pos[ex, 0]


def test_masked_requires_full_shape():
    rs = ReplicaSet.identity(E, EP, slots_per_rank=SPR)
    with pytest.raises(ValueError):
        rs.masked(np.ones(EP - 1, bool))


def test_planner_places_nothing_on_dead_ranks():
    load = np.ones(E)
    load[0] = 40.0
    alive = np.ones(EP, bool)
    alive[2] = False
    rs = plan_replication(load, EP, SPR, max_replicas=3, rank_alive=alive)
    assert not rs.hosts_rank(2)
    # every expert placed, distinct live ranks per expert
    for ex in range(E):
        ranks = rs.rep_pos[ex, :rs.n_rep[ex]] // SPR
        assert len(set(ranks.tolist())) == rs.n_rep[ex]
        assert alive[ranks].all()
    # the hot expert still gets replicas (on live ranks only)
    assert rs.n_rep[0] >= 2


def test_planner_dead_rank_capacity_floor():
    # 8 experts on 3 live ranks x 3 slots = 9 slots: tight but feasible
    alive = np.ones(EP, bool)
    alive[1] = False
    rs = plan_replication(np.ones(E), EP, SPR, max_replicas=3,
                          rank_alive=alive)
    assert not rs.hosts_rank(1)
    placed = set()
    for ex in range(E):
        placed.update(rs.rep_pos[ex, :rs.n_rep[ex]].tolist())
    assert len(placed) <= 9


def test_capacity_factor_ignores_dead_ranks():
    rs = ReplicaSet.identity(E, EP, slots_per_rank=SPR)
    load = np.ones(E)
    alive = np.ones(EP, bool)
    alive[3] = False
    # identity: rank 3 hosts experts 6,7 -> dead rank excluded from both
    # the peak and the mean of the live ranks
    f_all = rs.capacity_factor(load, margin=1.0, floor=0.0)
    f_live = rs.capacity_factor(load, margin=1.0, floor=0.0,
                                rank_alive=alive)
    assert f_all == pytest.approx(1.0)
    assert f_live == pytest.approx(1.0)


# --------------------------------------------------------------------------
# fault injection + slab zeroing
# --------------------------------------------------------------------------
def test_fault_injector_fires_once_in_order():
    fi = FaultInjector([(9, "rejoin", 2), FaultEvent(4, "fail", 2)])
    assert fi.due(3) == []
    evs = fi.due(5)
    assert [(e.it, e.kind, e.rank) for e in evs] == [(4, "fail", 2)]
    assert fi.due(5) == []               # fires exactly once
    assert not fi.exhausted
    evs = fi.due(20)
    assert [(e.kind, e.rank) for e in evs] == [("rejoin", 2)]
    assert fi.exhausted


def test_fault_event_rejects_unknown_kind():
    with pytest.raises(AssertionError):
        FaultEvent(1, "explode", 0)


def test_zero_rank_slabs_zeroes_exactly_that_rank():
    mgr = _mgr()
    _, params = _params(mgr.rsets)
    out = zero_rank_slabs(params, 2, SPR)
    for k in MOE_WEIGHT_KEYS:
        w = out["blocks"]["layer0"]["moe"][k]
        w0 = params["blocks"]["layer0"]["moe"][k]
        assert (w[:, 2 * SPR:3 * SPR] == 0).all()
        keep = [s for s in range(mgr.n_slots)
                if not 2 * SPR <= s < 3 * SPR]
        assert np.array_equal(w[:, keep], w0[:, keep])
        assert w is not w0               # original untouched


# --------------------------------------------------------------------------
# coordinator state machine
# --------------------------------------------------------------------------
def test_coordinator_requires_replica_manager():
    from repro.configs import PlacementConfig
    pm = PlacementManager.from_geometry(E, PlacementConfig(), EP)
    with pytest.raises(TypeError, match="ReplicaManager"):
        ElasticCoordinator(pm)


def test_fail_refusals():
    mgr = _mgr()
    co = ElasticCoordinator(mgr)         # no checkpoint
    # identity sets: every rank hosts singletons -> refused w/o ckpt,
    # and the refusal happens BEFORE any state mutation
    with pytest.raises(RuntimeError, match="no checkpoint"):
        co.fail_rank(1)
    assert mgr.rank_alive.all() and co.state == STATE_HEALTHY


def test_fail_last_rank_and_double_fail_refused(tmp_path):
    mgr = _mgr()
    _, params = _params(mgr.rsets)
    _save(mgr, params, tmp_path)
    co = ElasticCoordinator(mgr, ckpt_dir=str(tmp_path))
    for r in range(EP - 1):
        co.fail_rank(r)
    with pytest.raises(ValueError, match="already dead"):
        co.fail_rank(0)
    with pytest.raises(ValueError, match="last live rank"):
        co.fail_rank(EP - 1)


def test_replicated_only_loss_never_degrades():
    """Every expert on the lost rank has a surviving replica: the fail
    is a pure table flip — no lost experts, recovery_s == 0."""
    rpcfg = _rpcfg(spare_per_rank=2, max_replicas=2)
    mgr = ReplicaManager.from_geometry(E, rpcfg, EP, bytes_per_expert=64)
    # replicate everything: 2 replicas per expert fit 4 * 4 = 16 slots
    new = plan_replication(np.ones(E), EP, mgr.slots_per_rank,
                           max_replicas=2)
    assert (new.n_rep == 2).all()
    mgr.rsets[0] = new
    tel = Telemetry()
    co = ElasticCoordinator(mgr, telemetry=tel)   # no ckpt needed
    t0 = len(tel.recoveries)
    co.fail_rank(1)
    assert co.state == STATE_SHRUNK
    assert not co.recovering and co.lost_experts.size == 0
    assert co.last_recovery_s == 0.0
    assert len(tel.recoveries) == t0 + 1
    # survivors re-padded off the dead rank the same "iteration"
    assert not mgr.hosts_rank(1)


def test_kill_recover_rejoin_full_cycle(tmp_path):
    """fail -> degraded -> (recovery chunks land) -> shrunk -> rejoin ->
    warming -> healthy, with bitwise re-materialization from ckpt."""
    mgr = _mgr()
    logical, params = _params(mgr.rsets)
    co = ElasticCoordinator(mgr, ckpt_dir=str(tmp_path))

    # replicate the hot expert first so the distinct-rank invariant has
    # something to protect, then checkpoint the replicated layout
    load = np.ones(E)
    load[0] = 50.0
    _observe(mgr, load)
    plan = mgr.maybe_replan(1)
    assert plan is not None
    params = _drain_all(mgr, co, plan, params)
    _save(mgr, params, tmp_path)

    # pick a victim hosting at least one singleton primary
    rs = mgr.rset
    victim = next(r for r in range(EP)
                  if any(rs.n_rep[e] == 1 and rs.rep_pos[e, 0] // SPR == r
                         for e in range(E)))
    hot_ranks = set((rs.rep_pos[0, :rs.n_rep[0]] // SPR).tolist())

    params = co.fail_rank(victim, params)
    assert co.state == STATE_DEGRADED and co.recovering
    lost = set(co.lost_experts.tolist())
    assert lost
    # replicated expert 0 stays routable iff it had a surviving replica
    if victim in hot_ranks and len(hot_ranks) > 1:
        assert 0 not in lost
    # dead slabs zeroed; live experts never route to the dead rank
    w = params["blocks"]["layer0"]["moe"]["w_up"]
    assert (w[:, victim * SPR:(victim + 1) * SPR] == 0).all()
    for e in range(E):
        if e in lost:
            continue
        ranks = mgr.rset.rep_pos[e, :mgr.rset.n_rep[e]] // SPR
        assert victim not in ranks.tolist()
    # recovery layers are forced into the next (event-triggered) plan
    assert mgr.must_layers == set(co.lost)

    # mid-recovery: the saved-state cache must answer from the pre-kill
    # checkpoint; recovery drains through the executor with the patch
    _observe(mgr, load)
    plan2 = mgr.maybe_replan(2)
    assert plan2 is not None, "event replan must fire"
    assert co.recovery_layers(plan2) == [0]
    params = _drain_all(mgr, co, plan2, params)
    assert co.state == STATE_SHRUNK and not co.recovering
    assert co.last_recovery_s is not None and co.last_recovery_s >= 0
    assert mgr.must_layers == set()
    assert not mgr.rset.hosts_rank(victim)

    # bitwise parity: every routable slot holds the true logical rows
    for k in MOE_WEIGHT_KEYS:
        w = params["blocks"]["layer0"]["moe"][k]
        lw = logical["blocks"]["layer0"]["moe"][k]
        for e in range(E):
            for j in range(mgr.rset.n_rep[e]):
                slot = int(mgr.rset.rep_pos[e, j])
                assert np.array_equal(w[:, slot], lw[:, e]), (k, e, slot)

    # rejoin: plannable immediately, routable only after the plan lands
    co.rejoin_rank(victim)
    assert co.state == STATE_WARMING
    assert mgr.rank_alive[victim]
    assert not mgr.hosts_rank(victim)     # staged-commit: not yet routable
    _observe(mgr, load)
    plan3 = mgr.maybe_replan(3)
    assert plan3 is not None
    assert not mgr.hosts_rank(victim)     # still staged, still unroutable
    params = _drain_all(mgr, co, plan3, params)
    assert co.state == STATE_HEALTHY
    assert mgr.hosts_rank(victim)
    kinds = [e["kind"] for e in co.events]
    assert kinds == ["fail", "recovered", "rejoin", "warm"]


def test_rejoin_refused_while_live():
    mgr = _mgr()
    co = ElasticCoordinator(mgr)
    with pytest.raises(ValueError, match="already live"):
        co.rejoin_rank(0)


def test_effective_mesh_drops_dead_slices(tmp_path):
    jax = pytest.importorskip("jax")
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    mgr = ReplicaManager.from_geometry(4, _rpcfg(), 2, bytes_per_expert=8)
    _, params = _params([ReplicaSet.identity(E, EP, slots_per_rank=SPR)])
    mesh = make_mesh((1, 2), ("data", "model"))
    co = ElasticCoordinator(mgr, ckpt_dir=str(tmp_path))
    state = {"serving": {"params": {}, "m_state": np.zeros((1, 2))},
             mgr.ckpt_group: mgr.state_dict()}
    ckpt.save(str(tmp_path), 0, state)
    co.fail_rank(1)
    small = co.effective_mesh(mesh, lost_axis="model")
    assert small.devices.shape == (1, 1)


# --------------------------------------------------------------------------
# recovery-chunk priority + executor integration
# --------------------------------------------------------------------------
def test_recovery_chunks_drain_first():
    rpcfg = _rpcfg(per_layer=True)
    mgr = ReplicaManager.from_geometry(E, rpcfg, EP, bytes_per_expert=16,
                                       n_layers=3)
    # make all three layers want a replan (distinct hot experts)
    loads = np.ones((3, E))
    loads[0, 1] = 30.0
    loads[1, 3] = 30.0
    loads[2, 5] = 30.0
    mgr.observe(np.stack([np.stack([loads[l], np.zeros(E)])
                          for l in range(3)]))
    plan = mgr.maybe_replan(1)
    assert plan is not None
    layers = mgr.plan_layers(plan)
    assert len(layers) == 3
    prio = [layers[-1]]                  # pretend the last layer is lost
    ex = MigrationExecutor(mgr, plan, bytes_per_iter=1,
                           priority_layers=prio)
    order = [c.layer for c in ex.queue]
    assert order[0] == layers[-1]
    assert order[1:] == layers[:-1]      # stable within each class
    mgr.abort()


def test_patch_params_missing_checkpoint_raises(tmp_path):
    mgr = _mgr()
    _, params = _params(mgr.rsets)
    co = ElasticCoordinator(mgr, ckpt_dir=str(tmp_path))  # empty dir
    co.lost = {0: np.array([3])}
    plan = type("P", (), {"new_set": mgr.rset, "new_sets": None})()
    with pytest.raises(RuntimeError, match="no checkpoint"):
        co.patch_params(params, plan, [0])


def test_mid_recovery_checkpoint_state(tmp_path):
    """The coordinator reports ``recovering`` while lost experts are
    pending — the engine's checkpoint refusal keys off it."""
    mgr = _mgr()
    _, params = _params(mgr.rsets)
    _save(mgr, params, tmp_path)
    co = ElasticCoordinator(mgr, ckpt_dir=str(tmp_path))
    co.fail_rank(0)
    assert co.recovering                 # identity: rank 0 lost singletons
    _observe(mgr, np.ones(E))
    plan = mgr.maybe_replan(1)
    assert plan is not None
    params = _drain_all(mgr, co, plan, params)
    assert not co.recovering


# --------------------------------------------------------------------------
# churn budget
# --------------------------------------------------------------------------
def _perlayer_mgr(n_layers=3, **kw):
    rpcfg = _rpcfg(per_layer=True, **kw)
    return ReplicaManager.from_geometry(E, rpcfg, EP, bytes_per_expert=16,
                                        n_layers=n_layers)


def _skewed_obs(mgr, hots, mag=30.0):
    loads = np.ones((len(hots), E))
    for l, h in enumerate(hots):
        loads[l, h] = mag
    mgr.observe(np.stack([np.stack([loads[l], np.zeros(E)])
                          for l in range(len(hots))]))
    return loads


def test_churn_budget_caps_changed_layers():
    mgr = _perlayer_mgr(max_changed_layers=1)
    # layer 1 has the hottest expert -> highest predicted gain
    loads = np.ones((3, E))
    loads[0, 1] = 10.0
    loads[1, 3] = 60.0
    loads[2, 5] = 10.0
    mgr.observe(np.stack([np.stack([loads[l], np.zeros(E)])
                          for l in range(3)]))
    plan = mgr.maybe_replan(1)
    assert plan is not None
    assert mgr.plan_layers(plan) == [1]  # only the highest-gain layer
    mgr.abort()

    # unlimited budget: all three layers change
    mgr2 = _perlayer_mgr(max_changed_layers=0)
    mgr2.observe(np.stack([np.stack([loads[l], np.zeros(E)])
                           for l in range(3)]))
    plan2 = mgr2.maybe_replan(1)
    assert plan2 is not None
    assert len(mgr2.plan_layers(plan2)) == 3
    mgr2.abort()


def test_churn_budget_exempts_recovery_layers():
    mgr = _perlayer_mgr(max_changed_layers=1)
    loads = np.ones((3, E))
    loads[0, 1] = 60.0
    loads[1, 3] = 30.0
    loads[2, 5] = 20.0
    mgr.observe(np.stack([np.stack([loads[l], np.zeros(E)])
                          for l in range(3)]))
    # layer 2 carries lost experts: must replan on top of the budget
    mgr.must_layers = {2}
    mgr.request_replan()
    plan = mgr.maybe_replan(1)
    assert plan is not None
    changed = set(mgr.plan_layers(plan))
    assert 2 in changed                  # recovery layer always included
    assert len(changed) <= 2             # budget 1 + the mandatory layer
    mgr.abort()


def test_event_replan_bypasses_cadence_and_gain():
    mgr = _mgr(replan_every=1000, min_gain=0.9, warmup_iters=0)
    _observe(mgr, np.ones(E) + np.arange(E) * 0.01)
    # off-cadence, gain below min_gain: nothing fires normally
    assert mgr.maybe_replan(7) is None
    mgr.request_replan()
    plan = mgr.maybe_replan(8)           # event bypasses both guards
    assert plan is not None
    mgr.abort()
    # the request was consumed
    assert mgr.maybe_replan(9) is None


# --------------------------------------------------------------------------
# weighted per-replica token splitting
# --------------------------------------------------------------------------
def test_split_schedule_equal_matches_round_robin():
    rs = ReplicaSet.identity(E, EP, slots_per_rank=SPR, max_replicas=3)
    rep_pos = rs.rep_pos.copy()
    n_rep = rs.n_rep.copy()
    rep_pos[0, 1], n_rep[0] = 2 * SPR + 2, 2
    rep_pos[1, 1], rep_pos[1, 2], n_rep[1] = 3 * SPR + 2, 1 * SPR + 2, 3
    rs = ReplicaSet(rep_pos, n_rep, EP, SPR)
    sched = rs.split_schedule()
    q = ReplicaSet.SPLIT_QUANTUM
    assert sched.shape == (E, q)
    for e in range(E):
        want = np.arange(q) % max(int(n_rep[e]), 1)
        assert np.array_equal(sched[e], want), e


def test_split_schedule_weighted_quota():
    rep_pos = np.zeros((E, 3), np.int32)
    for ex in range(E):
        rep_pos[ex] = (ex // 2) * SPR + (ex % 2)
    rep_pos[0] = [0, 2 * SPR + 2, 3 * SPR + 2]
    n_rep = np.ones(E, np.int32)
    n_rep[0] = 3
    rs = ReplicaSet(rep_pos, n_rep, EP, SPR)
    w = np.zeros((E, 3))
    w[:, 0] = 1.0
    w[0] = [3.0, 2.0, 1.0]               # 6 units over Q=12 -> 6/4/2
    sched = rs.split_schedule(w)
    counts = np.bincount(sched[0], minlength=3)
    assert counts.tolist() == [6, 4, 2]
    # interleaved, not blocked: the first half already mixes replicas
    assert len(set(sched[0, :6].tolist())) == 3
    # singletons always schedule replica 0
    assert (sched[1:] == 0).all()


def test_residual_split_weights_shed_to_spare_capacity():
    rep_pos = np.zeros((E, 2), np.int32)
    for ex in range(E):
        rep_pos[ex] = (ex // 2) * SPR + (ex % 2)
    rep_pos[0, 1] = 2 * SPR + 2
    n_rep = np.ones(E, np.int32)
    n_rep[0] = 2
    rs = ReplicaSet(rep_pos, n_rep, EP, SPR)
    load = np.ones(E)
    load[0] = 10.0
    load[4], load[5] = 6.0, 6.0          # rank 2 (host of the replica) busy
    w = rs.residual_split_weights(load)
    # rank 2 is loaded -> the replica there gets LESS than the primary
    assert w[0, 0] > w[0, 1] > 0
    # symmetric case: idle rank 3 instead
    rep_pos2 = rep_pos.copy()
    rep_pos2[0, 1] = 3 * SPR + 2
    rs2 = ReplicaSet(rep_pos2, n_rep, EP, SPR)
    w2 = rs2.residual_split_weights(load)
    assert w2[0, 1] > w[0, 1]            # idler host -> bigger share
    # dead host -> zero share
    alive = np.ones(EP, bool)
    alive[3] = False
    w3 = rs2.residual_split_weights(load, rank_alive=alive)
    assert w3[0, 1] == 0.0 and w3[0, 0] > 0


def test_weighted_device_tables_have_schedule_entry():
    mgr = _mgr(weighted_split=True)
    tables = mgr.device_tables()
    assert len(tables) == 4
    q = ReplicaSet.SPLIT_QUANTUM
    assert tables[3].shape == (E, q)
    # before any observation: equal-share schedule
    assert (tables[3] == 0).all()        # identity sets: n_rep == 1
    assert mgr.wants_table_refresh(1)    # replan_every == 1
    mgr_plain = _mgr()
    assert len(mgr_plain.device_tables()) == 3
    assert not mgr_plain.wants_table_refresh(1)

    mgr_pl = _perlayer_mgr(weighted_split=True)
    t = mgr_pl.device_tables()
    assert len(t) == 4 and t[3].shape == (3, E, q)


# --------------------------------------------------------------------------
# telemetry + degraded accounting
# --------------------------------------------------------------------------
def test_telemetry_availability_and_recovery():
    from repro.serving.engine import IterStats
    tel = Telemetry()
    assert tel.availability == 1.0

    def it(n_unroutable=0, lost=0.0):
        return IterStats(n_active=1, tokens=4, ib_global=1.0,
                         fp4_ranks=0.0, gate_open=0.0,
                         n_unroutable=n_unroutable, lost_tokens=lost)

    for _ in range(8):
        tel.record_iter(it())
    for _ in range(2):
        tel.record_iter(it(n_unroutable=2, lost=3.0))
    tel.record_recovery(0.25)
    assert tel.degraded_iters == 2
    assert tel.availability == pytest.approx(0.8)
    assert tel.lost_tokens_total == pytest.approx(6.0)
    s = tel.summary()
    assert s["availability"] == pytest.approx(0.8)
    assert s["degraded_iters"] == 2
    assert s["n_recoveries"] == 1
    assert s["recovery_s"] == pytest.approx(0.25)
    assert Telemetry().summary()["recovery_s"] is None


def test_lost_token_count_per_layer_and_shared():
    mgr = _perlayer_mgr(n_layers=2)
    co = ElasticCoordinator(mgr)
    es = np.zeros((2, 2, E))
    es[0, 0, 3] = 5.0
    es[1, 0, 3] = 7.0
    es[1, 0, 6] = 2.0
    assert co.lost_token_count(es) == 0.0
    co.lost = {1: np.array([3, 6])}
    assert co.lost_token_count(es) == pytest.approx(9.0)

    mgr_s = _mgr()
    co_s = ElasticCoordinator(mgr_s)
    co_s.lost = {0: np.array([3])}
    assert co_s.lost_token_count(es) == pytest.approx(12.0)


# --------------------------------------------------------------------------
# engine end-to-end (slow): fault injection under load
# --------------------------------------------------------------------------
@pytest.fixture(scope="module")
def model():
    import jax
    import repro.models.transformer as tf
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


@pytest.mark.slow
def test_engine_weighted_split_identity_bitwise(model):
    """The 4-table traced path with an equal-share schedule generates
    exactly what the 3-table (and table-free) engines do."""
    from repro.serving.engine import Engine
    cfg, params = model
    rcfg = ReaLBConfig(gate_gamma=4)
    eng0 = Engine(cfg, params, rcfg, max_slots=3, max_len=32, virtual_ep=4)
    for r in _reqs(cfg):
        eng0.submit(r)
    g0 = [r.generated for r in sorted(eng0.run(), key=lambda r: r.uid)]

    mgr = ReplicaManager(cfg, ReplicationConfig(
        enabled=False, spare_per_rank=1, weighted_split=True), 4)
    eng1 = Engine(cfg, expand_moe_params(params, mgr.rset), rcfg,
                  max_slots=3, max_len=32, placement=mgr)
    for r in _reqs(cfg):
        eng1.submit(r)
    g1 = [r.generated for r in sorted(eng1.run(), key=lambda r: r.uid)]
    assert g0 == g1


@pytest.mark.slow
def test_engine_kill_rejoin_under_load(model, tmp_path):
    """Scripted kill + rejoin while serving: the engine masks the dead
    rank the same iteration, refuses checkpoints mid-recovery, streams
    recovery chunks ahead of optimization, and ends healthy."""
    from repro.serving.engine import Engine
    cfg, params = model
    mgr = ReplicaManager(cfg, ReplicationConfig(
        replan_every=4, warmup_iters=2, min_gain=0.0, per_layer=True,
        spare_per_rank=1, max_replicas=2), 4)
    tel = Telemetry()
    co = ElasticCoordinator(mgr, ckpt_dir=str(tmp_path), telemetry=tel)
    # kill BEFORE the first cadence replan (it=4): the sets are still
    # identity, so rank 2's primaries are singletons and the loss opens
    # a real degraded window (a later kill could land after replication
    # already covered them)
    fi = FaultInjector([(3, "fail", 2), (14, "rejoin", 2)])
    # per-layer chunks + a 1-byte budget: one recovery chunk lands per
    # iteration, so the degraded window spans recorded iterations
    eng = Engine(cfg, expand_moe_params(params, mgr.rsets),
                 ReaLBConfig(gate_gamma=4), max_slots=3, max_len=32,
                 placement=mgr, telemetry=tel, migrate_async=True,
                 migrate_bytes_per_iter=1,
                 elastic=co, fault_injector=fi)
    for r in _reqs(cfg, n=10, new=6):
        eng.submit(r)
    eng.save_checkpoint(str(tmp_path), 0)     # pre-kill re-mat source

    # drive manually so the mid-recovery refusal is observable
    saw_refusal = False
    for _ in range(200):
        if eng.scheduler.idle:
            break
        eng.step()
        if co.recovering and not saw_refusal:
            # refused either way: the recovery plan is draining AND the
            # params still hold zeroed slabs
            with pytest.raises(RuntimeError,
                               match="draining|mid-recovery"):
                eng.save_checkpoint(str(tmp_path), 1)
            saw_refusal = True
    assert eng.scheduler.idle
    eng.drain_migrations()
    assert fi.exhausted
    assert saw_refusal, "the kill never produced a degraded window"
    # recovery completed and was stamped
    assert not co.recovering
    assert co.last_recovery_s is not None and co.last_recovery_s >= 0.0
    assert tel.recoveries
    assert tel.summary()["recovery_s"] is not None
    assert tel.degraded_iters >= 1
    assert tel.availability < 1.0
    # degraded iterations were visible in the stats stream
    assert any(s.n_unroutable > 0 for s in eng.stats)
    # the rejoined rank ended healthy (possibly still warming if the
    # tail had no replan; drain state must at least be consistent)
    assert mgr.rank_alive.all()
    assert co.state in (STATE_HEALTHY, STATE_WARMING)
    # the dedicated mid-recovery refusal (no migration draining): a
    # pending lost expert alone blocks the save
    co.lost = {0: np.array([1])}
    with pytest.raises(RuntimeError, match="mid-recovery"):
        eng.save_checkpoint(str(tmp_path), 1)
    co.lost = {}
    # a healthy checkpoint can be written again after recovery
    eng.save_checkpoint(str(tmp_path), 2)
