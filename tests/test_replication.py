"""Replication subsystem: replica sets, the EPLB-style planner, slab
add/drop migration with the staged-commit consistency rule, the
token-split MoE dispatch (identity ≡ bitwise, replicated ≡ allclose with
post-split stats), the cost-model replan gate and the serving engine's
replica loop + checkpoint round-trips."""
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import (ReaLBConfig, ReplicationConfig, get_config,
                           reduced)
from repro.core import ep_moe
from repro.placement.table import PlacementTable
from repro.replication import (ReplicaManager, ReplicaSet, diff,
                               expand_moe_params, plan_replication)


@pytest.fixture(scope="module")
def setup():
    cfg = reduced(get_config("olmoe-1b-7b"))
    e = cfg.moe
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    D, E, F = cfg.d_model, e.num_experts, e.d_ff
    p = {"router": jax.random.normal(ks[0], (D, E)) * 0.2,
         "w_gate": jax.random.normal(ks[1], (E, D, F)) / np.sqrt(D),
         "w_up": jax.random.normal(ks[2], (E, D, F)) / np.sqrt(D),
         "w_down": jax.random.normal(ks[3], (E, F, D)) / np.sqrt(F)}
    x = jax.random.normal(ks[4], (2, 16, D)) * 0.5
    mod = jax.random.bernoulli(ks[5], 0.6, (2, 16))
    return cfg, p, x, mod


def hot_expert_set(e: int = 8, ep: int = 4, s_loc: int = 3) -> ReplicaSet:
    """Expert 0 replicated onto rank 2's spare slot; everything else in
    identity-with-spare layout."""
    rep_pos = np.zeros((e, 2), np.int32)
    for ex in range(e):
        rep_pos[ex] = (ex // 2) * s_loc + (ex % 2)
    rep_pos[0, 1] = 2 * s_loc + 2
    n_rep = np.ones(e, np.int32)
    n_rep[0] = 2
    return ReplicaSet(rep_pos, n_rep, ep, s_loc)


def expand_flat(p, rset):
    """Expand a flat single-layer param dict into slot order."""
    wrapped = {"blocks": {"layer0": {"moe": p}}}
    return expand_moe_params(wrapped, rset)["blocks"]["layer0"]["moe"]


# --------------------------------------------------------------------------
# replica set
# --------------------------------------------------------------------------
def test_identity_set_is_bijective_placement():
    rs = ReplicaSet.identity(8, 4)
    assert rs.is_bijective and rs.n_spare == 0
    assert np.array_equal(rs.slot_owner, np.arange(8))
    t = PlacementTable.identity(8, 4)
    rs2 = ReplicaSet.from_placement(t)
    assert np.array_equal(rs2.rep_pos[:, 0], t.pos)


def test_identity_with_spare_layout():
    rs = ReplicaSet.identity(8, 4, slots_per_rank=3, max_replicas=2)
    assert rs.n_slots == 12 and rs.n_spare == 4 and not rs.is_bijective
    own = rs.slot_owner
    assert (own[[2, 5, 8, 11]] == -1).all()         # spare tails empty
    assert np.array_equal(own[[0, 1, 3, 4]], [0, 1, 2, 3])


def test_set_rejects_same_rank_replicas():
    # expert 0's two replicas both land on rank 0 (slots 0 and 1 of a
    # 3-slot slab); splitting within one rank balances nothing
    rep_pos = np.array([[0, 1]] + [[e + 3, e + 3] for e in range(7)],
                       np.int32)
    n_rep = np.ones(8, np.int32)
    n_rep[0] = 2
    with pytest.raises(ValueError, match="one rank"):
        ReplicaSet(rep_pos, n_rep, 4, 3)


def test_set_rejects_shared_slot():
    rep_pos = np.arange(8, dtype=np.int32)[:, None].repeat(2, 1)
    rep_pos[0, 1] = 3                                # also expert 3's slot
    n_rep = np.ones(8, np.int32)
    n_rep[0] = 2
    with pytest.raises(ValueError, match="distinct"):
        ReplicaSet(rep_pos, n_rep, 4, 2)


def test_post_split_rank_and_slot_loads():
    rs = hot_expert_set()
    load = np.zeros(8)
    load[0] = 10.0
    load[4] = 4.0
    rl = rs.rank_loads(load)
    np.testing.assert_allclose(rl, [5.0, 0.0, 9.0, 0.0])
    sl = rs.slot_loads(load)
    assert sl[rs.rep_pos[0, 0]] == 5.0 and sl[rs.rep_pos[0, 1]] == 5.0
    mat = rs.ownership_matrix()
    np.testing.assert_allclose(mat.sum(1), np.ones(8))
    np.testing.assert_allclose(load @ mat, rl)


# --------------------------------------------------------------------------
# planner
# --------------------------------------------------------------------------
def test_planner_replicates_hottest_and_balances():
    load = np.array([10, 8, 1, 1, 1, 1, 1, 1.0])
    rs = plan_replication(load, 4, 3, max_replicas=2)
    assert rs.n_rep[0] == 2 and rs.n_rep[1] == 2
    ident = ReplicaSet.identity(8, 4, slots_per_rank=3, max_replicas=2)
    assert rs.rank_loads(load).max() < ident.rank_loads(load).max()


def test_planner_beats_bijective_on_single_hot_expert():
    """One expert hotter than a rank's fair share: un-placeable by any
    bijection, but replication splits it below that bound."""
    load = np.array([40, 1, 1, 1, 1, 1, 1, 1.0])
    from repro.placement import plan_least_loaded
    biject = plan_least_loaded(load, 4)
    rs = plan_replication(load, 4, 3, max_replicas=4)
    assert rs.rank_loads(load).max() < biject.rank_loads(load).max()
    assert rs.rank_loads(load).max() < load[0]       # actually split


def test_planner_vision_weight_prefers_vision_heavy():
    load = np.array([5.0, 5.0, 1, 1, 1, 1, 1, 1])
    vis = np.array([0.0, 5.0, 0, 0, 0, 0, 0, 0])
    rs = plan_replication(load, 4, 3, max_replicas=2, vis=vis,
                          vis_weight=2.0)
    # only 4 spare slots; the vision-heavy twin must be replicated
    assert rs.n_rep[1] == 2


def test_planner_deterministic_and_valid():
    rng = np.random.default_rng(0)
    load = rng.random(16)
    a = plan_replication(load, 4, 5, max_replicas=3)
    b = plan_replication(load.copy(), 4, 5, max_replicas=3)
    assert np.array_equal(a.rep_pos, b.rep_pos)
    assert np.array_equal(a.n_rep, b.n_rep)
    assert int(a.n_rep.sum()) <= a.n_slots


# --------------------------------------------------------------------------
# migration (diff / expand)
# --------------------------------------------------------------------------
def test_diff_identity_is_noop():
    rs = ReplicaSet.identity(8, 4, slots_per_rank=3, max_replicas=2)
    plan = diff(rs, rs, bytes_per_expert=10)
    assert plan.is_noop and plan.moved_bytes == 0


def test_diff_add_replica_sources_primary_cross_rank():
    old = ReplicaSet.identity(8, 4, slots_per_rank=3, max_replicas=2)
    new = hot_expert_set()
    plan = diff(old, new, bytes_per_expert=7)
    s = 2 * 3 + 2                                   # rank 2's spare slot
    assert plan.changed_slots.tolist() == [s]
    assert plan.crossrank_slots.tolist() == [s]
    assert plan.gather_idx[s] == new.rep_pos[0, 0]  # copy of the primary
    assert plan.moved_bytes == 7


def test_diff_retire_is_free_and_same_rank_copy_zero_bytes():
    old = hot_expert_set()
    # retire expert 0's replica -> back to identity-with-spare
    ident = ReplicaSet.identity(8, 4, slots_per_rank=3, max_replicas=2)
    plan = diff(old, ident, bytes_per_expert=7)
    assert plan.is_noop and plan.moved_bytes == 0   # slot just goes dark
    # move expert 4 into rank 2's spare (same rank as its primary):
    # an HBM-local copy, no cross-rank bytes
    rep_pos = ident.rep_pos.copy()
    n_rep = ident.n_rep.copy()
    rep_pos[4, 1] = 2 * 3 + 2
    n_rep[4] = 2
    with pytest.raises(ValueError, match="one rank"):
        ReplicaSet(rep_pos, n_rep, 4, 3)            # invalid: same rank
    rep_pos[4, 1] = 3 * 3 + 2                       # rank 3 instead
    new = ReplicaSet(rep_pos, n_rep, 4, 3)
    plan = diff(ident, new, bytes_per_expert=7)
    assert plan.moved_bytes == 7 and plan.n_moved == 1


def test_expand_moe_params_slot_layout():
    rs = hot_expert_set()
    w = np.arange(2 * 8 * 3 * 5, dtype=np.float32).reshape(2, 8, 3, 5)
    params = {"blocks": {"layer0": {"moe": {
        "router": np.zeros((3, 8)), "w_gate": w, "w_up": w + 1,
        "w_down": np.swapaxes(w, 2, 3)}}}}
    out = expand_moe_params(params, rs)
    got = out["blocks"]["layer0"]["moe"]["w_gate"]
    assert got.shape == (2, 12, 3, 5)
    own = rs.slot_owner
    for s in range(12):
        want = w[:, own[s]] if own[s] >= 0 else 0.0
        np.testing.assert_array_equal(got[:, s], want)
    # router stays logical
    assert out["blocks"]["layer0"]["moe"]["router"] is \
        params["blocks"]["layer0"]["moe"]["router"]


# --------------------------------------------------------------------------
# token-split MoE layer
# --------------------------------------------------------------------------
def test_occurrence_index_round_robin():
    flat = jnp.asarray([3, 0, 3, 3, 0, 1], jnp.int32)
    occ = np.asarray(ep_moe._occurrence_index(flat, 4))
    assert occ.tolist() == [0, 0, 1, 2, 1, 0]


@pytest.mark.parametrize("mode", ["dispatch", "broadcast"])
def test_identity_replication_bitwise_equal(setup, mode):
    """The replica-threaded layer with the identity set must be bitwise-
    identical to the default (placement=None) path."""
    cfg, p, x, mod = setup
    rcfg = ReaLBConfig(gate_gamma=10 ** 9)
    m = jnp.full((1, 4), 0.9)
    ident = ep_moe.identity_replication(cfg.moe.num_experts, 4)
    y0, m0, aux0 = ep_moe.ep_moe_forward(p, x, cfg, rcfg, m, mod, mode=mode)
    y1, m1, aux1 = ep_moe.ep_moe_forward(p, x, cfg, rcfg, m, mod, mode=mode,
                                         placement=ident)
    assert np.array_equal(np.asarray(y0), np.asarray(y1))
    assert np.array_equal(np.asarray(m0), np.asarray(m1))
    for k in ("load_d", "vis_d", "drop_frac", "lb_loss", "split_frac"):
        assert np.array_equal(np.asarray(aux0[k]), np.asarray(aux1[k])), k
    assert float(aux1["split_frac"]) == 0.0


@pytest.mark.parametrize("mode", ["dispatch", "broadcast"])
def test_replicated_dispatch_allclose_with_split_stats(setup, mode):
    """A replicated hot expert yields allclose outputs (replicas hold the
    same weights) while the physical loads split across its slots."""
    cfg, p, x, mod = setup
    p = dict(p, router=p["router"].at[:, 0].add(4.0))   # expert 0 hot
    rcfg = ReaLBConfig(gate_gamma=10 ** 9)
    rs = hot_expert_set()
    m = jnp.full((1, 4), 0.9)
    p_rep = dict(expand_flat(p, rs), router=p["router"])
    place = tuple(jnp.asarray(a) for a in rs.as_arrays())
    y0, _, aux0 = ep_moe.ep_moe_forward(p, x, cfg, rcfg, m, mod, mode=mode)
    y1, _, aux1 = ep_moe.ep_moe_forward(p_rep, x, cfg, rcfg, m, mod,
                                        mode=mode, placement=place)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), rtol=2e-5,
                               atol=2e-5)
    el = np.asarray(aux1["expert_load"])
    sl = np.asarray(aux1["slot_load"])
    # logical stats are replication-invariant; slot stats sum to them
    np.testing.assert_allclose(el, np.asarray(aux0["expert_load"]))
    np.testing.assert_allclose(sl.sum(), el.sum())
    # expert 0's load round-robins across its two replica slots
    a, b = sl[rs.rep_pos[0, 0]], sl[rs.rep_pos[0, 1]]
    assert a + b == el[0] and abs(a - b) <= 1.0
    if el[0] >= 2:
        assert float(aux1["split_frac"]) > 0.0
    # post-split rank loads match the host-side equal-split model up to
    # the round-robin integer remainder (±1 assignment per replica)
    np.testing.assert_allclose(np.asarray(aux1["load_d"]),
                               rs.rank_loads(el), atol=1.0)
    # empty spare slots never receive tokens
    assert (sl[rs.slot_owner < 0] == 0).all()


def test_replicated_split_ignores_padding(setup):
    """Chunk-bucket padding must not shift which replica serves a real
    token: the post-split slot stats (and load_d) of a padded batch equal
    those of the truncated batch exactly, with the hot expert split."""
    cfg, p, x, mod = setup
    p = dict(p, router=p["router"].at[:, 0].add(4.0))
    rcfg = ReaLBConfig(gate_gamma=10 ** 9)
    rs = hot_expert_set()
    m = jnp.full((1, 4), 0.9)
    p_rep = dict(expand_flat(p, rs), router=p["router"])
    place = tuple(jnp.asarray(a) for a in rs.as_arrays())
    x_pad = x.at[:, 8:].set(0.0)          # adversarial: identical padding
    valid = jnp.zeros(x.shape[:2], bool).at[:, :8].set(True)
    y_pad, _, aux_pad = ep_moe.ep_moe_forward(
        p_rep, x_pad, cfg, rcfg, m, mod, mode="dispatch", valid=valid,
        placement=place)
    y_ref, _, aux_ref = ep_moe.ep_moe_forward(
        p_rep, x_pad[:, :8], cfg, rcfg, m, mod[:, :8], mode="dispatch",
        placement=place)
    for k in ("slot_load", "slot_vis", "load_d", "vis_d", "split_frac"):
        np.testing.assert_array_equal(np.asarray(aux_pad[k]),
                                      np.asarray(aux_ref[k]), err_msg=k)
    assert float(aux_pad["split_frac"]) > 0.0
    np.testing.assert_allclose(np.asarray(y_pad[:, :8]),
                               np.asarray(y_ref), rtol=2e-5, atol=2e-5)


def test_replicated_dispatch_flattens_policy_loads(setup):
    """With the hot expert split, the max policy-rank load (what IB_d and
    the FP4 gate see) must not exceed the unsplit one."""
    cfg, p, x, mod = setup
    p = dict(p, router=p["router"].at[:, 0].add(4.0))
    rcfg = ReaLBConfig(gate_gamma=10 ** 9)
    m = jnp.full((1, 4), 0.9)
    rs = hot_expert_set()
    p_rep = dict(expand_flat(p, rs), router=p["router"])
    place = tuple(jnp.asarray(a) for a in rs.as_arrays())
    _, _, aux0 = ep_moe.ep_moe_forward(p, x, cfg, rcfg, m, mod,
                                       mode="dispatch")
    _, _, aux1 = ep_moe.ep_moe_forward(p_rep, x, cfg, rcfg, m, mod,
                                       mode="dispatch", placement=place)
    el = np.asarray(aux0["expert_load"])
    # rank 0 held experts 0+1 before; after the split half of expert 0
    # moved to rank 2
    l0 = np.asarray(aux0["load_d"])
    l1 = np.asarray(aux1["load_d"])
    assert l1[0] < l0[0]
    assert l1.sum() == l0.sum() == el.sum()


# --------------------------------------------------------------------------
# manager (staged commit, gating, state round-trip)
# --------------------------------------------------------------------------
def _skew_stats(e=8, hot=10.0):
    es = np.zeros((4, 2, e))
    es[:, 0] = np.array([hot, hot * 0.8, 1, 1, 1, 1, 1, 1.0])
    es[:, 1] = es[:, 0] * 0.7
    return es


def test_manager_stages_then_commits():
    rp = ReplicationConfig(replan_every=2, warmup_iters=1, min_gain=0.0)
    mgr = ReplicaManager.from_geometry(8, rp, 4, bytes_per_expert=7)
    mgr.observe(_skew_stats())
    assert mgr.maybe_replan(1) is None              # off-cadence
    before = mgr.device_tables()
    plan = mgr.maybe_replan(2)
    assert plan is not None and plan.n_moved > 0
    # consistency rule: the routable set is unchanged until commit
    after_stage = mgr.device_tables()
    for a, b in zip(before, after_stage):
        assert np.array_equal(a, b)
    assert mgr.n_migrations == 0
    assert mgr.maybe_replan(4) is None              # one plan in flight
    mgr.commit(plan)
    assert mgr.n_migrations == 1
    assert mgr.migrated_bytes == plan.moved_bytes > 0
    assert (mgr.rset.n_rep == plan.new_set.n_rep).all()
    # replanning from the same prediction is a no-op now
    mgr.observe(_skew_stats())
    assert mgr.maybe_replan(6) is None


def test_manager_abort_keeps_old_set():
    rp = ReplicationConfig(replan_every=1, warmup_iters=1, min_gain=0.0)
    mgr = ReplicaManager.from_geometry(8, rp, 4)
    mgr.observe(_skew_stats())
    plan = mgr.maybe_replan(1)
    assert plan is not None
    mgr.abort()
    assert mgr.n_migrations == 0 and (mgr.rset.n_rep == 1).all()
    # a later cadence point can restage
    assert mgr.maybe_replan(2) is not None


def test_manager_cost_gate_blocks_unprofitable_replans():
    class Reject:
        calls = 0

        def accept(self, old, new, n_moved):
            self.calls += 1
            return False

    gate = Reject()
    rp = ReplicationConfig(replan_every=1, warmup_iters=1, min_gain=0.0)
    mgr = ReplicaManager.from_geometry(8, rp, 4, cost_gate=gate)
    mgr.observe(_skew_stats())
    assert mgr.maybe_replan(1) is None
    assert gate.calls == 1 and mgr.n_migrations == 0


def test_costmodel_replan_gate_amortization():
    """Satellite: the ReplanCostGate accepts a replan exactly when the
    predicted layer-time savings over the horizon beat migration_time."""
    from benchmarks import costmodel as cm
    g = cm.KIMI_VL
    gate = cm.ReplanCostGate(g, 8, horizon_iters=100)
    skew = np.array([8.0, 1, 1, 1, 1, 1, 1, 1])
    flat = np.full(8, skew.sum() / 8)
    assert gate.accept(skew, flat, 4)               # big win, few slabs
    assert not gate.accept(skew, skew * 0.999, 64)  # no win, many slabs
    assert gate.accept(skew, flat, 0)               # free moves always ok
    # a one-iteration horizon cannot amortize a full-stack migration
    assert not cm.ReplanCostGate(g, 8, horizon_iters=1).accept(
        skew, flat, 16)


def test_manager_state_roundtrip():
    rp = ReplicationConfig(replan_every=1, warmup_iters=1, min_gain=0.0)
    mgr = ReplicaManager.from_geometry(8, rp, 4, bytes_per_expert=5)
    mgr.observe(_skew_stats())
    plan = mgr.maybe_replan(1)
    mgr.commit(plan)
    mgr.observe_slots(np.ones((2, 2, mgr.n_slots)))
    sd = {k: np.asarray(v) for k, v in mgr.state_dict().items()}
    m2 = ReplicaManager.from_geometry(8, rp, 4, bytes_per_expert=5)
    m2.load_state_dict(sd)
    assert np.array_equal(m2.rset.rep_pos, mgr.rset.rep_pos)
    assert np.array_equal(m2.rset.n_rep, mgr.rset.n_rep)
    assert m2.n_migrations == mgr.n_migrations
    assert np.array_equal(m2.cum_slot_load, mgr.cum_slot_load)
    assert m2.predictor.n_obs == mgr.predictor.n_obs
    m2.reset()
    assert (m2.rset.n_rep == 1).all() and m2.n_migrations == 0


# --------------------------------------------------------------------------
# engine end-to-end (identity bitwise, live replication, checkpoints)
# --------------------------------------------------------------------------
@pytest.fixture(scope="module")
def model():
    cfg = reduced(get_config("olmoe-1b-7b"), n_layers=2)
    import repro.models.transformer as tf
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


def _bias_router(params, hot=3.0):
    out = dict(params)
    blocks = dict(out["blocks"])
    for lname, lp in blocks.items():
        if isinstance(lp, dict) and "moe" in lp:
            lp = dict(lp)
            moe = dict(lp["moe"])
            moe["router"] = moe["router"].at[..., 0].add(hot) \
                .at[..., 1].add(hot * 0.7)
            lp["moe"] = moe
        blocks[lname] = lp
    out["blocks"] = blocks
    return out


@pytest.mark.slow
def test_engine_identity_replication_matches_baseline(model):
    """A replica engine that never replans generates exactly what a
    manager-free engine does — with and without spare slots."""
    from repro.serving.engine import Engine
    cfg, params = model
    rcfg = ReaLBConfig(gate_gamma=4)

    eng0 = Engine(cfg, params, rcfg, max_slots=3, max_len=32, virtual_ep=4)
    for r in _reqs(cfg):
        eng0.submit(r)
    g0 = [r.generated for r in sorted(eng0.run(), key=lambda r: r.uid)]

    for spare, reps in ((0, 1), (1, 2)):
        mgr = ReplicaManager(cfg, ReplicationConfig(
            enabled=False, spare_per_rank=spare, max_replicas=reps), 4)
        p = expand_moe_params(params, mgr.rset) if spare else params
        eng1 = Engine(cfg, p, rcfg, max_slots=3, max_len=32, placement=mgr)
        for r in _reqs(cfg):
            eng1.submit(r)
        g1 = [r.generated for r in sorted(eng1.run(), key=lambda r: r.uid)]
        assert g0 == g1, (spare, reps)
        assert mgr.n_migrations == 0


@pytest.mark.slow
def test_engine_refuses_unexpanded_params(model):
    from repro.serving.engine import Engine
    cfg, params = model
    mgr = ReplicaManager(cfg, ReplicationConfig(spare_per_rank=1), 4)
    with pytest.raises(AssertionError, match="expand_moe_params"):
        Engine(cfg, params, ReaLBConfig(), max_slots=3, max_len=32,
               placement=mgr)


@pytest.mark.slow
def test_engine_aborts_staged_plan_on_failed_apply(model, monkeypatch):
    """A failed slab gather must not leave the manager stuck with a
    pending plan: the engine aborts it, the old set stays routable, and a
    later cadence point can replan."""
    from repro.placement import migrate as pmigrate
    from repro.serving.engine import Engine
    cfg, params = model
    params = _bias_router(params)
    mgr = ReplicaManager(cfg, ReplicationConfig(
        replan_every=3, warmup_iters=2, min_gain=0.0), 4)
    eng = Engine(cfg, expand_moe_params(params, mgr.rset),
                 ReaLBConfig(gate_gamma=4), max_slots=3, max_len=32,
                 placement=mgr)
    for r in _reqs(cfg, n=8):
        eng.submit(r)
    orig = pmigrate.apply_to_params

    def boom(params, plan):
        raise RuntimeError("simulated gather failure")

    monkeypatch.setattr(pmigrate, "apply_to_params", boom)
    with pytest.raises(RuntimeError, match="gather failure"):
        eng.run()
    assert mgr._pending is None and mgr.n_migrations == 0
    assert (mgr.rset.n_rep == 1).all()          # old set still routable
    monkeypatch.setattr(pmigrate, "apply_to_params", orig)
    done = eng.run()                             # replans and finishes
    assert len(done) == 8
    assert mgr.n_migrations >= 1


def _pair_skew(params, pairs_by_block):
    """Explicit router skew that holds for every parameter draw.

    For each ``(a, b, w)`` of a block, ``+w`` goes to every row of expert
    ``a``'s router column and ``-w`` to expert ``b``'s, shifting their
    logits by ``+w·Σx`` and ``-w·Σx``.  The sign of ``Σx`` varies from
    token to token, so every token's top-k holds ``a`` or ``b`` and the
    pair is hot whatever the sign mix.  (A one-sided offset is hot only
    for the tokens with ``Σx > 0`` — a share that the parameter draw, and
    so JAX's random stream, decides.)"""
    out = dict(params)
    blocks = dict(out["blocks"])
    lp = dict(blocks["layer0"])
    moe = dict(lp["moe"])
    r = moe["router"]
    for blk, pairs in enumerate(pairs_by_block):
        for a, b, w in pairs:
            r = r.at[blk, :, a].add(w).at[blk, :, b].add(-w)
    moe["router"] = r
    lp["moe"] = moe
    blocks["layer0"] = lp
    out["blocks"] = blocks
    return out


@pytest.mark.slow
def test_engine_live_replication_beats_placement_ib(model):
    """Acceptance: on a hot-expert stream (every token routed to expert
    0 or 1) the replica engine performs live replica adds and ends with
    lower prefill IB than the bijective placement engine on the same
    stream."""
    from repro.configs import PlacementConfig
    from repro.placement import PlacementManager
    from repro.serving.engine import Engine
    from repro.serving.telemetry import Telemetry
    cfg, params = model
    params = _pair_skew(params, [[(0, 1, 3.0)]] * 2)
    rcfg = ReaLBConfig(gate_gamma=4)

    def run(mgr, p):
        tel = Telemetry()
        eng = Engine(cfg, p, rcfg, max_slots=4, max_len=32, placement=mgr,
                     telemetry=tel, virtual_ep=4)
        for r in _reqs(cfg, n=16, seed=3):
            eng.submit(r)
        done = eng.run()
        assert len(done) == 16
        pre = [s.ib_global for s in eng.stats if s.phase == "prefill"]
        return float(np.mean(pre)), eng

    pmgr = PlacementManager(cfg, PlacementConfig(
        planner="least_loaded", replan_every=3, warmup_iters=2,
        min_gain=0.0), 4)
    ib_p, _ = run(pmgr, params)

    rmgr = ReplicaManager(cfg, ReplicationConfig(
        replan_every=3, warmup_iters=2, min_gain=0.0, spare_per_rank=1,
        max_replicas=2), 4)
    ib_r, eng_r = run(rmgr, expand_moe_params(params, rmgr.rset))
    assert rmgr.n_migrations >= 1 and rmgr.migrated_bytes > 0
    assert any(s.split_frac > 0 for s in eng_r.stats)
    assert rmgr.cum_slot_load.sum() > 0
    assert ib_r < ib_p, (ib_r, ib_p)


@pytest.mark.slow
def test_engine_replication_checkpoint_roundtrip(model):
    from repro.serving.engine import Engine
    cfg, params = model
    params = _bias_router(params)
    rcfg = ReaLBConfig(gate_gamma=4)
    mgr = ReplicaManager(cfg, ReplicationConfig(
        replan_every=3, warmup_iters=2, min_gain=0.0), 4)
    eng = Engine(cfg, expand_moe_params(params, mgr.rset), rcfg,
                 max_slots=3, max_len=32, placement=mgr)
    for r in _reqs(cfg, n=10):
        eng.submit(r)
    eng.run()
    assert mgr.n_migrations >= 1

    with tempfile.TemporaryDirectory() as d:
        eng.save_checkpoint(d, 5)
        # same-kind restore resumes the exact replica set + weights
        mgr2 = ReplicaManager(cfg, ReplicationConfig(), 4)
        eng2 = Engine(cfg, expand_moe_params(params, mgr2.rset), rcfg,
                      max_slots=3, max_len=32, placement=mgr2)
        assert eng2.load_checkpoint(d) == 5
        assert np.array_equal(mgr2.rset.rep_pos, mgr.rset.rep_pos)
        assert mgr2.n_migrations == mgr.n_migrations
        w0 = np.asarray(eng.params["blocks"]["layer0"]["moe"]["w_gate"])
        w1 = np.asarray(eng2.params["blocks"]["layer0"]["moe"]["w_gate"])
        assert np.array_equal(w0, w1)
        # a manager-free engine must refuse the replicated checkpoint
        eng3 = Engine(cfg, params, rcfg, max_slots=3, max_len=32)
        with pytest.raises(ValueError, match="replication"):
            eng3.load_checkpoint(d)
        # and so must a bijective-placement engine (replicated↔bijective)
        from repro.configs import PlacementConfig
        from repro.placement import PlacementManager
        pmgr = PlacementManager(cfg, PlacementConfig(), 4)
        eng4 = Engine(cfg, params, rcfg, max_slots=3, max_len=32,
                      placement=pmgr)
        with pytest.raises(ValueError, match="replication"):
            eng4.load_checkpoint(d)

    # the reverse direction: a replica engine restoring a checkpoint
    # written WITHOUT any manager resets cleanly to identity and
    # re-expands the logical weights into its slot layout
    with tempfile.TemporaryDirectory() as d:
        eng_plain = Engine(cfg, params, rcfg, max_slots=3, max_len=32)
        eng_plain.save_checkpoint(d, 1)
        mgr5 = ReplicaManager(cfg, ReplicationConfig(), 4)
        mgr5.rset = mgr.rset                    # pretend it had replicated
        eng5 = Engine(cfg, expand_moe_params(params, mgr5.rset), rcfg,
                      max_slots=3, max_len=32, placement=mgr5)
        assert eng5.load_checkpoint(d) == 1
        assert (mgr5.rset.n_rep == 1).all() and mgr5.n_migrations == 0
        w = np.asarray(eng5.params["blocks"]["layer0"]["moe"]["w_gate"])
        assert w.shape[-3] == mgr5.n_slots      # re-expanded
        # a bijective-placement checkpoint is refused by a replica engine
        from repro.configs import PlacementConfig
        from repro.placement import PlacementManager
        pmgr = PlacementManager(cfg, PlacementConfig(
            planner="least_loaded", replan_every=2, warmup_iters=1,
            min_gain=0.0), 4)
        eng6 = Engine(cfg, params, rcfg, max_slots=3, max_len=32,
                      placement=pmgr)
        for r in _reqs(cfg, n=6):
            eng6.submit(r)
        eng6.run()
        eng6.save_checkpoint(d, 2)
        mgr7 = ReplicaManager(cfg, ReplicationConfig(), 4)
        eng7 = Engine(cfg, expand_moe_params(params, mgr7.rset), rcfg,
                      max_slots=3, max_len=32, placement=mgr7)
        with pytest.raises(ValueError, match="placement"):
            eng7.load_checkpoint(d)
