"""Per-layer placement/replication tables: stacked tables threaded
through the layer scan (identity ≡ bitwise to the shared path),
layer-diff migration (bytes ∝ changed layers only), per-layer planning
beating shared-table planning on depth-varying skew, decode-window
prediction, replica-aware capacity, the calibrated replan cost gate and
the per-layer checkpoint round-trip + per-layer↔shared refusal."""
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import (PlacementConfig, ReaLBConfig, ReplicationConfig,
                           get_config, reduced)
from repro.core import ep_moe
from repro.models import transformer as tf
from repro.placement import (EWMAPredictor, LayerMigrationPlan,
                             PlacementManager, PlacementTable,
                             apply_to_params, diff_layers,
                             plan_least_loaded)
from repro.replication import (ReplicaManager, ReplicaSet,
                               expand_moe_params, plan_replication)
from repro.replication import diff_layers as rep_diff_layers


@pytest.fixture(scope="module")
def model():
    cfg = reduced(get_config("olmoe-1b-7b"), n_layers=2)
    params = tf.init_model(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _skew_stats(skews, e=8):
    """[L, 2, E] per-layer (load, vis) stats from per-layer load rows."""
    es = np.zeros((len(skews), 2, e))
    for l, row in enumerate(skews):
        es[l, 0] = row
        es[l, 1] = np.asarray(row) * 0.5
    return es


SKEW = [10.0, 8, 1, 1, 1, 1, 1, 1]
FLAT = [1.0] * 8


# --------------------------------------------------------------------------
# stacked tables through the layer scan (tentpole identity parity)
# --------------------------------------------------------------------------
def test_split_placement_shapes():
    ident = ep_moe.identity_replication(8, 4)
    shared, stacked = tf.split_placement(tuple(ident), 3)
    assert stacked is None and len(shared) == 3
    st = tuple(np.broadcast_to(np.asarray(a), (3,) + a.shape)
               for a in ident)
    shared, stacked = tf.split_placement(st, 3)
    assert shared is None and stacked[0].shape == (3, 8, 1)
    with pytest.raises(AssertionError):
        tf.split_placement(st, 4)           # wrong leading axis
    assert tf.split_placement(None, 3) == (None, None)


def test_perlayer_identity_bitwise_full_model(model):
    """Stacked identity tables threaded through the scan must be bitwise
    equal to the shared identity table AND to the table-free path, for
    prefill and decode."""
    cfg, params = model
    rcfg = ReaLBConfig(gate_gamma=4)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 12)),
                         jnp.int32)
    m = jnp.full((1, 4), 0.9)
    _, n_blocks, _ = tf.block_structure(cfg)
    ident = ep_moe.identity_replication(cfg.moe.num_experts, 4)
    stacked = tuple(jnp.broadcast_to(a, (n_blocks,) + a.shape)
                    for a in ident)
    batch = {"tokens": tokens}
    r0 = tf.prefill_forward(params, cfg, rcfg, batch, m, cache_len=16)
    r1 = tf.prefill_forward(params, cfg, rcfg, batch, m, cache_len=16,
                            placement=stacked)
    r2 = tf.prefill_forward(params, cfg, rcfg, batch, m, cache_len=16,
                            placement=tuple(ident))
    for a, b in ((r0, r1), (r2, r1)):
        assert np.array_equal(np.asarray(a.logits), np.asarray(b.logits))
        assert np.array_equal(np.asarray(a.m_state), np.asarray(b.m_state))
    db = {"tokens": tokens[:, :1], "pos": jnp.full((2,), 12, jnp.int32)}
    d0 = tf.decode_forward(params, cfg, rcfg, db, r0.cache, r0.m_state)
    d1 = tf.decode_forward(params, cfg, rcfg, db, r1.cache, r1.m_state,
                           placement=stacked)
    assert np.array_equal(np.asarray(d0.logits), np.asarray(d1.logits))


def test_perlayer_tables_route_each_block_through_its_own_table(model):
    """Two different per-layer permutations (weights permuted per block)
    must reproduce the identity outputs — each block consumed its own
    slice, not a shared one."""
    cfg, params = model
    rcfg = ReaLBConfig(gate_gamma=10 ** 9)
    e = cfg.moe.num_experts
    rng = np.random.default_rng(3)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 12)),
                         jnp.int32)
    m = jnp.full((1, 4), 0.9)
    _, n_blocks, _ = tf.block_structure(cfg)
    tables = [PlacementTable.identity(e, 4)]
    for l in range(1, n_blocks):
        owner = rng.permutation(e)
        pos = np.empty(e, np.int64)
        pos[owner] = np.arange(e)
        tables.append(PlacementTable(pos // 2, pos % 2, 4))
    place = (jnp.asarray(np.stack([t.e2r for t in tables]), jnp.int32),
             jnp.asarray(np.stack([t.local_slot for t in tables]),
                         jnp.int32))
    # permute each block's weight slab by its own table
    perm = dict(params)
    blocks = dict(perm["blocks"])
    lp = dict(blocks["layer0"])
    moe = dict(lp["moe"])
    own = np.stack([t.owner for t in tables])          # [L, E]
    for key in ("w_gate", "w_up", "w_down"):
        w = np.asarray(moe[key])
        moe[key] = jnp.asarray(np.take_along_axis(
            w, own.reshape(own.shape + (1, 1)), axis=1))
    lp["moe"] = moe
    blocks["layer0"] = lp
    perm["blocks"] = blocks
    batch = {"tokens": tokens}
    r0 = tf.prefill_forward(params, cfg, rcfg, batch, m, cache_len=16)
    r1 = tf.prefill_forward(perm, cfg, rcfg, batch, m, cache_len=16,
                            placement=place)
    np.testing.assert_allclose(np.asarray(r1.logits),
                               np.asarray(r0.logits), rtol=2e-5, atol=2e-5)


# --------------------------------------------------------------------------
# layer-diff migration: bytes ∝ changed layers only
# --------------------------------------------------------------------------
def test_diff_layers_bytes_proportional_to_changed_layers():
    ident = PlacementTable.identity(8, 4)
    skewed = plan_least_loaded(np.asarray(SKEW), 4)
    assert not np.array_equal(skewed.e2r, ident.e2r)
    old = [ident, ident, ident]
    one = diff_layers(old, [skewed, ident, ident], bytes_per_expert=7)
    two = diff_layers(old, [skewed, ident, skewed], bytes_per_expert=7)
    assert isinstance(one, LayerMigrationPlan)
    assert one.changed_layers.tolist() == [0]
    assert two.changed_layers.tolist() == [0, 2]
    assert one.moved_per_layer[1] == one.moved_per_layer[2] == 0
    assert one.moved_bytes == 7 * one.n_moved
    assert two.moved_bytes == 2 * one.moved_bytes       # ∝ changed layers
    # unchanged layers carry the identity gather row
    np.testing.assert_array_equal(one.gather_idx[1], np.arange(8))
    assert diff_layers(old, old, 7).is_noop


def test_apply_to_params_per_layer_gather():
    ident = PlacementTable.identity(8, 4)
    skewed = plan_least_loaded(np.asarray(SKEW), 4)
    plan = diff_layers([ident, ident, ident], [ident, skewed, ident], 5)
    w = np.arange(3 * 8 * 2 * 4, dtype=np.float32).reshape(3, 8, 2, 4)
    params = {"blocks": {"layer0": {"moe": {
        "router": np.zeros((2, 8)), "w_gate": w, "w_up": w + 1,
        "w_down": np.swapaxes(w, 2, 3)}}}}
    out = apply_to_params(params, plan)
    got = out["blocks"]["layer0"]["moe"]["w_gate"]
    np.testing.assert_array_equal(got[0], w[0])         # unchanged layers
    np.testing.assert_array_equal(got[2], w[2])
    for p_new in range(8):
        np.testing.assert_array_equal(got[1, p_new],
                                      w[1, skewed.owner[p_new]])


def test_replication_diff_layers_and_expand():
    ident = ReplicaSet.identity(8, 4, slots_per_rank=3, max_replicas=2)
    hot = plan_replication(np.asarray(SKEW), 4, 3, max_replicas=2)
    plan = rep_diff_layers([ident, ident], [hot, ident], bytes_per_expert=7)
    assert plan.changed_layers.tolist() == [0]
    assert plan.crossrank_per_layer[1] == 0
    assert plan.moved_bytes == 7 * plan.n_crossrank > 0
    np.testing.assert_array_equal(plan.gather_idx[1], np.arange(12))
    # per-layer expansion: each block laid out by its own set
    w = np.arange(2 * 8 * 2 * 3, dtype=np.float32).reshape(2, 8, 2, 3)
    params = {"blocks": {"layer0": {"moe": {
        "router": np.zeros((2, 8)), "w_gate": w, "w_up": w,
        "w_down": np.swapaxes(w, 2, 3)}}}}
    out = expand_moe_params(params, [ident, hot])
    got = out["blocks"]["layer0"]["moe"]["w_gate"]
    assert got.shape == (2, 12, 2, 3)
    for l, rs in enumerate((ident, hot)):
        own = rs.slot_owner
        for s in range(12):
            want = w[l, own[s]] if own[s] >= 0 else 0.0
            np.testing.assert_array_equal(got[l, s], want)


# --------------------------------------------------------------------------
# per-layer managers
# --------------------------------------------------------------------------
def test_perlayer_manager_replans_only_skewed_layers():
    pcfg = PlacementConfig(replan_every=2, warmup_iters=1, min_gain=0.0,
                           per_layer=True)
    mgr = PlacementManager.from_geometry(8, pcfg, 4, bytes_per_expert=7,
                                         n_layers=3)
    assert mgr.n_tables == 3 and mgr.per_layer
    mgr.observe(_skew_stats([SKEW, FLAT, SKEW[::-1]]))
    assert mgr.maybe_replan(1) is None                  # off-cadence
    plan = mgr.maybe_replan(2)
    assert isinstance(plan, LayerMigrationPlan)
    assert plan.moved_per_layer[1] == 0                 # flat layer kept
    assert plan.moved_per_layer[0] > 0 and plan.moved_per_layer[2] > 0
    # staged: routable tables unchanged until the slabs land + commit
    assert mgr.in_flight is plan
    np.testing.assert_array_equal(mgr.tables[0].e2r,
                                  PlacementTable.identity(8, 4).e2r)
    mgr.commit(plan)
    # the two skewed layers got different tables (depth-varying skew)
    assert not np.array_equal(mgr.tables[0].e2r, mgr.tables[2].e2r)
    np.testing.assert_array_equal(mgr.tables[1].e2r,
                                  PlacementTable.identity(8, 4).e2r)
    assert mgr.migrated_bytes == plan.moved_bytes == 7 * plan.n_moved
    assert mgr.migrated_bytes_per_layer[1] == 0
    assert mgr.migrated_bytes_per_layer.sum() == mgr.migrated_bytes
    # same prediction again: layer-diff is a no-op
    mgr.observe(_skew_stats([SKEW, FLAT, SKEW[::-1]]))
    assert mgr.maybe_replan(4) is None


def test_perlayer_replica_manager_staged_commit():
    rp = ReplicationConfig(replan_every=2, warmup_iters=1, min_gain=0.0,
                           per_layer=True)
    mgr = ReplicaManager.from_geometry(8, rp, 4, bytes_per_expert=7,
                                       n_layers=2)
    mgr.observe(_skew_stats([SKEW, FLAT]))
    before = [a.copy() for a in mgr.device_tables()]
    plan = mgr.maybe_replan(2)
    assert plan is not None and plan.changed_layers.tolist() == [0]
    for a, b in zip(before, mgr.device_tables()):       # staged: unchanged
        np.testing.assert_array_equal(a, b)
    assert mgr.maybe_replan(4) is None                  # one in flight
    mgr.commit(plan)
    assert mgr.n_migrations == 1
    assert (mgr.rsets[0].n_rep.max() > 1) and (mgr.rsets[1].n_rep == 1).all()
    assert mgr.migrated_bytes_per_layer[1] == 0
    tables = mgr.device_tables()
    assert tables[0].shape[0] == 2 and tables[2].shape == (2, 12)


def test_perlayer_manager_state_roundtrip_and_shared_mismatch():
    pcfg = PlacementConfig(replan_every=1, warmup_iters=1, min_gain=0.0,
                           per_layer=True)
    mgr = PlacementManager.from_geometry(8, pcfg, 4, bytes_per_expert=3,
                                         n_layers=2)
    mgr.observe(_skew_stats([SKEW, SKEW[::-1]]))
    plan = mgr.maybe_replan(1)
    assert plan is not None
    mgr.commit(plan)
    sd = {k: np.asarray(v) for k, v in mgr.state_dict().items()}
    m2 = PlacementManager.from_geometry(8, pcfg, 4, bytes_per_expert=3,
                                        n_layers=2)
    m2.load_state_dict(sd)
    for a, b in zip(m2.tables, mgr.tables):
        np.testing.assert_array_equal(a.e2r, b.e2r)
    np.testing.assert_array_equal(m2.migrated_bytes_per_layer,
                                  mgr.migrated_bytes_per_layer)
    # per-layer state refused by a shared manager (and vice versa)
    shared = PlacementManager.from_geometry(
        8, PlacementConfig(), 4, bytes_per_expert=3)
    with pytest.raises(ValueError, match="table"):
        shared.load_state_dict(sd)
    with pytest.raises(ValueError, match="table"):
        m2.load_state_dict(
            {k: np.asarray(v) for k, v in shared.state_dict().items()})


def test_perlayer_replica_state_mismatch_refused():
    rp_pl = ReplicationConfig(per_layer=True)
    rp_sh = ReplicationConfig()
    pl = ReplicaManager.from_geometry(8, rp_pl, 4, n_layers=2)
    sh = ReplicaManager.from_geometry(8, rp_sh, 4)
    sd = {k: np.asarray(v) for k, v in pl.state_dict().items()}
    with pytest.raises(ValueError, match="replica set"):
        sh.load_state_dict(sd)
    with pytest.raises(ValueError, match="replica set"):
        pl.load_state_dict(
            {k: np.asarray(v) for k, v in sh.state_dict().items()})


# --------------------------------------------------------------------------
# decode-aware prediction
# --------------------------------------------------------------------------
def test_predictor_decode_window_not_drowned_by_prefill():
    """An interleaved prefill-dominated stream (5 prefill : 1 decode, the
    serving engine's usual mix): the shared-window predictor's decode
    view decays back toward the prefill skew after every decode burst,
    while the separate decode window preserves the decode-regime skew."""
    def feed(pred):
        for _ in range(10):
            for _ in range(5):
                pred.observe(np.array([[100.0, 0, 0, 0]]))
            pred.observe(np.array([[0, 0, 0, 8.0]]), decode=True)
        for _ in range(5):                    # stream ends prefill-heavy
            pred.observe(np.array([[100.0, 0, 0, 0]]))

    pred = EWMAPredictor(4, alpha=0.25, decode_halflife=2.0)
    feed(pred)
    mixed, _ = pred.predict()
    decode, _ = pred.predict(regime="decode")
    assert np.argmax(mixed) == 0              # main window: prefill skew
    assert np.argmax(decode) == 3             # decode window: decode skew
    assert decode[0] == 0.0
    assert pred.n_obs_decode == 10
    # without a decode window the same stream drowns the decode skew
    plain = EWMAPredictor(4, alpha=0.25)
    feed(plain)
    assert np.argmax(plain.predict(regime="decode")[0]) == 0


def test_predictor_decode_state_roundtrip():
    pred = EWMAPredictor(4, alpha=0.3, decode_halflife=4.0)
    pred.observe(np.array([[1.0, 2, 3, 4]]))
    pred.observe(np.array([[4.0, 3, 2, 1]]), decode=True)
    sd = {k: np.asarray(v) for k, v in pred.state_dict().items()}
    p2 = EWMAPredictor(4, decode_halflife=4.0)
    p2.load_state_dict(sd)
    np.testing.assert_allclose(p2.predict(regime="decode")[0],
                               pred.predict(regime="decode")[0])
    assert p2.n_obs_decode == 1 and p2.decode_halflife == 4.0
    # decode_halflife is config, not state: a window-less restorer drops
    # the (would-be-stale) decode window instead of serving it forever
    p3 = EWMAPredictor(4)
    p3.load_state_dict(sd)
    assert p3.decode_halflife == 0.0 and p3.n_obs_decode == 0
    assert p3.load_dec is None
    np.testing.assert_allclose(p3.predict(regime="decode")[0],
                               pred.predict()[0])     # falls back to main
    # ... and a decode-enabled restorer keeps its window even when the
    # checkpoint was written by a window-less run
    sd_plain = {k: np.asarray(v)
                for k, v in EWMAPredictor(4).state_dict().items()}
    p4 = EWMAPredictor(4, decode_halflife=8.0)
    p4.load_state_dict(sd_plain)
    assert p4.decode_halflife == 8.0 and p4.decode_alpha > 0


def test_manager_decode_cadence_replans_from_decode_window():
    """A decode-skewed stream triggers a decode-cadence replan planned
    from the decode window, off the prefill cadence."""
    pcfg = PlacementConfig(replan_every=1000, warmup_iters=1, min_gain=0.0,
                           decode_halflife=2.0, decode_replan_every=3)
    mgr = PlacementManager.from_geometry(8, pcfg, 4, bytes_per_expert=1)
    mgr.observe(_skew_stats([FLAT]))                    # flat prefill
    assert mgr.maybe_replan(7) is None                  # no decode obs yet
    for _ in range(3):
        mgr.observe(_skew_stats([SKEW]), decode=True)
    plan = mgr.maybe_replan(9)                          # off prefill cadence
    assert plan is not None and plan.n_moved > 0
    assert mgr._decode_since_replan == 0                # counter reset
    mgr.commit(plan)
    # a decode cadence point whose plan is REJECTED (no gain: the decode
    # skew is already balanced) must also consume the window — otherwise
    # the full planner would re-run on every subsequent iteration
    for _ in range(3):
        mgr.observe(_skew_stats([SKEW]), decode=True)
    assert mgr.maybe_replan(11) is None                 # already balanced
    assert mgr._decode_since_replan == 0                # window consumed
    assert mgr._cadence(12) is None                     # quiet until due
    # decode cadence WITHOUT a decode window (decode_halflife=0): still
    # fires, planning from the shared window (predict's fallback) —
    # never a silently dead configuration
    pcfg2 = PlacementConfig(replan_every=1000, warmup_iters=1,
                            min_gain=0.0, decode_replan_every=2)
    m2 = PlacementManager.from_geometry(8, pcfg2, 4, bytes_per_expert=1)
    for _ in range(2):
        m2.observe(_skew_stats([SKEW]), decode=True)
    assert m2.predictor.n_obs_decode == 2
    assert m2.maybe_replan(5) is not None
    # and the plan balanced the DECODE skew, not the flat prefill view
    load = np.asarray(SKEW)
    ident = PlacementTable.identity(8, 4)
    assert mgr.table.rank_loads(load).max() < \
        ident.rank_loads(load).max()


# --------------------------------------------------------------------------
# replica-aware capacity
# --------------------------------------------------------------------------
def test_replica_capacity_factor_shrinks_with_split():
    load = np.array([40.0, 1, 1, 1, 1, 1, 1, 1])
    ident = ReplicaSet.identity(8, 4, slots_per_rank=3, max_replicas=4)
    rs = plan_replication(load, 4, 3, max_replicas=4)
    f_ident = ident.capacity_factor(load, margin=1.25)
    f_split = rs.capacity_factor(load, margin=1.25)
    assert f_split < f_ident                    # buffer shrinks
    # the reduced cap still fits the post-split peak rank load: the
    # per-rank buffer holds tot/ep * factor entries
    tot = load.sum()
    assert rs.rank_loads(load).max() <= tot / 4 * f_split
    # ... while the bijective peak would overflow it
    assert ident.rank_loads(load).max() > tot / 4 * f_split
    assert ident.capacity_factor(np.zeros(8)) == 1.0    # floor


def test_replica_manager_capacity_factor_tracks_post_split_loads():
    """The manager derives the effective dispatch factor from its
    predicted post-split loads: identity sets price the bijective peak,
    committed replication prices the flattened one (per-layer managers
    take the worst layer).  The real-dispatch no-drop check at the
    reduced cap runs on the (2,4) mesh (``replica_capacity_reduced_cap``
    in tests/_dist_worker.py)."""
    rp = ReplicationConfig(replan_every=1, warmup_iters=1, min_gain=0.0,
                           max_replicas=4, spare_per_rank=2)
    mgr = ReplicaManager.from_geometry(8, rp, 4)
    # no observation = no evidence to shrink on: +inf (engine clamps to
    # its static provision), NOT the most aggressive floor
    assert mgr.capacity_factor(margin=1.25) == float("inf")
    hot = [40.0, 1, 1, 1, 1, 1, 1, 1]
    mgr.observe(_skew_stats([hot]))
    f_before = mgr.capacity_factor(margin=1.25)
    plan = mgr.maybe_replan(1)
    assert plan is not None
    mgr.commit(plan)
    f_after = mgr.capacity_factor(margin=1.25)
    assert f_after < f_before                           # buffer shrinks
    # ... and still covers the post-split peak rank load with margin
    load = np.asarray(hot)
    assert mgr.rset.rank_loads(load).max() <= \
        load.sum() / 4 * f_after
    # per-layer manager: the worst layer prices the buffer
    rp_pl = ReplicationConfig(replan_every=1, warmup_iters=1,
                              min_gain=0.0, max_replicas=4,
                              spare_per_rank=2, per_layer=True)
    mpl = ReplicaManager.from_geometry(8, rp_pl, 4, n_layers=2)
    mpl.observe(_skew_stats([hot, FLAT]))
    plan = mpl.maybe_replan(1)
    mpl.commit(plan)
    f_pl = mpl.capacity_factor(margin=1.25)
    assert f_pl >= mpl.rsets[0].capacity_factor(
        mpl.predictor.predict_layers()[0][0], 1.25)
    # decode-regime drift the (frozen) main window cannot see must still
    # re-grow the buffer: the worst prediction window prices it
    rp_dec = ReplicationConfig(replan_every=1, warmup_iters=1,
                               min_gain=0.0, max_replicas=4,
                               spare_per_rank=2, decode_halflife=2.0)
    md = ReplicaManager.from_geometry(8, rp_dec, 4)
    md.observe(_skew_stats([FLAT]))                     # flat prefill view
    f_flat = md.capacity_factor(margin=1.25)
    for _ in range(3):                                  # decode goes hot
        md.observe(_skew_stats([hot]), decode=True)
    assert md.capacity_factor(margin=1.25) > f_flat


# --------------------------------------------------------------------------
# calibrated replan cost gate
# --------------------------------------------------------------------------
def test_calibrated_cost_gate_tracks_iteration_history():
    from benchmarks import costmodel as cm
    g = cm.KIMI_VL
    gate = cm.CalibratedReplanCostGate(g, 8, horizon_iters=100,
                                       default_tokens=4096.0, window=8)
    assert gate.tokens_per_iter == 4096.0       # pre-calibration fallback
    skew = np.array([8.0, 1, 1, 1, 1, 1, 1, 1])
    flat = np.full(8, skew.sum() / 8)
    assert gate.accept(skew, flat, 4)           # big batches: worth it
    # a synthetic history of tiny decode iterations: savings shrink with
    # tokens/iter, so the same plan stops amortizing
    for i in range(16):
        gate.observe_iter(4.0, t_wall=0.1 * i)
    assert gate.tokens_per_iter == 4.0          # window mean (last 8)
    assert gate.tokens_per_s > 0
    assert not gate.accept(skew, flat, 4)
    # back to large measured batches: accepts again
    for i in range(16):
        gate.observe_iter(8192.0, t_wall=2.0 + 0.1 * i)
    assert gate.accept(skew, flat, 4)
    # per-layer plans route through the same calibrated constant
    assert gate.accept_layers(np.stack([skew] * 4), np.stack([flat] * 4),
                              4)


def test_perlayer_gate_charges_per_layer_transfer_cost():
    """A single skewed layer: diluted into the 47-layer aggregate, the
    shared-table gate sees savings too small to pay for whole-stack
    slabs; the per-layer gate sees the full layer-0 saving against only
    that layer's slab cost — accept_layers charges changed layers only."""
    from benchmarks import costmodel as cm
    g = cm.KIMI_VL
    gate = cm.ReplanCostGate(g, 8, horizon_iters=4, tokens_per_iter=4096.0)
    skew = np.array([8.0, 1, 1, 1, 1, 1, 1, 1])
    flat = np.full(8, skew.sum() / 8)
    # shared view: the one skewed layer vanishes into the depth average,
    # but a shared-table migration still ships every layer's slabs
    agg_old = (skew + 46 * flat) / 47
    assert not gate.accept(agg_old, flat, 8)
    # per-layer view: same physical situation, 8 (expert, layer) pairs in
    # the one changed layer — full saving, 1/47th of the bytes
    old = np.tile(flat, (47, 1))
    new = old.copy()
    old[0] = skew
    assert gate.accept_layers(old, new, 8)
    assert not gate.accept_layers(old, old, 8)  # no savings -> reject
    assert gate.accept_layers(old, new, 0)      # free moves always ok
    assert cm.migration_bytes_layers(8, g, 47) < cm.migration_bytes(8, g)


# --------------------------------------------------------------------------
# per-layer beats shared on depth-varying skew (cost-model acceptance)
# --------------------------------------------------------------------------
def test_perlayer_planning_beats_shared_on_depth_varying_trace():
    from benchmarks import costmodel as cm
    from benchmarks import traces as tr
    cfg = tr.TraceConfig(name="depth", iters=240, jump_every=80,
                         zipf_a=1.3, vision_frac_mean=0.7, seed=5)
    g = cm.KIMI_VL
    shared = cm.sim_placement_layers(cfg, g, n_layers=4, per_layer=False)
    perlay = cm.sim_placement_layers(cfg, g, n_layers=4, per_layer=True)
    ib_s = float(np.mean(shared.extra["ib_global"]))
    ib_p = float(np.mean(perlay.extra["ib_global"]))
    assert ib_p < ib_s, (ib_p, ib_s)            # strictly lower peak IB
    rs = cm.sim_replication_layers(cfg, g, n_layers=4, per_layer=False)
    rp = cm.sim_replication_layers(cfg, g, n_layers=4, per_layer=True)
    assert float(np.mean(rp.extra["ib_global"])) < \
        float(np.mean(rs.extra["ib_global"]))


# --------------------------------------------------------------------------
# engine end-to-end (slow)
# --------------------------------------------------------------------------
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
    """biases: [n_blocks, E] logit offsets — depth-varying router skew."""
    out = dict(params)
    blocks = dict(out["blocks"])
    lp = dict(blocks["layer0"])
    moe = dict(lp["moe"])
    moe["router"] = moe["router"] + jnp.asarray(biases)[:, None, :]
    lp["moe"] = moe
    blocks["layer0"] = lp
    out["blocks"] = blocks
    return out


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
def test_engine_perlayer_identity_matches_baseline(model):
    """A per-layer identity-planner engine generates exactly what a
    manager-free engine does — the n_blocks-stacked degenerate case."""
    from repro.serving.engine import Engine
    cfg, params = model
    rcfg = ReaLBConfig(gate_gamma=4)
    eng0 = Engine(cfg, params, rcfg, max_slots=3, max_len=32, virtual_ep=4)
    for r in _reqs(cfg):
        eng0.submit(r)
    g0 = [r.generated for r in sorted(eng0.run(), key=lambda r: r.uid)]
    mgr = PlacementManager(cfg, PlacementConfig(planner="identity",
                                                per_layer=True), 4)
    assert mgr.n_tables == tf.block_structure(cfg)[1] == 2
    eng1 = Engine(cfg, params, rcfg, max_slots=3, max_len=32, placement=mgr)
    for r in _reqs(cfg):
        eng1.submit(r)
    g1 = [r.generated for r in sorted(eng1.run(), key=lambda r: r.uid)]
    assert g0 == g1
    assert mgr.n_migrations == 0


@pytest.mark.slow
def test_engine_perlayer_beats_shared_on_depth_antisymmetric_skew(model):
    """Depth-antisymmetric router skew (layer 0 hot on experts 0-3,
    layer 1 on the mirror-image experts 7-4, so every EP rank is hot in
    one layer): the shared planner cannot fix both layers with one table
    while per-layer planning flattens each layer — strictly lower
    prefill IB, and migration traffic only for the layers that
    changed."""
    from repro.serving.engine import Engine
    cfg, params = model
    rcfg = ReaLBConfig(gate_gamma=4)
    params = _pair_skew(params, [[(0, 1, 3.0), (2, 3, 1.0)],
                                 [(7, 6, 3.0), (5, 4, 1.0)]])

    def run(per_layer):
        mgr = PlacementManager(cfg, PlacementConfig(
            planner="least_loaded", replan_every=3, warmup_iters=2,
            min_gain=0.02, per_layer=per_layer), 4)
        eng = Engine(cfg, params, rcfg, max_slots=4, max_len=32,
                     placement=mgr, virtual_ep=4)
        for r in _reqs(cfg, n=16, seed=3):
            eng.submit(r)
        assert len(eng.run()) == 16
        pre = [s.ib_global for s in eng.stats if s.phase == "prefill"]
        return float(np.mean(pre)), mgr

    ib_shared, mgr_s = run(False)
    ib_perlayer, mgr_p = run(True)
    assert mgr_p.n_migrations >= 1
    assert ib_perlayer < ib_shared, (ib_perlayer, ib_shared)
    # layer-diff accounting: bytes land on the layers that moved
    assert mgr_p.migrated_bytes == mgr_p.migrated_bytes_per_layer.sum()


@pytest.mark.slow
def test_engine_perlayer_replication_checkpoint_roundtrip(model):
    """Per-layer replica engine: live replans, checkpoint resume with the
    exact per-layer sets, refusal by shared-table and manager-free
    readers."""
    from repro.serving.engine import Engine
    cfg, params = model
    b0 = np.array([3.0, 2.0, 0, 0, 0, 0, 0, 0])
    params_b = _bias_routers_by_depth(params, np.stack([b0, b0[::-1]]))
    rcfg = ReaLBConfig(gate_gamma=4)
    mgr = ReplicaManager(cfg, ReplicationConfig(
        replan_every=3, warmup_iters=2, min_gain=0.0, per_layer=True), 4)
    assert mgr.n_tables == 2
    eng = Engine(cfg, expand_moe_params(params_b, mgr.rsets), rcfg,
                 max_slots=3, max_len=32, placement=mgr)
    for r in _reqs(cfg, n=10):
        eng.submit(r)
    eng.run()
    assert mgr.n_migrations >= 1

    with tempfile.TemporaryDirectory() as d:
        eng.save_checkpoint(d, 5)
        mgr2 = ReplicaManager(cfg, ReplicationConfig(per_layer=True), 4)
        eng2 = Engine(cfg, expand_moe_params(params_b, mgr2.rsets), rcfg,
                      max_slots=3, max_len=32, placement=mgr2)
        assert eng2.load_checkpoint(d) == 5
        for a, b in zip(mgr2.rsets, mgr.rsets):
            np.testing.assert_array_equal(a.rep_pos, b.rep_pos)
            np.testing.assert_array_equal(a.n_rep, b.n_rep)
        np.testing.assert_array_equal(mgr2.migrated_bytes_per_layer,
                                      mgr.migrated_bytes_per_layer)
        w0 = np.asarray(eng.params["blocks"]["layer0"]["moe"]["w_gate"])
        w1 = np.asarray(eng2.params["blocks"]["layer0"]["moe"]["w_gate"])
        assert np.array_equal(w0, w1)
        # a shared-table replica engine refuses the per-layer checkpoint
        mgr3 = ReplicaManager(cfg, ReplicationConfig(), 4)
        eng3 = Engine(cfg, expand_moe_params(params_b, mgr3.rsets), rcfg,
                      max_slots=3, max_len=32, placement=mgr3)
        with pytest.raises(ValueError, match="replica set"):
            eng3.load_checkpoint(d)
        # ... and a manager-free engine refuses it entirely
        eng4 = Engine(cfg, params_b, rcfg, max_slots=3, max_len=32)
        with pytest.raises(ValueError, match="replication"):
            eng4.load_checkpoint(d)
