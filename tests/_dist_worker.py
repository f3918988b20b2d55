"""Subprocess worker for multi-device tests (8 fake CPU devices).

Run as: python tests/_dist_worker.py <check>
Exits 0 on success; prints diagnostics on failure.
"""
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import dataclasses  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# same discipline as tests/conftest.py (subprocesses skip conftest)
jax.config.update("jax_numpy_rank_promotion", "raise")

from repro.configs import ReaLBConfig, get_config, reduced  # noqa: E402
from repro.core import ep_moe  # noqa: E402
from repro.models import transformer as tf  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models.common import use_mesh  # noqa: E402


def _moe_setup():
    cfg = reduced(get_config("olmoe-1b-7b"))
    e = cfg.moe
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    D, E, F = cfg.d_model, e.num_experts, e.d_ff
    p = {"router": jax.random.normal(ks[0], (D, E)) * 0.2,
         "w_gate": jax.random.normal(ks[1], (E, D, F)) / np.sqrt(D),
         "w_up": jax.random.normal(ks[2], (E, D, F)) / np.sqrt(D),
         "w_down": jax.random.normal(ks[3], (E, F, D)) / np.sqrt(F)}
    x = jax.random.normal(ks[4], (4, 16, D)) * 0.5
    mod = jax.random.bernoulli(ks[5], 0.6, (4, 16))
    return cfg, p, x, mod


def check_ep_dispatch_matches_local():
    cfg, p, x, mod = _moe_setup()
    rcfg = ReaLBConfig(gate_gamma=10 ** 9)
    y_ref, _, _ = ep_moe.ep_moe_forward(p, x, cfg, rcfg,
                                        jnp.full((1, 1), 0.9), mod,
                                        mode="dispatch")
    mesh = make_mesh((2, 4), ("data", "model"))
    with use_mesh(mesh):
        m = jnp.full(ep_moe.moe_state_shape(mesh, 4), 0.9)
        y, _, aux = jax.jit(
            lambda p, x, m, mod: ep_moe.ep_moe_forward(
                p, x, cfg, rcfg, m, mod, mode="dispatch"))(p, x, m, mod)
    err = float(jnp.max(jnp.abs(y - y_ref)))
    assert err < 5e-5, err
    assert float(aux["drop_frac"]) == 0.0


def check_ep_broadcast_matches_local():
    cfg, p, x, mod = _moe_setup()
    rcfg = ReaLBConfig(gate_gamma=10 ** 9)
    xd, md = x[:, :1], mod[:, :1]
    y_ref, _, _ = ep_moe.ep_moe_forward(p, xd, cfg, rcfg,
                                        jnp.full((1, 1), 0.9), md,
                                        mode="broadcast")
    mesh = make_mesh((2, 4), ("data", "model"))
    with use_mesh(mesh):
        m = jnp.full(ep_moe.moe_state_shape(mesh, 4), 0.9)
        y, _, _ = jax.jit(
            lambda p, x, m, mod: ep_moe.ep_moe_forward(
                p, x, cfg, rcfg, m, mod, mode="broadcast"))(p, xd, m, md)
    err = float(jnp.max(jnp.abs(y - y_ref)))
    assert err < 5e-5, err


def check_realb_fp4_rank_activates():
    """Skew routing so one EP rank is hot + vision heavy; with M=0 the
    policy must compress it and the output must differ from bf16 by a
    small quantization-sized delta."""
    cfg, p, x, mod = _moe_setup()
    # bias router toward experts 0..1 (rank 0 when ep=4)
    p = dict(p)
    p["router"] = p["router"].at[:, 0].add(3.0).at[:, 1].add(2.5)
    mesh = make_mesh((2, 4), ("data", "model"))
    vis = jnp.ones_like(mod)
    with use_mesh(mesh):
        m_on = jnp.zeros(ep_moe.moe_state_shape(mesh, 4))
        rc_on = ReaLBConfig(gate_gamma=1)
        y_on, _, aux_on = jax.jit(lambda p, x, m, mod: ep_moe.ep_moe_forward(
            p, x, cfg, rc_on, m, mod, mode="dispatch"))(p, x, m_on, vis)
        rc_off = ReaLBConfig(enabled=False)
        m_off = jnp.zeros(ep_moe.moe_state_shape(mesh, 4))
        y_off, _, _ = jax.jit(lambda p, x, m, mod: ep_moe.ep_moe_forward(
            p, x, cfg, rc_off, m, mod, mode="dispatch"))(p, x, m_off, vis)
    assert float(aux_on["fp4_ranks"]) >= 1.0, float(aux_on["fp4_ranks"])
    diff = float(jnp.max(jnp.abs(y_on - y_off)))
    rel = diff / float(jnp.max(jnp.abs(y_off)))
    assert 1e-6 < rel < 0.5, rel   # changed, but quantization-sized


def check_chunk_padding_isolated_under_ep():
    """Chunk-bucket padding on an EP>1 mesh: adversarial padding (zero
    embeddings, so every padding token routes to the same top-k experts)
    must neither crowd real tokens out of the per-rank capacity nor move
    the routing stats."""
    cfg, p, x, mod = _moe_setup()
    rcfg = ReaLBConfig(gate_gamma=10 ** 9)
    x_pad = x.at[:, 8:].set(0.0)                 # second half = padding
    valid = jnp.zeros((4, 16), bool).at[:, :8].set(True)
    mesh = make_mesh((2, 4), ("data", "model"))
    with use_mesh(mesh):
        m = jnp.full(ep_moe.moe_state_shape(mesh, 4), 0.9)
        y, _, aux = jax.jit(
            lambda p, x, m, mod, v: ep_moe.ep_moe_forward(
                p, x, cfg, rcfg, m, mod, mode="dispatch", valid=v))(
            p, x_pad, m, mod, valid)
    y_ref, _, _ = ep_moe.ep_moe_forward(
        p, x_pad[:, :8], cfg, rcfg, jnp.full((1, 1), 0.9), mod[:, :8],
        mode="dispatch")
    err = float(jnp.max(jnp.abs(y[:, :8] - y_ref)))
    assert err < 5e-5, err
    assert float(aux["drop_frac"]) == 0.0, float(aux["drop_frac"])
    total = float(jnp.sum(jnp.asarray(aux["load_d"])))
    assert total == 4 * 8 * cfg.moe.top_k, total   # valid tokens only


def check_placement_identity_bitwise_under_ep():
    """Under a real EP mesh, the explicit identity table is bitwise-equal
    to the default (placement=None) path — dispatch and broadcast."""
    cfg, p, x, mod = _moe_setup()
    rcfg = ReaLBConfig(gate_gamma=10 ** 9)
    mesh = make_mesh((2, 4), ("data", "model"))
    ident = ep_moe.identity_placement(cfg.moe.num_experts, 4)
    with use_mesh(mesh):
        m = jnp.full(ep_moe.moe_state_shape(mesh, 4), 0.9)
        for mode, xx, mm in (("dispatch", x, mod),
                             ("broadcast", x[:, :1], mod[:, :1])):
            y0, m0, _ = jax.jit(lambda p, x, m, mod: ep_moe.ep_moe_forward(
                p, x, cfg, rcfg, m, mod, mode=mode))(p, xx, m, mm)
            y1, m1, _ = jax.jit(
                lambda p, x, m, mod, pl: ep_moe.ep_moe_forward(
                    p, x, cfg, rcfg, m, mod, mode=mode, placement=pl))(
                p, xx, m, mm, ident)
            assert np.array_equal(np.asarray(y0), np.asarray(y1)), mode
            assert np.array_equal(np.asarray(m0), np.asarray(m1)), mode


def check_placement_permuted_matches_local_under_ep():
    """A permutation table with correspondingly permuted weight slabs on a
    (2,4) mesh matches the identity result, with permuted per-rank stats."""
    cfg, p, x, mod = _moe_setup()
    rcfg = ReaLBConfig(gate_gamma=10 ** 9)
    e = cfg.moe.num_experts
    ep = 4
    rng = np.random.default_rng(5)
    owner = rng.permutation(e)                  # physical row -> logical
    pos = np.empty(e, np.int64)
    pos[owner] = np.arange(e)
    e_loc = e // ep
    place = (jnp.asarray(pos // e_loc, jnp.int32),
             jnp.asarray(pos % e_loc, jnp.int32))
    p_perm = dict(p, w_gate=p["w_gate"][owner], w_up=p["w_up"][owner],
                  w_down=p["w_down"][owner])
    mesh = make_mesh((2, 4), ("data", "model"))
    with use_mesh(mesh):
        m = jnp.full(ep_moe.moe_state_shape(mesh, 4), 0.9)
        for mode, xx, mm in (("dispatch", x, mod),
                             ("broadcast", x[:, :1], mod[:, :1])):
            y0, _, aux0 = jax.jit(
                lambda p, x, m, mod: ep_moe.ep_moe_forward(
                    p, x, cfg, rcfg, m, mod, mode=mode))(p, xx, m, mm)
            y1, _, aux1 = jax.jit(
                lambda p, x, m, mod, pl: ep_moe.ep_moe_forward(
                    p, x, cfg, rcfg, m, mod, mode=mode, placement=pl))(
                p_perm, xx, m, mm, place)
            err = float(jnp.max(jnp.abs(y1 - y0)))
            assert err < 5e-5, (mode, err)
            # global logical per-expert loads, re-aggregated by the
            # permuted table, must equal the placed per-rank loads summed
            # over EP groups
            el = np.asarray(aux0["expert_load"])
            want = np.zeros(ep)
            np.add.at(want, np.asarray(pos // e_loc), el)
            got = np.asarray(aux1["load_d"]).reshape(-1, ep).sum(0)
            np.testing.assert_allclose(got, want, rtol=1e-6,
                                       err_msg=mode)


def check_virtual_ep_policy_parity():
    """ROADMAP satellite: the single-device *virtual* EP topology must
    produce the same policy statistics as the real EP mesh on the same
    token stream — the virtual-ep serving experiments are only meaningful
    if IB_d / LB gate / FP4 duty / AIMD updates agree with the hardware
    topology they emulate.

    Batch 3 is indivisible by the data axis, so the mesh run keeps one
    replicated policy group ([1, 4] M-state) — exactly the virtual
    topology's shape — and every scalar must match, not just the counts.
    """
    cfg, p, _, _ = _moe_setup()
    ks = jax.random.split(jax.random.PRNGKey(7), 2)
    x = jax.random.normal(ks[0], (3, 16, cfg.d_model)) * 0.5
    mod = jax.random.bernoulli(ks[1], 0.6, (3, 16))
    rcfg = ReaLBConfig(gate_gamma=8)      # open the gate: policy active
    m_virt = jnp.zeros((1, 4))            # virtual 4-rank topology, M=0
    y_v, m_v, aux_v = ep_moe.ep_moe_forward(p, x, cfg, rcfg, m_virt, mod,
                                            mode="dispatch")
    mesh = make_mesh((2, 4), ("data", "model"))
    with use_mesh(mesh):
        shape = ep_moe.moe_state_shape(mesh, 3)
        assert shape == (1, 4), shape     # batch 3 -> replicated group
        m = jnp.zeros(shape)
        y_d, m_d, aux_d = jax.jit(
            lambda p, x, m, mod: ep_moe.ep_moe_forward(
                p, x, cfg, rcfg, m, mod, mode="dispatch"))(p, x, m, mod)
    # routed counts are integers: exact equality across topologies
    for k in ("load_d", "vis_d", "expert_load", "expert_vis",
              "slot_load", "slot_vis"):
        a = np.asarray(aux_v[k]).reshape(-1)
        b = np.asarray(aux_d[k]).reshape(-1)
        assert np.array_equal(a, b), (k, a, b)
    # policy decisions and AIMD state evolve identically
    for k in ("ib_global", "gate_open", "fp4_ranks", "drop_frac",
              "split_frac"):
        a, b = float(aux_v[k]), float(aux_d[k])
        assert abs(a - b) < 1e-6, (k, a, b)
    assert np.allclose(np.asarray(m_v), np.asarray(m_d)), (m_v, m_d)
    # NOTE: outputs are *not* compared here — the policy decided FP4 for
    # the same virtual ranks, but a single device applies compression to
    # its whole (virtual) group while the mesh compresses per physical
    # rank; numerical output parity (policy off) is pinned by
    # ep_dispatch_matches_local.


def check_replication_identity_bitwise_under_ep():
    """Under a real EP mesh, the explicit identity replica set is
    bitwise-equal to the default (placement=None) path."""
    cfg, p, x, mod = _moe_setup()
    rcfg = ReaLBConfig(gate_gamma=10 ** 9)
    mesh = make_mesh((2, 4), ("data", "model"))
    ident = ep_moe.identity_replication(cfg.moe.num_experts, 4)
    with use_mesh(mesh):
        m = jnp.full(ep_moe.moe_state_shape(mesh, 4), 0.9)
        for mode, xx, mm in (("dispatch", x, mod),
                             ("broadcast", x[:, :1], mod[:, :1])):
            y0, m0, _ = jax.jit(lambda p, x, m, mod: ep_moe.ep_moe_forward(
                p, x, cfg, rcfg, m, mod, mode=mode))(p, xx, m, mm)
            y1, m1, aux1 = jax.jit(
                lambda p, x, m, mod, pl: ep_moe.ep_moe_forward(
                    p, x, cfg, rcfg, m, mod, mode=mode, placement=pl))(
                p, xx, m, mm, ident)
            assert np.array_equal(np.asarray(y0), np.asarray(y1)), mode
            assert np.array_equal(np.asarray(m0), np.asarray(m1)), mode
            assert float(aux1["split_frac"]) == 0.0, mode


def check_replication_split_under_ep():
    """A replicated hot expert on a (2,4) mesh: outputs match the
    local single-device reference, the EP ranks exchange split tokens,
    and the post-split rank loads flatten the hot rank."""
    from repro.replication import ReplicaSet, expand_moe_params

    cfg, p, x, mod = _moe_setup()
    e = cfg.moe.num_experts
    p = dict(p, router=p["router"].at[:, 0].add(4.0))    # expert 0 hot
    rcfg = ReaLBConfig(gate_gamma=10 ** 9)
    # expert 0 replicated onto rank 2's spare slot (slots_per_rank=3)
    rep_pos = np.zeros((e, 2), np.int32)
    for ex in range(e):
        rep_pos[ex] = (ex // 2) * 3 + (ex % 2)
    rep_pos[0, 1] = 2 * 3 + 2
    n_rep = np.ones(e, np.int32)
    n_rep[0] = 2
    rs = ReplicaSet(rep_pos, n_rep, 4, 3)
    wrapped = {"blocks": {"l0": {"moe": p}}}
    p_rep = dict(expand_moe_params(wrapped, rs)["blocks"]["l0"]["moe"],
                 router=p["router"])
    place = tuple(jnp.asarray(a) for a in rs.as_arrays())

    y_ref, _, aux_ref = ep_moe.ep_moe_forward(
        p, x, cfg, rcfg, jnp.full((1, 1), 0.9), mod, mode="dispatch")
    mesh = make_mesh((2, 4), ("data", "model"))
    with use_mesh(mesh):
        m = jnp.full(ep_moe.moe_state_shape(mesh, 4), 0.9)
        y, _, aux = jax.jit(
            lambda p, x, m, mod, pl: ep_moe.ep_moe_forward(
                p, x, cfg, rcfg, m, mod, mode="dispatch", placement=pl))(
            p_rep, x, m, mod, place)
    err = float(jnp.max(jnp.abs(y - y_ref)))
    assert err < 5e-5, err
    assert float(aux["split_frac"]) > 0.0
    el = np.asarray(aux["expert_load"])
    assert np.array_equal(el, np.asarray(aux_ref["expert_load"]))
    sl = np.asarray(aux["slot_load"])
    a, b = sl[rs.rep_pos[0, 0]], sl[rs.rep_pos[0, 1]]
    assert a + b == el[0] and a > 0 and b > 0, (a, b, el[0])
    # the hot rank sheds (about) half the hot expert's load to rank 2 —
    # each of the 8 shard-local round-robin counters keeps its odd
    # remainder on the primary, so allow one assignment per shard
    load_d = np.asarray(aux["load_d"]).reshape(-1, 4).sum(0)
    want = rs.rank_loads(el)
    assert np.abs(load_d - want).max() <= 8.0, (load_d, want)
    ident = ep_moe.identity_replication(e, 4)
    with use_mesh(mesh):
        m = jnp.full(ep_moe.moe_state_shape(mesh, 4), 0.9)
        _, _, aux_i = jax.jit(
            lambda p, x, m, mod, pl: ep_moe.ep_moe_forward(
                p, x, cfg, rcfg, m, mod, mode="dispatch", placement=pl))(
            p, x, m, mod, ident)
    load_i = np.asarray(aux_i["load_d"]).reshape(-1, 4).sum(0)
    assert load_d[0] < load_i[0], (load_d, load_i)   # hot rank shed load


def check_perlayer_identity_bitwise_under_ep():
    """Per-layer tentpole on a real (2,4) mesh: stacked identity tables
    threaded through the layer scan are bitwise-equal to the shared
    identity table AND to the table-free path — full model, prefill and
    decode."""
    cfg = reduced(get_config("olmoe-1b-7b"), n_layers=2)
    rcfg = ReaLBConfig(gate_gamma=10 ** 9)
    params = tf.init_model(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 16)), jnp.int32)
    batch = {"tokens": tokens}
    _, n_blocks, _ = tf.block_structure(cfg)
    ident = ep_moe.identity_replication(cfg.moe.num_experts, 4)
    stacked = tuple(jnp.broadcast_to(a, (n_blocks,) + a.shape)
                    for a in ident)
    mesh = make_mesh((2, 4), ("data", "model"))
    with use_mesh(mesh):
        m = jnp.full(ep_moe.moe_state_shape(mesh, 4), 0.9)
        outs = {}
        for name, pl in (("none", None), ("shared", tuple(ident)),
                         ("stacked", stacked)):
            res = jax.jit(lambda p, m, pl=pl: tf.prefill_forward(
                p, cfg, rcfg, batch, m, cache_len=20,
                placement=pl))(params, m)
            db = {"tokens": tokens[:, :1],
                  "pos": jnp.full((4,), 16, jnp.int32)}
            dec = jax.jit(lambda p, c, m, pl=pl: tf.decode_forward(
                p, cfg, rcfg, db, c, m, placement=pl))(
                params, res.cache, res.m_state)
            outs[name] = (np.asarray(res.logits), np.asarray(res.m_state),
                          np.asarray(dec.logits))
        for name in ("none", "shared"):
            for a, b in zip(outs[name], outs["stacked"]):
                assert np.array_equal(a, b), name


def check_perlayer_tables_matches_local_under_ep():
    """Depth-varying per-layer permutation tables (each block's weights
    permuted by its own table) on the (2,4) mesh match the local
    single-device per-layer run and the table-free reference.  Capacity
    is provisioned for every assignment (factor = EP) so that a capacity
    drop — correct dispatch behaviour, absent from the drop-free local
    runs — cannot mask or mimic a table mismatch."""
    cfg = reduced(get_config("olmoe-1b-7b"), n_layers=2)
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, capacity_factor=4.0))
    rcfg = ReaLBConfig(gate_gamma=10 ** 9)
    params = tf.init_model(cfg, jax.random.PRNGKey(0))
    e = cfg.moe.num_experts
    rng = np.random.default_rng(5)
    _, n_blocks, _ = tf.block_structure(cfg)
    owners, e2r, slot = [], [], []
    for l in range(n_blocks):
        owner = rng.permutation(e)
        pos = np.empty(e, np.int64)
        pos[owner] = np.arange(e)
        owners.append(owner)
        e2r.append(pos // 2)
        slot.append(pos % 2)
    place = (jnp.asarray(np.stack(e2r), jnp.int32),
             jnp.asarray(np.stack(slot), jnp.int32))
    own = np.stack(owners)
    perm = dict(params)
    blocks = dict(perm["blocks"])
    lp = dict(blocks["layer0"])
    moe = dict(lp["moe"])
    for key in ("w_gate", "w_up", "w_down"):
        w = np.asarray(moe[key])
        moe[key] = jnp.asarray(np.take_along_axis(
            w, own.reshape(own.shape + (1, 1)), axis=1))
    lp["moe"] = moe
    blocks["layer0"] = lp
    perm["blocks"] = blocks
    rng2 = np.random.default_rng(1)
    tokens = jnp.asarray(rng2.integers(0, cfg.vocab_size, (4, 16)),
                         jnp.int32)
    batch = {"tokens": tokens}
    m1 = jnp.full((1, 4), 0.9)
    ref = tf.prefill_forward(params, cfg, rcfg, batch, m1, cache_len=20)
    loc = tf.prefill_forward(perm, cfg, rcfg, batch, m1, cache_len=20,
                             placement=place)
    mesh = make_mesh((2, 4), ("data", "model"))
    with use_mesh(mesh):
        m = jnp.full(ep_moe.moe_state_shape(mesh, 4), 0.9)
        res = jax.jit(lambda p, m: tf.prefill_forward(
            p, cfg, rcfg, batch, m, cache_len=20,
            placement=place))(perm, m)
    assert float(res.aux["drop_frac"]) == 0.0, res.aux["drop_frac"]
    e1 = float(jnp.max(jnp.abs(loc.logits - ref.logits)))
    e2 = float(jnp.max(jnp.abs(res.logits - ref.logits)))
    assert e1 < 5e-3 and e2 < 5e-3, (e1, e2)


def check_async_migrate_chunks_match_sync_under_ep():
    """Async tentpole on a real (2,4) mesh: draining a staged per-layer
    plan chunk-by-chunk (subset gathers on the mesh-resident stacked
    weights, per-layer table commits) must leave params bitwise-equal to
    the one-shot synchronous apply — and the model must produce the same
    logits through either copy under the committed tables."""
    from repro.configs import PlacementConfig
    from repro.placement import PlacementManager, apply_to_params
    from repro.serving.async_migrate import MigrationExecutor

    cfg = reduced(get_config("olmoe-1b-7b"), n_layers=2)
    rcfg = ReaLBConfig(gate_gamma=10 ** 9)
    params = tf.init_model(cfg, jax.random.PRNGKey(0))

    def mk():
        mgr = PlacementManager(cfg, PlacementConfig(
            replan_every=2, warmup_iters=1, min_gain=0.0,
            per_layer=True), 4)
        es = np.zeros((2, 2, cfg.moe.num_experts))
        es[0, 0] = [10.0, 8, 1, 1, 1, 1, 1, 1]
        es[1, 0] = [1.0, 1, 1, 1, 1, 1, 8, 10]
        es[:, 1] = es[:, 0] * 0.5
        mgr.observe(es)
        return mgr, mgr.maybe_replan(2)

    mesh = make_mesh((2, 4), ("data", "model"))
    with use_mesh(mesh):
        m_sync, p_sync = mk()
        m_async, p_async = mk()
        assert p_sync is not None and len(m_sync.plan_layers(p_sync)) == 2
        np.testing.assert_array_equal(p_sync.gather_idx, p_async.gather_idx)
        ref = apply_to_params(params, p_sync)
        m_sync.commit(p_sync)
        ex = MigrationExecutor(m_async, p_async, bytes_per_iter=1)
        out = params
        while ex.draining:
            out, _ = ex.drain(out)
        assert ex.n_drains == 2          # one chunk (layer) per drain
        for key in ("w_gate", "w_up", "w_down"):
            a = np.asarray(ref["blocks"]["layer0"]["moe"][key])
            b = np.asarray(out["blocks"]["layer0"]["moe"][key])
            assert np.array_equal(a, b), key
        for a, b in zip(m_sync.tables, m_async.tables):
            np.testing.assert_array_equal(a.e2r, b.e2r)
        assert m_async.bandwidth.calibrated
        rng = np.random.default_rng(0)
        tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 16)),
                             jnp.int32)
        m = jnp.full(ep_moe.moe_state_shape(mesh, 4), 0.9)
        place = tuple(jnp.asarray(t) for t in m_async.device_tables())
        r_ref = jax.jit(lambda p, m: tf.prefill_forward(
            p, cfg, rcfg, {"tokens": tokens}, m, cache_len=20,
            placement=place))(ref, m)
        r_out = jax.jit(lambda p, m: tf.prefill_forward(
            p, cfg, rcfg, {"tokens": tokens}, m, cache_len=20,
            placement=place))(out, m)
        assert np.array_equal(np.asarray(r_ref.logits),
                              np.asarray(r_out.logits))


def check_replica_capacity_reduced_cap():
    """Replica-aware capacity on the (2,4) mesh: at the post-split-derived
    reduced ``capacity_factor`` the skewed stream routes with zero drops
    through the replicated dispatch, while the bijective layout at the
    same cap overflows its per-rank buffer."""
    from repro.replication import ReplicaSet, expand_moe_params

    cfg, p, x, mod = _moe_setup()
    e = cfg.moe.num_experts
    # a deterministically hot expert 0: feature 0 of every token is a
    # constant 1.0 and only expert 0's router column reads it
    p = dict(p)
    p["router"] = p["router"].at[0, :].set(0.0).at[0, 0].set(8.0)
    x = x.at[..., 0].set(1.0)
    rcfg = ReaLBConfig(gate_gamma=10 ** 9)
    # expert 0 replicated onto rank 2's spare slot
    rep_pos = np.zeros((e, 2), np.int32)
    for ex in range(e):
        rep_pos[ex] = (ex // 2) * 3 + (ex % 2)
    rep_pos[0, 1] = 3 * 3 + 2        # replica on the coldest rank's spare
    n_rep = np.ones(e, np.int32)
    n_rep[0] = 2
    rs = ReplicaSet(rep_pos, n_rep, 4, 3)
    # observe the skew at the generous default cap, then derive the
    # reduced factor from the post-split peak rank load
    _, _, aux = ep_moe.ep_moe_forward(
        p, x, cfg, rcfg, jnp.full((1, 1), 0.9), mod, mode="dispatch")
    el = np.asarray(aux["expert_load"])
    assert el[0] / el.sum() > 0.4, el           # genuinely hot
    f_red = rs.capacity_factor(el, margin=1.2)
    # the bijective peak does NOT fit the reduced buffer
    ident = ReplicaSet.identity(e, 4, slots_per_rank=3, max_replicas=2)
    assert ident.rank_loads(el).max() > el.sum() / 4 * f_red
    cfg_red = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=f_red))
    wrapped = {"blocks": {"l0": {"moe": p}}}
    place_rep = tuple(jnp.asarray(a) for a in rs.as_arrays())
    place_bij = tuple(jnp.asarray(a) for a in ident.as_arrays())
    p_rep = dict(expand_moe_params(wrapped, rs)["blocks"]["l0"]["moe"],
                 router=p["router"])
    p_bij = dict(expand_moe_params(wrapped, ident)["blocks"]["l0"]["moe"],
                 router=p["router"])
    mesh = make_mesh((2, 4), ("data", "model"))
    with use_mesh(mesh):
        m = jnp.full(ep_moe.moe_state_shape(mesh, 4), 0.9)
        _, _, aux_rep = jax.jit(
            lambda p, x, m, mod, pl: ep_moe.ep_moe_forward(
                p, x, cfg_red, rcfg, m, mod, mode="dispatch",
                placement=pl))(p_rep, x, m, mod, place_rep)
        _, _, aux_bij = jax.jit(
            lambda p, x, m, mod, pl: ep_moe.ep_moe_forward(
                p, x, cfg_red, rcfg, m, mod, mode="dispatch",
                placement=pl))(p_bij, x, m, mod, place_bij)
    drop_rep = float(aux_rep["drop_frac"])
    drop_bij = float(aux_bij["drop_frac"])
    assert drop_rep == 0.0, drop_rep            # split fits the reduced cap
    assert drop_bij > 0.0, (drop_bij, f_red)    # bijective overflows it


def check_model_train_step_under_mesh():
    """Tiny full model: distributed train step ≈ single-device step."""
    from repro.optim import adamw
    from repro.configs import TrainConfig

    cfg = reduced(get_config("olmoe-1b-7b"), n_layers=2)
    # zero the aux-loss coefficients (the LB loss is *defined* per EP group,
    # so its gradient legitimately differs between 1 global group and
    # per-data-row groups) and make capacity drop-free (cap ≥ t·k: the
    # tiny per-source-per-dest buffers would otherwise drop a few routed
    # items that the single-device ep=1 reference keeps).
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, aux_loss_coef=0.0,
                                     router_z_coef=0.0,
                                     capacity_factor=8.0))
    rcfg = ReaLBConfig(gate_gamma=10 ** 9)
    tcfg = TrainConfig(lr=1e-3)
    params = tf.init_model(cfg, jax.random.PRNGKey(0))
    opt = adamw.init_opt_state(params, tcfg)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 16)), jnp.int32)
    batch = {"tokens": tokens, "labels": tokens}

    def loss_fn(params, m):
        return tf.train_loss(params, cfg, rcfg, batch, m)

    m0 = jnp.full((1, 1), 0.9)
    (l_ref, _), g_ref = jax.value_and_grad(loss_fn, has_aux=True)(params, m0)

    mesh = make_mesh((2, 4), ("data", "model"))
    with use_mesh(mesh):
        m = jnp.full(ep_moe.moe_state_shape(mesh, 4), 0.9)
        (l_d, _), g_d = jax.jit(jax.value_and_grad(
            lambda p, m: tf.train_loss(p, cfg, rcfg, batch, m),
            has_aux=True))(params, m)
    assert abs(float(l_d) - float(l_ref)) < 5e-3, (float(l_d), float(l_ref))
    errs = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), g_ref, g_d)
    worst = max(jax.tree.leaves(errs))
    assert worst < 5e-3, worst


def check_decode_under_mesh():
    """Prefill + decode of a tiny model under the mesh: finite and
    consistent with the single-device path."""
    cfg = reduced(get_config("olmoe-1b-7b"), n_layers=2)
    rcfg = ReaLBConfig(gate_gamma=10 ** 9)
    params = tf.init_model(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 16)), jnp.int32)
    batch = {"tokens": tokens}

    res_ref = tf.prefill_forward(params, cfg, rcfg, batch,
                                 jnp.full((1, 1), 0.9), cache_len=20)
    db = {"tokens": tokens[:, :1], "pos": jnp.full((4,), 16, jnp.int32)}
    dec_ref = tf.decode_forward(params, cfg, rcfg, db, res_ref.cache,
                                res_ref.m_state)

    mesh = make_mesh((2, 4), ("data", "model"))
    with use_mesh(mesh):
        m = jnp.full(ep_moe.moe_state_shape(mesh, 4), 0.9)
        res = jax.jit(lambda p, m: tf.prefill_forward(
            p, cfg, rcfg, batch, m, cache_len=20))(params, m)
        dec = jax.jit(lambda p, c, m: tf.decode_forward(
            p, cfg, rcfg, db, c, m))(params, res.cache, res.m_state)
    e1 = float(jnp.max(jnp.abs(res.logits - res_ref.logits)))
    e2 = float(jnp.max(jnp.abs(dec.logits - dec_ref.logits)))
    assert e1 < 5e-3 and e2 < 5e-3, (e1, e2)


def check_elastic_reshard():
    """Params sharded on a (2,4) mesh move to a (1,4) mesh (lost 'data'
    slice) and produce identical outputs."""
    from repro.models.common import named_sharding

    cfg = reduced(get_config("qwen1.5-0.5b"), n_layers=2)
    params = tf.init_model(cfg, jax.random.PRNGKey(0))
    mesh_a = make_mesh((2, 4), ("data", "model"))
    mesh_b = make_mesh((1, 4), ("data", "model"), jax.devices()[:4])
    rcfg = ReaLBConfig()
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 16)), jnp.int32)
    batch = {"tokens": tokens, "labels": tokens}
    m = jnp.full((1, 1), 0.9)
    l_ref, _ = tf.train_loss(params, cfg, rcfg, batch, m)

    # place on A, pull to host, re-place on B (checkpoint-free reshard)
    from repro.models.common import resolve_spec
    from jax.sharding import NamedSharding

    def place(tree, mesh):
        return jax.tree.map(lambda a: jax.device_put(a, NamedSharding(
            mesh, resolve_spec(a.shape, (None,) * a.ndim, mesh))), tree)

    pa = place(params, mesh_a)
    host = jax.tree.map(lambda a: np.asarray(a), pa)
    pb = place(host, mesh_b)
    with use_mesh(mesh_b):
        l_b, _ = jax.jit(lambda p, m: tf.train_loss(
            p, cfg, rcfg, batch, m))(pb, m)
    assert abs(float(l_b) - float(l_ref)) < 1e-3


def check_weighted_split_under_ep():
    """Weighted per-replica token splitting on the (2,4) mesh: an
    equal-share schedule is bitwise-identical to the 3-table round-robin
    path, and a skewed schedule shifts the replica's share of the hot
    expert's tokens to the scheduled quota (within shard quantization)."""
    from repro.replication import ReplicaSet, expand_moe_params

    cfg, p, x, mod = _moe_setup()
    e = cfg.moe.num_experts
    p = dict(p, router=p["router"].at[:, 0].add(4.0))    # expert 0 hot
    rcfg = ReaLBConfig(gate_gamma=10 ** 9)
    rep_pos = np.zeros((e, 2), np.int32)
    for ex in range(e):
        rep_pos[ex] = (ex // 2) * 3 + (ex % 2)
    rep_pos[0, 1] = 2 * 3 + 2
    n_rep = np.ones(e, np.int32)
    n_rep[0] = 2
    rs = ReplicaSet(rep_pos, n_rep, 4, 3)
    wrapped = {"blocks": {"l0": {"moe": p}}}
    p_rep = dict(expand_moe_params(wrapped, rs)["blocks"]["l0"]["moe"],
                 router=p["router"])
    base = tuple(jnp.asarray(a) for a in rs.as_arrays())

    def run(place):
        with use_mesh(mesh):
            m = jnp.full(ep_moe.moe_state_shape(mesh, 4), 0.9)
            return jax.jit(
                lambda p, x, m, mod, pl: ep_moe.ep_moe_forward(
                    p, x, cfg, rcfg, m, mod, mode="dispatch",
                    placement=pl))(p_rep, x, m, mod, place)

    mesh = make_mesh((2, 4), ("data", "model"))
    y3, _, aux3 = run(base)
    # equal-share schedule == occ % n_rep: the 4-table path is bitwise
    # the 3-table path
    sched_eq = jnp.asarray(rs.split_schedule())
    y4, _, aux4 = run(base + (sched_eq,))
    assert np.array_equal(np.asarray(y3), np.asarray(y4))
    assert np.array_equal(np.asarray(aux3["slot_load"]),
                          np.asarray(aux4["slot_load"]))

    # skewed 2:1 schedule: the primary keeps ~2/3 of the hot expert
    w = np.zeros((e, 2))
    w[:, 0] = 1.0
    w[0] = [2.0, 1.0]
    y_w, _, aux_w = run(base + (jnp.asarray(rs.split_schedule(w)),))
    el = np.asarray(aux_w["expert_load"])
    sl = np.asarray(aux_w["slot_load"])
    a, b = sl[rs.rep_pos[0, 0]], sl[rs.rep_pos[0, 1]]
    assert a + b == el[0], (a, b, el[0])            # zero dropped tokens
    # 8 shard-local counters each quantize the 12-phase schedule: allow
    # one assignment of slack per shard around the exact 2/3 quota
    assert abs(a - 2.0 * el[0] / 3.0) <= 8.0, (a, el[0])
    assert a > b > 0
    # outputs stay correct under the skewed split (same expert math,
    # different replica routing)
    y_ref, _, _ = ep_moe.ep_moe_forward(
        p, x, cfg, rcfg, jnp.full((1, 1), 0.9), mod, mode="dispatch")
    err = float(jnp.max(jnp.abs(y_w - y_ref)))
    assert err < 5e-5, err


def check_elastic_kill_rejoin_under_ep():
    """Kill/rejoin of EP rank 2 on the (2,4) mesh, full elastic cycle:
    the replicated expert stays routable the same iteration with zero
    dropped tokens, stranded singletons land on the dead (zeroed) slots
    and are re-materialized from checkpoint through the byte-budgeted
    executor, the recovered path is bitwise-identical to a healthy
    engine on the final tables, and the rejoined rank hosts replicas
    again after its warm-up plan lands."""
    import tempfile

    from repro.checkpoint import ckpt
    from repro.configs import ReplicationConfig
    from repro.replication import ReplicaManager, ReplicaSet, \
        expand_moe_params
    from repro.serving.async_migrate import MigrationExecutor
    from repro.serving.elastic import ElasticCoordinator

    cfg, p, x, mod = _moe_setup()
    e = cfg.moe.num_experts
    p = dict(p, router=p["router"].at[:, 0].add(4.0))    # expert 0 hot
    rcfg = ReaLBConfig(gate_gamma=10 ** 9)
    rpcfg = ReplicationConfig(enabled=True, spare_per_rank=1,
                              max_replicas=2, replan_every=1,
                              warmup_iters=0, min_gain=0.0)
    mgr = ReplicaManager.from_geometry(e, rpcfg, 4, bytes_per_expert=256)
    spr = mgr.slots_per_rank
    assert spr == 3
    # expert 0 replicated onto rank 2's spare; identity otherwise
    rep_pos = np.zeros((e, 2), np.int32)
    for ex in range(e):
        rep_pos[ex] = (ex // 2) * spr + (ex % 2)
    rep_pos[0, 1] = 2 * spr + 2
    n_rep = np.ones(e, np.int32)
    n_rep[0] = 2
    mgr.rsets[0] = ReplicaSet(rep_pos, n_rep, 4, spr)
    wrapped = {"blocks": {"l0": {"moe": p}}}
    params = expand_moe_params(wrapped, mgr.rset)
    params["blocks"]["l0"]["moe"]["router"] = p["router"]

    tmp = tempfile.mkdtemp()
    ckpt.save(tmp, 0, {"serving": {"params": params,
                                   "m_state": np.zeros((1, 4))},
                       mgr.ckpt_group: mgr.state_dict()})
    co = ElasticCoordinator(mgr, ckpt_dir=tmp)

    mesh = make_mesh((2, 4), ("data", "model"))

    def run(params):
        place = tuple(jnp.asarray(a) for a in mgr.device_tables())
        moe = params["blocks"]["l0"]["moe"]
        with use_mesh(mesh):
            m = jnp.full(ep_moe.moe_state_shape(mesh, 4), 0.9)
            return jax.jit(
                lambda p, x, m, mod, pl: ep_moe.ep_moe_forward(
                    p, x, cfg, rcfg, m, mod, mode="dispatch",
                    placement=pl))(moe, x, m, mod, place)

    y_ref, _, aux_ref = ep_moe.ep_moe_forward(
        p, x, cfg, rcfg, jnp.full((1, 1), 0.9), mod, mode="dispatch")
    el_ref = np.asarray(aux_ref["expert_load"])

    # ---- kill rank 2: experts 4, 5 are stranded singletons; the hot
    # expert 0 keeps its rank-0 primary routable the same iteration
    params = co.fail_rank(2, params)
    assert sorted(co.lost_experts.tolist()) == [4, 5]
    assert co.state == "degraded"
    # no live expert routes to the dead rank (lost experts keep their
    # dead-slot rows by design — that is where lost tokens are counted)
    for ex in range(e):
        if ex in (4, 5):
            continue
        ranks = mgr.rset.rep_pos[ex, :mgr.rset.n_rep[ex]] // spr
        assert 2 not in ranks.tolist(), ex
    assert mgr.rset.n_rep[0] == 1                # replica masked off
    assert mgr.rset.rep_pos[0, 0] == 0           # primary survives

    y_deg, _, aux_deg = run(params)
    el = np.asarray(aux_deg["expert_load"])
    sl = np.asarray(aux_deg["slot_load"])
    assert np.array_equal(el, el_ref)            # routing itself unchanged
    # zero dropped tokens for every live expert: its slot loads sum to
    # its expert load exactly
    for ex in range(e):
        if ex in (4, 5):
            continue
        slots = np.unique(mgr.rset.rep_pos[ex, :mgr.rset.n_rep[ex]])
        assert sl[slots].sum() == el[ex], (ex, sl[slots], el[ex])
    # stranded tokens landed on the dead rank's zeroed slots, counted
    assert sl[2 * spr + 0] == el[4] and sl[2 * spr + 1] == el[5]
    es = np.stack([el, np.zeros(e)])[None]
    assert co.lost_token_count(es) == el[4] + el[5]
    # the physical mesh minus the dead model slice
    assert co.effective_mesh(mesh, lost_axis="model").devices.shape \
        == (2, 3)

    # ---- recovery: event replan onto the 3 live ranks, recovery chunks
    # first, checkpoint rows patched in pre-commit
    mgr.observe(es)
    plan = mgr.maybe_replan(1)
    assert plan is not None
    ex_mig = MigrationExecutor(mgr, plan, bytes_per_iter=1 << 30,
                               priority_layers=co.recovery_layers(plan),
                               patch_fn=co.patch_params)
    while ex_mig.draining:
        params, rep = ex_mig.drain(params)
        co.on_layers_landed(plan, rep.layers)
    assert not co.recovering
    assert co.last_recovery_s is not None
    assert not mgr.rset.hosts_rank(2)

    # bitwise parity with the healthy path: a fresh expansion of the
    # logical weights onto the recovered tables gives identical logits
    p_healthy = expand_moe_params(wrapped, mgr.rset)
    p_healthy["blocks"]["l0"]["moe"]["router"] = p["router"]
    y_rec, _, aux_rec = run(params)
    y_h, _, _ = run(p_healthy)
    assert np.array_equal(np.asarray(y_rec), np.asarray(y_h))
    err = float(jnp.max(jnp.abs(y_rec - y_ref)))
    assert err < 5e-5, err
    # every expert routable again: slot loads cover every expert load
    sl = np.asarray(aux_rec["slot_load"])
    for ex in range(e):
        slots = np.unique(mgr.rset.rep_pos[ex, :mgr.rset.n_rep[ex]])
        assert sl[slots].sum() == el_ref[ex], ex

    # ---- rejoin: plannable at once, routable only after the staged
    # warm-up plan lands
    co.rejoin_rank(2)
    assert co.state == "warming"
    assert not mgr.hosts_rank(2)
    mgr.observe(es)
    plan2 = mgr.maybe_replan(2)
    assert plan2 is not None
    assert not mgr.hosts_rank(2)                 # staged, not routable
    ex_mig2 = MigrationExecutor(mgr, plan2, bytes_per_iter=1 << 30,
                                priority_layers=co.recovery_layers(plan2),
                                patch_fn=co.patch_params)
    while ex_mig2.draining:
        params, rep = ex_mig2.drain(params)
        co.on_layers_landed(plan2, rep.layers)
    assert co.state == "healthy"
    assert mgr.hosts_rank(2)
    y_fin, _, _ = run(params)
    err = float(jnp.max(jnp.abs(y_fin - y_ref)))
    assert err < 5e-5, err


def check_collective_census_reconciles():
    """Three independent derivations of the dispatch path's collective
    traffic on the (2,4) mesh must agree: the traced jaxpr census, the
    post-XLA HLO census (while-loop trip counts multiplied through) and
    the FlopByteLedger's analytic graph prediction.  An extra psum or a
    silently widened all-to-all payload breaks one of the three."""
    from repro.analysis.jaxpr_audit import collective_census_jaxpr
    from repro.launch.hlo_analysis import collective_census
    from repro.obs.ledger import FlopByteLedger

    cfg, p, x, mod = _moe_setup()
    rcfg = ReaLBConfig(gate_gamma=10 ** 9)
    L = 3
    mesh = make_mesh((2, 4), ("data", "model"))

    def fwd(p, x, m):
        def step(carry, _):
            x_c, m_c = carry
            y, m_n, aux = ep_moe.ep_moe_forward(p, x_c, cfg, rcfg, m_c,
                                                mod, mode="dispatch")
            # return the full aux so no psum is dead code post-XLA
            return (y, m_n), aux
        return jax.lax.scan(step, (x, m), None, length=L)

    with use_mesh(mesh):
        m = jnp.full(ep_moe.moe_state_shape(mesh, 4), 0.9)
        closed = jax.make_jaxpr(fwd)(p, x, m)
        hlo = jax.jit(fwd).lower(p, x, m).compile().as_text()

    jx = collective_census_jaxpr(closed)
    # per-device tokens entering the layer: batch 4/2 x seq 16/4
    led = FlopByteLedger(cfg, ep=4).predict_graph_census(
        t_local=8, layers=L, itemsize=x.dtype.itemsize)
    # jaxpr == ledger, exactly: same capacity formula, same shapes
    for kind in ("all_to_all", "psum"):
        assert jx.get(kind) == led[kind], (kind, jx.get(kind), led[kind])

    hl = collective_census(hlo)
    # program-issued collectives only ("user"): the partitioner also
    # inserts all-reduces to aggregate the harness's sharded aux outputs
    a2a = hl["user"].get("all-to-all", {"count": 0, "bytes": 0})
    ar = hl["user"].get("all-reduce", {"count": 0, "bytes": 0})
    assert a2a["count"] == led["all_to_all"]["count"], (a2a, led)
    assert a2a["bytes"] == led["all_to_all"]["bytes"], (a2a, led)
    # psum lowers to all-reduce; XLA may merge several and hoist
    # loop-invariant scalar psums out of the scan (count <=, bytes
    # within a few hoisted scalars of the prediction)
    assert 0 < ar["count"] <= led["psum"]["count"], (ar, led)
    pred_b = led["psum"]["bytes"]
    assert abs(ar["bytes"] - pred_b) / pred_b <= 0.05, (ar, led)
    # the steady-state body is loop-carried with the full trip count
    assert hl["layers"] == L, hl["layers"]
    # and the ledger's *routed* ICI bytes never exceed the graph's
    # capacity-buffer bytes (the buffers are what actually moves)
    t_global = 4 * 16
    a2a_routed = (t_global * cfg.moe.top_k / 4 * 3 / 4
                  * cfg.d_model * 2.0) * 4 * 2 * L
    graph_global = led["all_to_all"]["bytes"] * 8  # 8 devices
    assert a2a_routed <= graph_global, (a2a_routed, graph_global)


def check_kernel_fp4_parity_under_ep():
    """Pallas grouped FP4 FFN + quantize kernels wired into the hot loop
    (interpret mode on CPU): FP4 genuinely fires on the (2,4) mesh and the
    kernel output matches the jnp fallback *at the same sharding* to
    float-reassociation noise.  (Local-vs-mesh is NOT compared under FP4:
    the per-tensor global scale is computed per weight slab, so the local
    one-slab and mesh four-slab quantizations legitimately differ.)"""
    from repro.kernels import ops as kops
    cfg, p, x, mod = _moe_setup()
    p = dict(p)   # skew routing: rank 0 hot + all-vision -> FP4 fires
    p["router"] = p["router"].at[:, 0].add(3.0).at[:, 1].add(2.5)
    vis = jnp.ones_like(mod)
    rcfg = ReaLBConfig(gate_gamma=1)
    mesh = make_mesh((2, 4), ("data", "model"))

    def run(local):
        if local:
            return ep_moe.ep_moe_forward(
                p, x, cfg, rcfg, jnp.zeros((1, 4)), vis, mode="dispatch")
        with use_mesh(mesh):
            m = jnp.zeros(ep_moe.moe_state_shape(mesh, 4))
            return jax.jit(lambda p, x, m, mod: ep_moe.ep_moe_forward(
                p, x, cfg, rcfg, m, mod, mode="dispatch"))(p, x, m, vis)

    kops.set_ffn_backend("interpret")
    try:
        assert kops.ffn_fused()
        y_loc_k, _, aux_loc = run(local=True)
        y_mesh_k, _, aux_mesh = run(local=False)
    finally:
        kops.set_ffn_backend(None)
    assert float(aux_loc["fp4_ranks"]) >= 1.0, float(aux_loc["fp4_ranks"])
    assert float(aux_mesh["fp4_ranks"]) >= 1.0, float(aux_mesh["fp4_ranks"])
    y_loc_j, _, _ = run(local=True)          # default backend: jnp on CPU
    y_mesh_j, _, _ = run(local=False)
    d_loc = float(jnp.max(jnp.abs(y_loc_k - y_loc_j)))
    d_mesh = float(jnp.max(jnp.abs(y_mesh_k - y_mesh_j)))
    assert d_loc < 1e-3, d_loc
    assert d_mesh < 1e-3, d_mesh
    # and the quantization really happened: FP4 output != a bf16 run
    y_off, _, _ = ep_moe.ep_moe_forward(
        p, x, cfg, ReaLBConfig(enabled=False), jnp.zeros((1, 4)), vis,
        mode="dispatch")
    assert float(jnp.max(jnp.abs(y_mesh_k - y_off))) > 1e-6


def check_fp4_transform_placement_under_ep():
    """EP=4 on the (2,4) mesh: both paths quantize inside the expert
    cond's FP4 branch, so the expert stacks feed one cond and no cond
    yields FP4 codes (uint8)."""
    from repro.analysis.jaxpr_audit import _walk
    cfg, p, x, mod = _moe_setup()
    e = cfg.moe
    rcfg = ReaLBConfig(gate_gamma=1, capacity_c=0.5)
    stacks = {(e.num_experts // 4, cfg.d_model, e.d_ff),
              (e.num_experts // 4, e.d_ff, cfg.d_model)}
    mesh = make_mesh((2, 4), ("data", "model"))
    for mode, xs in (("dispatch", x), ("broadcast", x[:, :1])):
        with use_mesh(mesh):
            m = jnp.zeros(ep_moe.moe_state_shape(mesh, 4))
            closed = jax.make_jaxpr(
                lambda p_, x_, m_: ep_moe.ep_moe_forward(
                    p_, x_, cfg, rcfg, m_, mod[:, :x_.shape[1]],
                    mode=mode))(p, xs, m)
        conds = []
        _walk(closed.jaxpr, lambda eqn, mult: conds.append(eqn)
              if eqn.primitive.name == "cond" else None)
        fed = [c for c in conds
               if any(getattr(v.aval, "shape", None) in stacks
                      for v in c.invars)]
        u8 = [c for c in conds
              if any(v.aval.dtype == jnp.uint8 for v in c.outvars)]
        assert len(fed) == 1, (mode, len(fed))
        assert not u8, (mode, len(u8))


CHECKS = {k[len("check_"):]: v for k, v in list(globals().items())
          if k.startswith("check_")}

if __name__ == "__main__":
    name = sys.argv[1]
    CHECKS[name]()
    print(f"OK {name}")
