"""Jaxpr auditor: callback / f64 / widening detection, the collective
census with scan multipliers, and the hot-path audit of the real MoE
layer (local path must be collective-free and clean)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.jaxpr_audit import (DEFAULT_WIDEN_ALLOWLIST,
                                        audit_jaxpr,
                                        collective_census_jaxpr)
from repro.configs import ReaLBConfig, get_config, reduced
from repro.core import ep_moe


# --------------------------------------------------------------------------
# rule detection on handcrafted traces
# --------------------------------------------------------------------------
def test_clean_fn_passes():
    rep = audit_jaxpr(jax.make_jaxpr(lambda x: jnp.sin(x) * 2)(
        jnp.ones(4)))
    assert rep.ok and rep.n_eqns > 0 and rep.census == {}


def test_callback_flagged():
    def f(x):
        y = jax.pure_callback(lambda v: np.asarray(v) + 1, x, x)
        return y * 2

    rep = audit_jaxpr(jax.make_jaxpr(f)(jnp.ones(4, jnp.float32)))
    assert [v.kind for v in rep.violations] == ["callback"]
    assert "round trip" in rep.violations[0].detail


def test_f64_flagged_and_waivable():
    with jax.enable_x64():
        closed = jax.make_jaxpr(lambda x: x * 2.0)(np.ones(4, np.float64))
    rep = audit_jaxpr(closed)
    assert any(v.kind == "f64" for v in rep.violations)
    assert audit_jaxpr(closed, allow_f64=True).ok


def test_widening_violation_on_dispatch_path_only():
    def f(x):
        with jax.named_scope("dispatch"):
            return x.astype(jnp.float32) * 2

    closed = jax.make_jaxpr(f)(jnp.ones(4, jnp.bfloat16))
    rep = audit_jaxpr(closed)
    assert [v.kind for v in rep.violations] == ["widening"]
    assert "dispatch" in rep.violations[0].where
    # same widening is legal when the scope names an allowlisted phase
    assert "route" in DEFAULT_WIDEN_ALLOWLIST

    def g(x):
        with jax.named_scope("dispatch"), jax.named_scope("route"):
            return x.astype(jnp.float32) * 2

    rep2 = audit_jaxpr(jax.make_jaxpr(g)(jnp.ones(4, jnp.bfloat16)))
    assert rep2.ok
    # ...and recorded either way
    assert rep.widenings and rep2.widenings
    assert rep.widenings[0]["src"] == "bfloat16"


def test_widening_off_fp4_path_recorded_not_flagged():
    def f(x):
        with jax.named_scope("misc"):
            return x.astype(jnp.float32) * 2

    rep = audit_jaxpr(jax.make_jaxpr(f)(jnp.ones(4, jnp.bfloat16)))
    assert rep.ok and len(rep.widenings) == 1


def test_subbyte_dequant_widening_always_legal():
    def f(x):
        with jax.named_scope("dispatch"):
            return x.astype(jnp.bfloat16) * 2

    rep = audit_jaxpr(jax.make_jaxpr(f)(
        jnp.ones(4, jnp.float8_e4m3fn)))
    assert rep.ok and rep.widenings          # seen, but it IS the dequant


# --------------------------------------------------------------------------
# collective census
# --------------------------------------------------------------------------
def _shard_mapped_psum():
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("x",), jax.devices()[:1])
    P = jax.sharding.PartitionSpec

    def inner(x):
        def step(c, _):
            return c + jax.lax.psum(x, "x"), None
        y, _ = jax.lax.scan(step, jnp.zeros_like(x), None, length=3)
        return y

    return jax.shard_map(inner, mesh=mesh, in_specs=(P("x"),),
                         out_specs=P("x"), check_vma=False)


def test_census_multiplies_scan_trips():
    f = _shard_mapped_psum()
    closed = jax.make_jaxpr(f)(jnp.ones(4, jnp.float32))
    census = collective_census_jaxpr(closed)
    assert census == {"psum": {"count": 3, "bytes": 3 * 4 * 4}}
    # the full audit carries the same census
    assert audit_jaxpr(closed, allow_f64=True).census == census


# --------------------------------------------------------------------------
# the real hot path
# --------------------------------------------------------------------------
@pytest.fixture(scope="module")
def moe():
    cfg = reduced(get_config("olmoe-1b-7b"))
    e = cfg.moe
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    D, F, E = cfg.d_model, e.d_ff, e.num_experts
    p = {
        "router": jax.random.normal(ks[0], (D, E)) * 0.2,
        "w_gate": jax.random.normal(ks[1], (E, D, F)) / np.sqrt(D),
        "w_up": jax.random.normal(ks[2], (E, D, F)) / np.sqrt(D),
        "w_down": jax.random.normal(ks[3], (E, F, D)) / np.sqrt(F),
    }
    x = jax.random.normal(ks[4], (2, 16, D)) * 0.5
    mod = jax.random.bernoulli(ks[5], 0.6, (2, 16))
    return cfg, p, x, mod


def test_local_moe_path_audits_clean(moe):
    """Single-host ep_moe (FP4 policy active): no callbacks, no f64, no
    collectives, every dispatch-path widening allowlisted."""
    cfg, p, x, mod = moe
    rcfg = ReaLBConfig(gate_gamma=1e-6)      # policy ON: fp4 branch live
    m = jnp.full((1, 1), 0.9)
    closed = jax.make_jaxpr(
        lambda p_, x_, m_: ep_moe.ep_moe_forward(
            p_, x_, cfg, rcfg, m_, mod, mode="dispatch"))(p, x, m)
    rep = audit_jaxpr(closed)
    assert rep.ok, [v.format() for v in rep.violations]
    assert rep.census == {}, "local path must not emit collectives"
    assert rep.n_eqns > 50


@pytest.mark.parametrize("mode", ["dispatch", "broadcast"])
def test_fp4_branch_quantize_ops_are_audited(moe, mode):
    """The FP4 branch quantizes under ``quantize_fp4``, one of the scopes
    the widening check watches: a bf16 layer's f32 scale arithmetic shows
    there, and it and the other by-design f32 phases of the expert FFN
    are on the allowlist, so the bf16 layer audits clean."""
    cfg, p, x, mod = moe
    p = {n: v.astype(jnp.bfloat16) if n != "router" else v
         for n, v in p.items()}
    x = x.astype(jnp.bfloat16)
    if mode == "broadcast":
        x, mod = x[:, :1], mod[:, :1]
    closed = jax.make_jaxpr(
        lambda p_, x_: ep_moe.ep_moe_forward(
            p_, x_, cfg, ReaLBConfig(gate_gamma=1e-6), jnp.zeros((1, 1)),
            mod, mode=mode))(p, x)
    rep = audit_jaxpr(closed)
    assert any("quantize_fp4" in w["where"] and w["src"] == "bfloat16"
               for w in rep.widenings), rep.widenings
    assert rep.ok, [v.format() for v in rep.violations]
