"""Architectures found by name: a configuration without ``"arch"`` runs
``gqa_moe``, a new module is picked up from its file alone, the one-device
draw is the one the cells were measured with, and the reference runs FP4
exactly on the EP ranks the policy flagged."""
import hashlib
import json
import textwrap
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _paths
import _tiny
from harness import arch, policy, weights
from harness.reference import HI, a4, nvfp4_weights

# sha256 of the tiny configuration's draw at seed 2**40 + 2026, as the
# benchmark drew it before architectures moved into bench/archs/
TINY_DRAW = "b490add0a3b0bbcb42c4334110b3a3642029a648bb9dcd6e5b45c3928089b58c"


def _conf(name):
    return json.loads((_paths.BENCH / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", ["moonlight-5l", "olmoe-8l"])
def test_config_without_arch_runs_gqa_moe(name):
    conf = _conf(name)
    assert "arch" not in conf
    mod = arch.module(conf)
    assert mod is arch.module(dict(conf, arch="gqa_moe"))
    assert mod.__file__ == str(_paths.BENCH / "archs" / "gqa_moe.py")
    assert mod.sizes(conf).n_layers == conf["num_hidden_layers"]


def test_new_arch_module_is_found_by_name(tmp_path):
    """A later configuration brings ``bench/archs/<arch>.py``; nothing in
    the harness names it."""
    (tmp_path / "bench" / "archs").mkdir(parents=True)
    (tmp_path / "bench" / "archs" / "toy_mla.py").write_text(textwrap.dedent(
        '''
        from dataclasses import dataclass

        @dataclass(frozen=True)
        class Sizes:
            vocab: int
            kv_lora_rank: int

        def sizes(conf):
            return Sizes(conf["vocab_size"], conf["kv_lora_rank"])

        def quantize_work(a, experts):
            return 7.0 * experts
        '''))
    conf = {"arch": "toy_mla", "vocab_size": 32, "kv_lora_rank": 512}
    mod, a = arch.of(conf, root=tmp_path)
    assert (a.vocab, a.kv_lora_rank) == (32, 512)
    assert mod.quantize_work(a, 16) == 112.0
    with pytest.raises(FileNotFoundError):
        arch.of(dict(conf, arch="missing"), root=tmp_path)


def test_one_device_draw_is_unchanged():
    mod, a = arch.of(_tiny.config())
    params = weights.draw(mod, a, 2 ** 40 + 2026)
    h = hashlib.sha256()
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    for path, leaf in sorted(flat, key=lambda kv: jax.tree_util.keystr(kv[0])):
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == TINY_DRAW


# ---------------------------------------------------------------- per rank
@pytest.fixture(scope="module")
def layer():
    """One MoE layer of the tiny configuration (16 experts, top-6), in f32,
    and its input."""
    mod, a = arch.of(_tiny.config())
    params = weights.draw(mod, a, 11)
    p = jax.tree.map(lambda v: v[0].astype(jnp.float32),
                     params["blocks"]["layer0"])
    p = {k: p[k] for k in ("norm2", "moe", "shared")}
    x = jax.random.normal(jax.random.PRNGKey(5), (24, a.d_model))
    return mod, a, p, x


def _moe(mod, a, p, x, flags):
    with jax.default_matmul_precision("highest"):
        return np.asarray(mod.moe(p, x, jnp.asarray(flags), a=a,
                                  mode="f32", fp4=bool(flags.any())))


def test_fp4_only_on_flagged_ranks_experts(layer):
    mod, a, p, x = layer
    t, ep = x.shape[0], 4
    per = a.n_experts // ep
    none = _moe(mod, a, p, x, np.zeros((t, ep), bool))
    picks = np.asarray(mod.routes(p, x, a=a))               # [T, top_k]
    deltas = []
    for r in range(ep):
        flags = np.zeros((t, ep), bool)
        flags[:, r] = True
        got = _moe(mod, a, p, x, flags)
        hit = ((picks // per) == r).any(1)
        assert hit.any() and not hit.all()
        # a position none of whose experts sit on rank r reads BF16 only
        np.testing.assert_array_equal(got[~hit], none[~hit])
        assert np.all(np.abs(got[hit] - none[hit]).max(1) > 0)
        deltas.append(got - none)
    every = _moe(mod, a, p, x, np.ones((t, ep), bool))
    np.testing.assert_allclose(sum(deltas), every - none, atol=1e-5)


@partial(jax.jit, static_argnums=0)
def _whole_layer_fp4(a, p, x):
    """Every routed expert in FP4, one global scale per stack: the layer
    as one chip runs it when a virtual rank is flagged."""
    from harness.reference import rms
    m = p["moe"]
    h = rms(x, p["norm2"], a.norm_eps)
    probs = jax.nn.softmax(jnp.einsum("td,de->te", h, m["router"],
                                      precision=HI), axis=-1)
    top, idx = jax.lax.top_k(probs, a.top_k)
    gates = (jax.nn.one_hot(idx, a.n_experts)
             * (top / top.sum(-1, keepdims=True))[..., None]).sum(1)
    xq = a4(h)
    g = jnp.einsum("td,edf->tef", xq, nvfp4_weights(m["w_gate"]),
                   precision=HI)
    u = jnp.einsum("td,edf->tef", xq, nvfp4_weights(m["w_up"]),
                   precision=HI)
    return jnp.einsum("tef,efd->td", a4(jax.nn.silu(g) * u)
                      * gates[..., None], nvfp4_weights(m["w_down"]),
                      precision=HI)


def test_every_rank_flagged_is_the_whole_layer(layer):
    mod, a, p, x = layer
    t = x.shape[0]
    shared = _moe(mod, a, p, x, np.zeros((t, 1), bool)) \
        - _moe(mod, a, {k: v for k, v in p.items() if k != "shared"}, x,
               np.zeros((t, 1), bool))
    whole = np.asarray(x + _whole_layer_fp4(a, p, x)) + shared
    one_chip = _moe(mod, a, p, x, np.ones((t, 1), bool))
    np.testing.assert_allclose(one_chip, whole, atol=1e-5)
    # four ranks, each stack's largest weight on every rank: four global
    # scales equal to the one scale, so every rank flagged is the layer
    q = dict(p, moe=dict(p["moe"]))
    for name in ("w_gate", "w_up", "w_down"):
        w = q["moe"][name]
        top = jnp.max(jnp.abs(w))
        q["moe"][name] = w.at[::a.n_experts // 4, 0, 0].set(top)
    np.testing.assert_array_equal(
        _moe(mod, a, q, x, np.ones((t, 4), bool)),
        _moe(mod, a, q, x, np.ones((t, 1), bool)))


def test_nvfp4_scales_each_rank_on_its_own():
    w = jax.random.normal(jax.random.PRNGKey(2), (8, 32, 16))
    w = w.at[:4].multiply(100.0)            # rank 0 of 2 holds the large
    two = nvfp4_weights(w, 2)
    np.testing.assert_array_equal(two[4:], nvfp4_weights(w[4:]))
    np.testing.assert_array_equal(two[:4], nvfp4_weights(w[:4]))
    assert not np.array_equal(nvfp4_weights(w)[4:], two[4:])


def test_rank_flags_follow_the_chips():
    ms = np.zeros((2, 2, 1, 4))
    ms[:, 0, 0] = [4000.0, 500.0, 500.0, 500.0]      # rank 0 hot
    ms[:, 1, 0] = [3900.0, 0.0, 0.0, 0.0]            # and vision-heavy
    m = np.zeros((1, 4))
    per_rank = policy.rank_flags(ms, m, 4)
    assert per_rank.shape == (2, 4)
    assert per_rank[:, 0].all() and not per_rank[:, 1:].any()
    np.testing.assert_array_equal(policy.rank_flags(ms, m, 1),
                                  per_rank.any(1, keepdims=True))
    assert [ib for _, ib in policy.layers(ms, m)] \
        == pytest.approx([4000.0 / 1375.0, 4000.0 / 1375.0])
    with pytest.raises(ValueError):
        policy.rank_flags(ms, m, 2)
