"""Substrate tests: optimizer, checkpointing, data pipeline, grad utils."""
import pathlib
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import ckpt
from repro.configs import TrainConfig
from repro.data.pipeline import DataConfig, DataLoader, lm_batch, \
    multimodal_batch
from repro.optim import adamw
from repro.optim.grad_utils import accumulate_grads, init_error_feedback


# -- AdamW ------------------------------------------------------------------
def _numpy_adamw(p, g, m, v, step, cfg: TrainConfig):
    lr = float(adamw.lr_schedule(jnp.asarray(step), cfg))
    m2 = cfg.b1 * m + (1 - cfg.b1) * g
    v2 = cfg.b2 * v + (1 - cfg.b2) * g * g
    mh = m2 / (1 - cfg.b1 ** step)
    vh = v2 / (1 - cfg.b2 ** step)
    delta = mh / (np.sqrt(vh) + cfg.eps)
    if p.ndim >= 2:
        delta = delta + cfg.weight_decay * p
    return p - lr * delta, m2, v2


def test_adamw_matches_numpy_reference():
    cfg = TrainConfig(lr=1e-2, grad_clip=1e9, warmup_steps=1, total_steps=10)
    rng = np.random.default_rng(0)
    p = {"w": jnp.asarray(rng.normal(0, 1, (4, 4)), jnp.float32),
         "b": jnp.asarray(rng.normal(0, 1, (4,)), jnp.float32)}
    state = adamw.init_opt_state(p, cfg)
    np_p = {k: np.asarray(v) for k, v in p.items()}
    np_m = {k: np.zeros_like(v) for k, v in np_p.items()}
    np_v = {k: np.zeros_like(v) for k, v in np_p.items()}
    for step in range(1, 4):
        g = {k: np.asarray(rng.normal(0, 0.1, v.shape), np.float32)
             for k, v in np_p.items()}
        p, state, _ = adamw.adamw_update(p, {k: jnp.asarray(v)
                                             for k, v in g.items()},
                                         state, cfg)
        for k in np_p:
            np_p[k], np_m[k], np_v[k] = _numpy_adamw(
                np_p[k], g[k], np_m[k], np_v[k], step, cfg)
    for k in np_p:
        np.testing.assert_allclose(np.asarray(p[k]), np_p[k], rtol=1e-5,
                                   atol=1e-6)


def test_grad_clip():
    g = {"w": jnp.full((10,), 10.0)}
    clipped, norm = adamw.clip_by_global_norm(g, 1.0)
    assert abs(float(norm) - 10.0 * np.sqrt(10)) < 1e-3
    assert abs(float(adamw.global_norm(clipped)) - 1.0) < 1e-5


def test_lr_schedule_shape():
    cfg = TrainConfig(lr=1.0, warmup_steps=10, total_steps=100)
    lrs = [float(adamw.lr_schedule(jnp.asarray(s), cfg))
           for s in range(0, 101, 5)]
    assert lrs[0] == 0.0
    assert max(lrs) <= 1.0
    assert lrs[-1] < lrs[4]          # decayed below warmup peak


# -- checkpointing ------------------------------------------------------------
def test_ckpt_roundtrip_and_gc():
    tree = {"a": jnp.arange(6).reshape(2, 3),
            "nest": {"b": jnp.ones((4,), jnp.bfloat16)}}
    with tempfile.TemporaryDirectory() as d:
        for s in (1, 2, 3, 4):
            ckpt.save(d, s, {"state": tree}, keep=2)
        kept = sorted(p.name for p in pathlib.Path(d).iterdir())
        assert kept == ["step_00000003", "step_00000004"]
        assert ckpt.latest_step(d) == 4
        step, out = ckpt.restore(d, {"state": tree})
        assert step == 4
        np.testing.assert_array_equal(np.asarray(out["state"]["a"]),
                                      np.asarray(tree["a"]))
        assert out["state"]["nest"]["b"].dtype == jnp.bfloat16


def test_async_checkpointer():
    tree = {"a": jnp.arange(10)}
    with tempfile.TemporaryDirectory() as d:
        ac = ckpt.AsyncCheckpointer(d, keep=2)
        ac.save(5, {"state": tree})
        ac.wait()
        assert ckpt.latest_step(d) == 5


# -- data pipeline -------------------------------------------------------------
def test_lm_batch_deterministic_and_learnable():
    dc = DataConfig(vocab_size=128, seq_len=32, global_batch=8)
    b1, b2 = lm_batch(dc, 5), lm_batch(dc, 5)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    # labels are next tokens
    np.testing.assert_array_equal(b1["labels"][:, :-1], b1["tokens"][:, 1:])
    assert (b1["labels"][:, -1] == -1).all()


def test_multimodal_batch_properties():
    dc = DataConfig(vocab_size=128, seq_len=64, global_batch=32,
                    vision_frac_mean=0.6)
    b = multimodal_batch(dc, 0, d_model=16)
    mod = b["modality"]
    assert 0.3 < mod.mean() < 0.9
    # vision tokens in the top vocab half; labels masked at vision positions
    assert (b["tokens"][mod] >= 64).all()
    assert (b["labels"][mod] == -1).all()
    assert b["vision_embeds"].shape[0] == 32


def test_loader_resume():
    dc = DataConfig(vocab_size=64, seq_len=16, global_batch=4)
    a = DataLoader(dc)
    for _ in range(3):
        next(a)
    b = DataLoader(dc, start_step=3)
    np.testing.assert_array_equal(next(a)["tokens"], next(b)["tokens"])


# -- grad utils ---------------------------------------------------------------
def test_accumulate_grads_matches_full_batch():
    def loss_fn(p, batch):
        return ((p["w"] * batch["x"]) ** 2).mean(), {}

    p = {"w": jnp.asarray(2.0)}
    xs = jnp.arange(8.0)
    full, gfull = jax.value_and_grad(
        lambda p: ((p["w"] * xs) ** 2).mean())(p)
    micro = {"x": xs.reshape(4, 2)}
    loss, g, _ = accumulate_grads(loss_fn, p, micro, 4)
    np.testing.assert_allclose(float(loss), float(full), rtol=1e-6)
    np.testing.assert_allclose(float(g["w"]), float(gfull["w"]), rtol=1e-6)


def test_error_feedback_zero_init():
    ef = init_error_feedback({"w": jnp.ones((3, 3))})
    assert float(jnp.abs(ef["w"]).sum()) == 0.0


def test_compressed_all_reduce_contract():
    """Host-level shard_map wrapper: on a 1-rank mesh the reduction is the
    int8 quantize/dequantize of the input, and reduced + residual
    reconstructs the gradient exactly (error-feedback invariant)."""
    from repro.launch.mesh import make_mesh
    from repro.optim.grad_utils import compressed_all_reduce

    mesh = make_mesh((1,), ("data",), jax.devices()[:1])
    rng = np.random.default_rng(0)
    grads = {"w": jnp.asarray(rng.normal(0, 0.1, (1, 4, 8)), jnp.float32),
             "b": jnp.asarray(rng.normal(0, 1.0, (1, 8)), jnp.float32)}
    err = jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), grads)
    red, new_err = compressed_all_reduce(grads, err, mesh, "data")
    for k in grads:
        assert red[k].shape == grads[k].shape
        # g_hat + residual == g (bitwise, per the error-feedback algebra)
        np.testing.assert_allclose(np.asarray(red[k] + new_err[k]),
                                   np.asarray(grads[k]), atol=1e-7)
        # int8 quantization error bounded by scale = amax/127
        amax = float(jnp.abs(grads[k]).max())
        assert float(jnp.abs(red[k] - grads[k]).max()) <= amax / 127.0 + 1e-9


@pytest.mark.parametrize("env_dir", [None, "given"])
def test_compile_cache_dir(monkeypatch, tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR, when set, is JAX's own and is left
    alone; otherwise the cache sits at the fixed repo-root .jax_cache/."""
    from repro.launch import compile_cache
    prev = jax.config.jax_compilation_cache_dir
    sentinel = str(tmp_path / "untouched")
    jax.config.update("jax_compilation_cache_dir", sentinel)
    try:
        if env_dir is None:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            got = compile_cache.enable_compile_cache()
            want = pathlib.Path(__file__).resolve().parents[1] / ".jax_cache"
            assert got == str(want)
            assert jax.config.jax_compilation_cache_dir == str(want)
        else:
            given = str(tmp_path / env_dir)
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", given)
            assert compile_cache.enable_compile_cache() == given
            assert jax.config.jax_compilation_cache_dir == sentinel
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
