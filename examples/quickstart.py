"""Quickstart: the whole system in one minute on CPU.

    PYTHONPATH=src python examples/quickstart.py

1. builds a tiny Kimi-VL-backbone-family MMoE (moonshot-v1-16b-a3b reduced),
2. runs a training step (loss + MoE aux losses),
3. prefills a multimodal prompt and decodes a few tokens with ReaLB live,
4. shows the ReaLB policy making a precision decision on a skewed load.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ReaLBConfig, get_config, reduced
from repro.core import init_m_state
from repro.core.policy import realb_policy
from repro.models import transformer as tf

# 1) model ------------------------------------------------------------------
cfg = reduced(get_config("moonshot-v1-16b-a3b"))
rcfg = ReaLBConfig(gate_gamma=16)       # tiny gate so the demo activates
params = tf.init_model(cfg, jax.random.PRNGKey(0))
n_params = sum(p.size for p in jax.tree.leaves(params))
print(f"model: {cfg.name} (reduced) — {n_params/1e6:.2f}M params, "
      f"{cfg.moe.num_experts} experts top-{cfg.moe.top_k}")

# 2) one training step --------------------------------------------------------
B, S = 4, 32
rng = np.random.default_rng(0)
tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)
modality = jnp.asarray(rng.random((B, S)) < 0.6)
batch = {"tokens": tokens, "labels": tokens, "modality": modality}
m = init_m_state(1, 1, rcfg)
loss, (m, metrics) = tf.train_loss(params, cfg, rcfg, batch, m)
print(f"train: loss={float(loss):.3f} lb_loss={float(metrics['lb_loss']):.3f}")

# 3) prefill + decode ---------------------------------------------------------
res = tf.prefill_forward(params, cfg, rcfg, batch, m, cache_len=S + 8)
cache, m = res.cache, res.m_state
tok = jnp.argmax(res.logits, -1)[:, None].astype(jnp.int32)
pos = jnp.full((B,), S, jnp.int32)
text = [int(t) for t in tok[:, 0]]
for step in range(4):
    out = tf.decode_forward(params, cfg, rcfg,
                            {"tokens": tok, "pos": pos}, cache, m)
    cache, m = out.cache, out.m_state
    tok = jnp.argmax(out.logits, -1)[:, None].astype(jnp.int32)
    pos = pos + 1
    text.append(int(tok[0, 0]))
print(f"serve: greedy continuation of sequence 0 -> {text}")

# 4) the ReaLB decision, directly --------------------------------------------
load = jnp.asarray([900.0, 300.0, 350.0, 250.0])   # rank 0 is a straggler
vis = jnp.asarray([850.0, 60.0, 180.0, 50.0])      # ...and vision-heavy
m_d = jnp.full((4,), 0.9)
dec = realb_policy(load, vis, m_d, ReaLBConfig(gate_gamma=1000))
print(f"policy: IB_d={np.round(np.asarray(dec.ib_d),2)} "
      f"hotspots={np.asarray(dec.hotspots)} "
      f"-> FP4 ranks={np.asarray(dec.use_fp4)} "
      f"(M_d -> {np.round(np.asarray(dec.m_new), 2)})")
print("rank 0 exceeds IB>C with R_v>M_d ⇒ executes its experts in FP4; "
      "its weights are quantized inside its expert compute.")
