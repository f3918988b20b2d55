"""The configuration file as the program runs it: its ``ModelConfig``, its
``ReaLBConfig`` and the engine the window drives."""
from __future__ import annotations

import jax

from harness.arch import Arch


def model_config(name: str, a: Arch):
    from repro.configs.base import ModelConfig, MoEConfig
    return ModelConfig(
        name=name, family="moe", n_layers=a.n_layers, d_model=a.d_model,
        n_heads=a.n_heads, n_kv_heads=a.n_kv_heads, head_dim=a.head_dim,
        d_ff=a.d_ff, vocab_size=a.vocab,
        moe=MoEConfig(num_experts=a.n_experts, top_k=a.top_k,
                      d_ff=a.d_expert, n_shared_experts=a.n_shared,
                      capacity_factor=a.capacity_factor),
        n_dense_layers=a.n_dense, layer_pattern="attn", activation="swiglu",
        rope_theta=a.rope_theta, norm_eps=a.norm_eps,
        param_dtype=a.param_dtype)


def check_layout(cfg, params) -> None:
    """The drawn tree has the program's parameter layout, leaf for leaf."""
    from repro.models import transformer as tf
    want = jax.tree.map(lambda s: (tuple(s.shape), str(s.dtype)),
                        tf.abstract_model(cfg))
    got = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), params)
    if want != got:
        raise ValueError(f"drawn parameters do not match the program's "
                         f"layout:\n want {want}\n got {got}")


def make_engine(cfg, params, eng: dict, clock):
    from repro.configs.base import ReaLBConfig
    from repro.serving.engine import Engine
    return Engine(cfg, params, ReaLBConfig(), max_slots=eng["max_slots"],
                  max_len=eng["max_len"],
                  prefill_budget=eng["prefill_budget"],
                  virtual_ep=eng["virtual_ep"], clock=clock)
