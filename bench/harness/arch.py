"""The sizes of a configuration file, read once, for the benchmark's own
weight draw, reference and operation counts (no program import here)."""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Arch:
    d_model: int
    n_layers: int
    n_dense: int               # leading dense-FFN layers
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int                  # dense FFN width (0 when no dense layer)
    n_experts: int
    top_k: int
    d_expert: int
    n_shared: int
    vocab: int
    rope_theta: float
    norm_eps: float
    capacity_factor: float
    param_dtype: str
    zipf_a: float
    embed_rms: float
    id_noise_share: float

    @property
    def n_moe(self) -> int:
        return self.n_layers - self.n_dense


def arch_of(conf: dict) -> Arch:
    """Read a configuration file's published keys (HF names) plus its
    ``assumed`` block."""
    a = conf["assumed"]
    d = int(conf["hidden_size"])
    heads = int(conf["num_attention_heads"])
    n_dense = int(conf.get("first_k_dense_replace", 0))
    n_experts = int(conf.get("n_routed_experts", conf.get("num_experts", 0)))
    router = a["structured_router"]
    return Arch(
        d_model=d, n_layers=int(conf["num_hidden_layers"]), n_dense=n_dense,
        n_heads=heads,
        n_kv_heads=int(conf.get("num_key_value_heads", heads)),
        head_dim=int(a.get("head_dim", d // heads)),
        d_ff=int(conf["intermediate_size"]) if n_dense else 0,
        n_experts=n_experts, top_k=int(conf["num_experts_per_tok"]),
        d_expert=int(conf.get("moe_intermediate_size",
                              conf["intermediate_size"])),
        n_shared=int(conf.get("n_shared_experts", 0)),
        vocab=int(conf["vocab_size"]),
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]),
        capacity_factor=float(a["capacity_factor"]),
        param_dtype=a["param_dtype"],
        zipf_a=float(router["zipf_a"]), embed_rms=float(router["embed_rms"]),
        id_noise_share=float(router["id_noise_share"]))
