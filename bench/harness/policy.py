"""Which MoE layers of a step ran in FP4, recomputed from the step's
routing counters and the AIMD state it started from.

A float32 numpy copy of the ReaLB policy (``repro.core.policy``, paper
section 4.2) under the engine's default ``ReaLBConfig``: a rank is
compressed when it is a hotspot, its vision share exceeds its threshold
and the LB gate is open; the threshold falls by half while the global
imbalance exceeds tau and rises by ``md_add`` otherwise, updated once per
MoE layer.  On one chip the policy runs over a virtual EP group and any
flagged virtual rank puts the whole layer in FP4.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Tuple

import numpy as np

F = np.float32


@dataclass(frozen=True)
class Policy:
    capacity_c: float = 1.0
    tau: float = 1.5
    md_add: float = 0.1
    md_mult: float = 0.5
    md_min: float = 0.0
    gate_gamma: float = 2048.0


def step(load_d, vis_d, m, p: Policy = Policy()):
    """One control step: (use_fp4 [R], gate_open, ib_global, m_new)."""
    load_d, vis_d, m = (np.asarray(x, F) for x in (load_d, vis_d, m))
    total = load_d.sum(dtype=F)
    ideal = F(total / F(load_d.shape[0]))
    ib = load_d / np.maximum(ideal, F(1.0))
    ib_global = ib.max()
    gate = bool(total > F(p.gate_gamma))
    r_v = vis_d / np.maximum(load_d, F(1.0))
    use = (ib > F(p.capacity_c)) & (r_v > m) & gate
    m_new = np.where(ib_global > F(p.tau),
                     np.maximum(F(p.md_min), m * F(p.md_mult)),
                     np.minimum(F(1.0), m + F(p.md_add))).astype(F)
    return use, gate, float(ib_global), (m_new if gate else m)


def layer_flags(moe_stats, m_in, p: Policy = Policy()
                ) -> Tuple[List[bool], List[int], List[float]]:
    """Per MoE layer of one step: FP4 on the whole layer (one chip: any
    flagged virtual rank), flagged rank count, and ``ib_global``.
    ``moe_stats`` is ``aux["moe_stats"]`` ``[layers, 2, groups, ep]``."""
    ms = np.asarray(moe_stats, F)
    m = np.asarray(m_in, F).reshape(-1)
    fired, ranks, ibs = [], [], []
    for layer in range(ms.shape[0]):
        use, _, ib, m = step(ms[layer, 0].reshape(-1),
                             ms[layer, 1].reshape(-1), m, p)
        fired.append(bool(use.any()))
        ranks.append(int(use.sum()))
        ibs.append(ib)
    return fired, ranks, ibs
