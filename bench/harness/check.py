"""How ``correct`` is decided: after the window, a sample of the finished
requests (drawn from the seed, the longest always in it) is run through
the plain reference over its prompt and served tokens, applying FP4 at
exactly the positions and layers where the program's policy ran it; the
mean over served tokens of the gap by which a served token's logit lies
below the reference's best must stay under the cell's limit
(``bench/cells/<cell>.json``).  The widest gap is printed beside it: it is
set by single FP4 activation-rounding flips (the program's a4 sees bf16
inputs, the reference's f32) and swings from seed to seed as much as the
fp8 control's does."""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from harness import policy
from harness.serve import StepRecord
from harness.traffic import rng_of


def verdict(mean_gap: float, failed: int, limit: float) -> bool:
    """``correct``: the mean logit gap within the cell's limit, and no
    request refused or lost.  The fp8 control is judged by the same."""
    return bool(mean_gap <= limit and failed == 0)


def sample(requests: Dict[int, object], n: int, seed: int) -> List[object]:
    """``n`` finished requests, the one with the most served tokens first
    (longest prompt on a tie), the rest drawn from the seed."""
    done = sorted((r for r in requests.values() if r.done),
                  key=lambda r: r.uid)
    if not done:
        return []
    first = max(done, key=lambda r: (len(r.generated), r.prompt_len))
    rest = [r for r in done if r is not first]
    pick = rng_of(seed, 9).permutation(len(rest))[:max(n - 1, 0)]
    return [first] + [rest[i] for i in sorted(pick)]


def fp4_positions(steps: List[StepRecord], n_moe: int
                  ) -> Dict[int, Dict[int, np.ndarray]]:
    """uid -> position -> per-layer FP4 flags of the step that computed
    that position."""
    out: Dict[int, Dict[int, np.ndarray]] = {}
    for s in steps:
        flags = np.asarray(policy.layer_flags(s.aux["moe_stats"], s.m_in)[0],
                           bool)
        assert flags.shape == (n_moe,), flags.shape
        if s.kind == "chunk":
            for uid, start, take in s.rows:
                pos = out.setdefault(uid, {})
                for p in range(start, start + take):
                    pos[p] = flags
        else:
            for uid, p in s.rows:
                out.setdefault(uid, {})[p] = flags
    return out


def sequences(reqs: List[object], steps: List[StepRecord], n_moe: int
              ) -> List[dict]:
    """Reference inputs: prompt, served tokens, per-position FP4 flags.
    Raises when a position was not computed by exactly the steps seen."""
    where = fp4_positions(steps, n_moe)
    out = []
    for r in reqs:
        served = np.asarray(r.generated, np.int32)
        t = r.prompt_len + len(served) - 1
        pos = where.get(r.uid, {})
        missing = [p for p in range(t) if p not in pos]
        if missing:
            raise ValueError(f"request {r.uid}: positions {missing[:5]} "
                             "were computed by no recorded step")
        out.append({"uid": r.uid, "prompt": np.asarray(r.tokens, np.int32),
                    "served": served,
                    "fp4": np.stack([pos[p] for p in range(t)])})
    return out
