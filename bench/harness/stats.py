"""End-to-end arithmetic over the harness's stamps.

Every request due in the window counts: one still waiting when the window
closes counts at its elapsed wait, so a stall cannot hide.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np


@dataclass
class Track:
    """One request as the harness saw it (absolute clock seconds)."""
    uid: int
    due: float
    max_new: int
    submit: Optional[float] = None
    admit: Optional[float] = None          # first stamp holding a slot
    tokens: List[float] = field(default_factory=list)   # stamp per token
    failed: bool = False

    @property
    def done(self) -> bool:
        return len(self.tokens) >= self.max_new


def percentile(values, q: float) -> Optional[float]:
    v = np.asarray(list(values), float)
    return float(np.percentile(v, q)) if v.size else None


def due_in(tracks: List[Track], t0: float, close: float) -> List[Track]:
    return [t for t in tracks if t0 <= t.due < close]


def waits_until(tracks: List[Track], close: float, stamp: str
                ) -> List[float]:
    """Due time to the ``stamp`` ("admit" or "first"), or to ``close``
    where it had not come by then."""
    out = []
    for t in tracks:
        at = t.admit if stamp == "admit" else (t.tokens[0] if t.tokens
                                               else None)
        out.append((at if at is not None and at <= close else close)
                   - t.due)
    return out


def inter_token_gaps(tracks: List[Track], close: float) -> List[float]:
    gaps = []
    for t in tracks:
        ts = [x for x in t.tokens if x <= close]
        gaps.extend(np.diff(ts).tolist())
    return gaps


def tokens_in(tracks: List[Track], t0: float, close: float) -> int:
    return sum(1 for t in tracks for x in t.tokens if t0 <= x <= close)
