"""The one traffic generator: reads a mix's parameters (``bench/traffic/
<name>.json``) and draws its requests.

Every seed gets the same work.  Token ids are drawn from the seed; vision
tokens take the upper half of the vocabulary, text tokens the lower (as
``repro.workloads.multimodal`` draws them).

Two kinds of mix:

* ``open_mmpp2`` -- open loop, the MMPP-2 of
  ``repro.workloads.arrivals._bursty``: calm phases (``calm_mult`` x the
  base rate) and bursts (``burst_mult`` x) alternate, starting calm, each
  phase an exponential dwell of mean ``calm_s`` or ``burst_s``; arrivals
  are Poisson at the phase's rate.  ``mean_rate`` is the long-run mean.
  The phases, the arrivals and each request's sizes (clipped normals, as
  ``synth_request`` draws them) are one draw from the mix's
  ``schedule_seed``, the same for every run seed and in the same order:
  the run seed draws only the token ids (and where an interleaved
  prompt's vision tokens sit).  Which requests share a chunk sets whether
  FP4 fires, so a seeded order would change the work each seed offers.
* ``closed`` -- ``clients`` callers, each sending its next request when the
  last one finishes (after ``think_s``).  Round ``r`` of all clients is one
  stratified set of sizes (quantiles of each distribution), permuted by
  the seed.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List, Tuple

import numpy as np


@dataclass
class Spec:
    uid: int
    due: float                  # seconds after the window opens (open loop)
    tokens: np.ndarray          # [S] int32
    modality: np.ndarray        # [S] bool, True = vision
    max_new: int
    decode_vision: bool
    client: int = -1            # closed loop: the caller
    round: int = 0              # closed loop: the caller's n-th request


def rng_of(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 64), stream])


def quantiles(dist: dict, n: int, rng) -> np.ndarray:
    """``n`` stratified draws of ``dist``, in a seeded order: a clipped
    normal ``{mean, std, min, max}`` or a uniform ``{min, max}``."""
    u = (np.arange(n) + 0.5) / n
    if "mean" in dist:
        nd = NormalDist(dist["mean"], dist["std"])
        v = np.array([nd.inv_cdf(x) for x in u])
    else:
        v = dist["min"] + u * (dist["max"] - dist["min"])
    v = np.clip(v, dist["min"], dist["max"])
    return rng.permutation(v)


def _share(share: float, n: int, rng) -> np.ndarray:
    """Exactly ``round(share * n)`` True entries, at seeded places."""
    flags = np.zeros(n, bool)
    flags[:int(round(share * n))] = True
    return rng.permutation(flags)


def _stratified(mix: dict, n: int, rng) -> tuple:
    """Sizes of ``n`` requests, each a stratified set in a seeded order:
    prompt lengths, vision shares, interleaved flags, output lengths,
    vision-decode flags."""
    lens = np.rint(quantiles(mix["prompt_len"], n, rng)).astype(int)
    vf = quantiles(mix["vision_frac"], n, rng) \
        if "vision_frac" in mix else np.zeros(n)
    inter = _share(mix.get("interleave_share", 0.0), n, rng)
    outs = np.rint(quantiles(mix["output_len"], n, rng)).astype(int)
    dvis = _share(mix.get("decode_vision_share", 0.0), n, rng)
    return lens, vf, inter, outs, dvis


def _drawn(mix: dict, n: int, seed: int) -> tuple:
    """Sizes of ``n`` requests drawn independently, one stream each (so
    the first ``k`` of ``n`` are those of ``k``): the same fields as
    :func:`_stratified`."""
    def dist(d, stream):
        rng = rng_of(seed, stream)
        v = rng.normal(d["mean"], d["std"], n) if "mean" in d \
            else rng.uniform(d["min"], d["max"], n)
        return np.clip(v, d["min"], d["max"])

    def flags(share, stream):
        return rng_of(seed, stream).random(n) < share
    lens = np.rint(dist(mix["prompt_len"], 6)).astype(int)
    vf = dist(mix["vision_frac"], 7) if "vision_frac" in mix \
        else np.zeros(n)
    outs = np.rint(dist(mix["output_len"], 9)).astype(int)
    return (lens, vf, flags(mix.get("interleave_share", 0.0), 8), outs,
            flags(mix.get("decode_vision_share", 0.0), 10))


def _prompts(sizes: tuple, vocab: int, rng) -> List[tuple]:
    """(tokens, modality, max_new, decode_vision) of each request, its
    token ids drawn from ``rng``."""
    lens, vf, inter, outs, dvis = sizes
    out = []
    for i in range(len(lens)):
        p = int(lens[i])
        n_vis = int(round(p * vf[i]))
        toks = rng.integers(0, vocab // 2, p).astype(np.int32)
        mod = np.zeros(p, bool)
        if n_vis:
            pos = rng.choice(p, n_vis, replace=False) if inter[i] \
                else np.arange(n_vis)
            mod[pos] = True
            toks[mod] += vocab // 2
        out.append((toks, mod, int(outs[i]), bool(dvis[i])))
    return out


def base_rate(mix: dict) -> float:
    cyc = mix["calm_s"] + mix["burst_s"]
    return mix["mean_rate"] * cyc / (mix["calm_mult"] * mix["calm_s"]
                                     + mix["burst_mult"] * mix["burst_s"])


def phase_edges(mix: dict, horizon: float) -> np.ndarray:
    """Start and end times of the phases that cover ``[0, horizon]``:
    phase ``i`` spans ``edges[i]`` to ``edges[i + 1]``, calm for even
    ``i``, a burst for odd; dwells exponential, from ``schedule_seed``."""
    rng = rng_of(mix["schedule_seed"], 4)
    edges, burst = [0.0], False
    while edges[-1] <= horizon:
        edges.append(edges[-1] + rng.exponential(
            mix["burst_s"] if burst else mix["calm_s"]))
        burst = not burst
    return np.asarray(edges)


def schedule(mix: dict, horizon: float) -> Tuple[np.ndarray, np.ndarray]:
    """Arrival times in ``[0, horizon)`` and the phase each falls in.  A
    unit-rate Poisson process (from ``schedule_seed``) mapped through the
    inverse of the phases' cumulative rate: Poisson arrivals at each
    phase's own rate, the MMPP-2 given its phases."""
    edges = phase_edges(mix, horizon)
    r = base_rate(mix)
    mult = np.where(np.arange(len(edges) - 1) % 2 == 0, mix["calm_mult"],
                    mix["burst_mult"])
    cum = np.concatenate([[0.0], np.cumsum(r * mult * np.diff(edges))])
    total = float(np.interp(horizon, edges, cum))
    n_max = int(total + 10 * math.sqrt(total) + 20)
    units = np.cumsum(rng_of(mix["schedule_seed"], 5).exponential(1.0, n_max))
    times = np.interp(units[units < total], cum, edges)
    return times, np.searchsorted(edges, times, side="right") - 1


def open_loop(mix: dict, seed: int, horizon: float, vocab: int
              ) -> List[Spec]:
    """The schedule's requests, their sizes in schedule order; the seed
    draws their token ids."""
    times, _ = schedule(mix, horizon)
    sizes = _drawn(mix, len(times), mix["schedule_seed"])
    reqs = _prompts(sizes, vocab, rng_of(seed, 2))
    return [Spec(uid=i, due=float(t), tokens=tk, modality=md, max_new=mn,
                 decode_vision=dv)
            for i, (t, (tk, md, mn, dv)) in enumerate(zip(times, reqs))]


def closed_loop(mix: dict, seed: int, rounds: int, vocab: int
                ) -> List[List[Spec]]:
    """``[client][round]`` requests."""
    c = mix["clients"]
    rng = rng_of(seed, 3)
    per_client: List[List[Spec]] = [[] for _ in range(c)]
    uid = 0
    for r in range(rounds):
        reqs = _prompts(_stratified(mix, c, rng), vocab, rng)
        for i, (tk, md, mn, dv) in enumerate(reqs):
            per_client[i].append(Spec(uid=uid, due=0.0, tokens=tk,
                                      modality=md, max_new=mn,
                                      decode_vision=dv, client=i, round=r))
            uid += 1
    return per_client


def prompt_buckets(mix: dict, lo: int = 8) -> List[int]:
    """Every chunk length bucket the engine can form from this mix: its
    power-of-two rounding of any take up to the longest prompt (a prompt
    split by the token budget can leave any shorter remainder)."""
    top = int(mix["prompt_len"]["max"])
    out, b = [lo], lo
    while b < top:
        b *= 2
        out.append(b)
    return out
