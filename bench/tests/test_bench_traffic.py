"""The traffic generator: one seed gives one workload, other seeds the
same work (closed loop: in another order)."""
import numpy as np
import pytest

import _paths  # noqa: F401
from harness import spec, traffic

MIXES = {n: spec.load_cell(f"moonlight-5l.{n}").traffic
         for n in ("vision-burst", "text-decode")}
BIG = 2 ** 31 + 12345


def _open(seed, horizon=51.0):
    return traffic.open_loop(MIXES["vision-burst"], seed, horizon, 163840)


def _closed(seed):
    return traffic.closed_loop(MIXES["text-decode"], seed, 4, 163840)


def _sig(specs):
    return [(s.due, s.tokens.tolist(), s.modality.tolist(), s.max_new,
             s.decode_vision) for s in specs]


@pytest.mark.parametrize("seed", [0, 7, BIG, 2 ** 40 + 3])
def test_open_loop_same_seed_same_traffic(seed):
    assert _sig(_open(seed)) == _sig(_open(seed))


def test_open_loop_seeds_differ_in_order_not_in_work():
    """Seeds differ in token ids only: every request has the same due
    time, sizes and vision share, in the same order."""
    a, b = _open(1), _open(BIG)
    assert _sig(a) != _sig(b)
    assert len(a) == len(b)
    for key in (lambda s: s.due, lambda s: len(s.tokens), lambda s: s.max_new,
                lambda s: s.decode_vision, lambda s: int(s.modality.sum())):
        assert list(map(key, a)) == list(map(key, b))
    assert any(not np.array_equal(x.tokens, y.tokens) for x, y in zip(a, b))


def test_open_loop_sizes_and_modality_follow_the_mix():
    mix = MIXES["vision-burst"]
    specs = _open(3)
    lens = np.array([len(s.tokens) for s in specs])
    assert lens.min() >= mix["prompt_len"]["min"]
    assert lens.max() <= mix["prompt_len"]["max"]
    vis = np.concatenate([s.modality for s in specs])
    toks = np.concatenate([s.tokens for s in specs])
    assert np.all(toks[vis] >= 163840 // 2) and np.all(toks[~vis] < 163840 // 2)
    assert 0.6 < vis.mean() < 0.8
    outs = np.array([s.max_new for s in specs])
    assert outs.min() >= 2 and outs.max() <= 32


def test_open_loop_arrivals_follow_the_cumulative_rate():
    """Over a long draw: exponential dwells of the mix's mean lengths,
    Poisson arrivals at each phase's rate, the mix's mean rate overall."""
    mix = MIXES["vision-burst"]
    horizon = 4000.0
    times, phase = traffic.schedule(mix, horizon)
    assert np.all(np.diff(times) >= 0) and times[-1] < horizon
    assert len(times) / horizon == pytest.approx(mix["mean_rate"], rel=0.1)
    edges = traffic.phase_edges(mix, horizon)
    dwell = np.diff(edges)
    assert dwell[0::2].mean() == pytest.approx(mix["calm_s"], rel=0.15)
    assert dwell[1::2].mean() == pytest.approx(mix["burst_s"], rel=0.15)
    count = np.bincount(phase, minlength=len(dwell))
    burst_rate = count[1::2].sum() / dwell[1::2].sum()
    calm_rate = count[0::2].sum() / dwell[0::2].sum()
    assert burst_rate / calm_rate == pytest.approx(
        mix["burst_mult"] / mix["calm_mult"], rel=0.2)
    in_burst = np.diff(times)[(phase[1:] == phase[:-1]) & (phase[1:] % 2 == 1)]
    assert in_burst.std() / in_burst.mean() == pytest.approx(1.0, abs=0.15)


def _fits(ss):
    """Does schedule seed ``ss``'s 51 s draw offer each vision-burst
    cell's mean rate within 5%, with 1.5/7.5 of it in bursts within 10%?"""
    for cell in ("moonlight-5l.vision-burst", "olmoe-8l.vision-burst"):
        m = dict(spec.load_cell(cell).traffic, schedule_seed=ss)
        n = len(traffic.schedule(m, 51.0)[0])
        e = np.minimum(traffic.phase_edges(m, 51.0), 51.0)
        if abs(n / (m["mean_rate"] * 51.0) - 1) > 0.05 \
                or abs(np.diff(e)[1::2].sum() / (51.0 * 1.5 / 7.5) - 1) > 0.1:
            return False
    return True


def test_schedule_seed_offers_the_mean_rate():
    """The mix's ``schedule_seed`` is the first seed that fits."""
    ss = MIXES["vision-burst"]["schedule_seed"]
    assert _fits(ss)
    assert not any(_fits(s) for s in range(ss))


def test_open_loop_shorter_horizon_is_a_prefix():
    a, b = _open(9, horizon=20.0), _open(9)
    assert [s.due for s in a] == [s.due for s in b][:len(a)]
    assert len(a) < len(b)


@pytest.mark.parametrize("seed", [0, BIG])
def test_closed_loop_same_seed_same_traffic(seed):
    a, b = _closed(seed), _closed(seed)
    assert [_sig(c) for c in a] == [_sig(c) for c in b]


def test_closed_loop_rounds_are_one_stratified_set_per_seed():
    mix = MIXES["text-decode"]
    a, b = _closed(11), _closed(12)
    assert [_sig(c) for c in a] != [_sig(c) for c in b]
    for r in range(4):
        ra = sorted(c[r].max_new for c in a)
        rb = sorted(c[r].max_new for c in b)
        assert ra == rb
        assert min(ra) >= mix["output_len"]["min"]
        assert max(ra) <= mix["output_len"]["max"]
        pa = sorted(len(c[r].tokens) for c in a)
        assert pa == sorted(len(c[r].tokens) for c in b)
    assert not any(s.modality.any() for c in a for s in c)


def test_prompt_buckets_cover_every_take():
    mix = MIXES["vision-burst"]
    b = traffic.prompt_buckets(mix)
    assert b == [8, 16, 32, 64, 128, 256, 512]
    assert traffic.prompt_buckets(MIXES["text-decode"]) == [8, 16, 32, 64, 128]


def test_every_phase_gets_the_same_sizes_for_every_seed():
    mix = MIXES["vision-burst"]
    a, b = _open(21), _open(BIG)
    assert [s.due for s in a] == [s.due for s in b]
    phase = traffic.schedule(mix, 51.0)[1]
    for ph in np.unique(phase):
        idx = np.flatnonzero(phase == ph)
        assert sorted(len(a[i].tokens) for i in idx) \
            == sorted(len(b[i].tokens) for i in idx)
        assert sorted(a[i].max_new for i in idx) \
            == sorted(b[i].max_new for i in idx)
