"""The structured router draw at reduced widths: the bf16 program and the
f32 reference route nearly every token to the same experts, and a
vision-heavy batch fires FP4 under the default ReaLBConfig."""
import time

import numpy as np
import pytest

import _paths  # noqa: F401
import _tiny
from harness import policy, reference, traffic, weights
from harness.arch import arch_of
from harness.model import check_layout, make_engine, model_config
from harness.serve import Recorder

SEED = 2 ** 32 + 77


@pytest.fixture(scope="module")
def served():
    """One chunk step (8 vision-heavy prompts) and one decode step."""
    conf, mix = _tiny.config(), _tiny.mix()
    a = arch_of(conf)
    cfg = model_config("tiny", a)
    params = weights.draw(a, SEED)
    check_layout(cfg, params)
    eng = make_engine(cfg, params, mix["engine"], time.perf_counter)
    rec = Recorder(eng)
    specs = [c[0] for c in traffic.closed_loop(mix, SEED, 1, a.vocab)]
    from repro.serving.scheduler import Request
    for s in specs:
        eng.submit(Request(uid=s.uid, tokens=s.tokens, modality=s.modality,
                           max_new_tokens=s.max_new, arrival_time=0.0))
    eng.step()
    rec.to_host()
    return a, params, specs, eng, rec


def test_program_and_reference_route_alike(served):
    a, params, specs, _, rec = served
    chunk = rec.steps[0]
    assert chunk.kind == "chunk" and len(chunk.rows) == len(specs)
    want = np.zeros((a.n_moe, a.n_experts))
    for s in specs:
        picks = []
        reference.forward(params, a, s.tokens,
                          np.zeros((len(s.tokens), a.n_moe), bool),
                          np.array([len(s.tokens) - 1]), 64, picks=picks)
        for layer, idx in enumerate(picks):
            want[layer] += np.bincount(idx.reshape(-1), minlength=a.n_experts)
    got = chunk.aux["expert_stats"][:, 0, :]
    moved = 0.5 * np.abs(got - want).sum() / want.sum()
    assert want.sum() == a.n_moe * a.top_k * sum(len(s.tokens) for s in specs)
    assert moved < 0.01, moved


def test_vision_burst_fires_fp4_under_default_policy(served):
    a, _, _, eng, rec = served
    first = eng.stats[0]
    assert first.phase == "prefill" and first.gate_open > 0
    assert first.fp4_ranks > 0
    fired, ranks, ibs = policy.layer_flags(rec.steps[0].aux["moe_stats"],
                                           rec.steps[0].m_in)
    assert any(fired) and max(ibs) > policy.Policy().tau
    assert sum(ranks) / a.n_moe == pytest.approx(first.fp4_ranks)
    assert sum(ranks) == pytest.approx(float(rec.steps[0].aux["fp4_ranks"]))
    decode = [s for s in eng.stats if s.phase == "decode"]
    assert decode and all(s.fp4_ranks == 0 for s in decode)


def test_zipf_order_puts_hot_vision_experts_on_rank_zero():
    a = arch_of(_tiny.config())
    import jax
    member = np.asarray(weights.expert_sets(a, jax.random.PRNGKey(3)))
    assert np.all(member.sum(1) == a.top_k)
    half = a.vocab // 2
    per_rank = a.n_experts // 4
    vis = member[half:].reshape(-1, 4, per_rank).sum(-1).mean(0)
    txt = member[:half].reshape(-1, 4, per_rank).sum(-1).mean(0)
    assert vis[0] > 2 * vis[3] and vis[0] == vis.max()
    assert np.allclose(txt, a.top_k / 4, rtol=0.05)
