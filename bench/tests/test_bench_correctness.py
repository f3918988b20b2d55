"""``correct`` at reduced widths on the CPU: the program passes, and the
fp8 control and each fault a serving cell can have fail, with the rest of
a run (window, recorder, sample, reference) as on the chip.

The tiny cell's limit on the mean logit gap (0.001) sits between what the
program read here (0 to 4.1e-5 over four seeds) and what the fp8 control
read (0.0035 to 0.0093)."""
import jax.numpy as jnp
import numpy as np
import pytest

import _paths  # noqa: F401
import _tiny

LIMIT = 1e-3


def test_program_is_correct(tmp_path):
    res = _tiny.run(tmp_path, LIMIT)
    assert res["correct"], res["checked"]
    assert res["checked"]["served_tokens_checked"]["value"] > 10
    assert res["attempted"] >= 8 and res["failed"] == 0
    assert set(res["metrics"]) == {"ttft_p90_s", "itl_p90_ms",
                                   "output_tok_per_s", "setup_s"}


def test_fp8_control_is_not_correct(tmp_path):
    res = _tiny.run(tmp_path, LIMIT, seed=3, control=True)
    got = res["checked"]
    assert res["correct"] and not res["control_correct"], got
    assert got["mean_logit_gap"]["value"] <= LIMIT
    assert got["mean_logit_gap_fp8_control"]["value"] > LIMIT
    assert got["mean_logit_gap_fp8_control"]["value"] \
        >= 3 * max(got["mean_logit_gap"]["value"], 1e-4)


def _alter_tokens(eng):
    sample = eng._sample

    def altered(logits):
        toks = sample(logits)
        return (toks + 1) % eng.cfg.vocab_size
    eng._sample = altered


def _decode_keeps_state(eng):
    decode = eng._decode

    def unchanged(params, cache, *rest):
        logits, _, m_state, aux = decode(params, cache, *rest)
        return logits, cache, m_state, aux
    eng._decode = unchanged


def _fp4_everywhere(eng):
    from repro.configs.base import ReaLBConfig
    eng.rcfg = ReaLBConfig(gate_gamma=-1, capacity_c=-1.0, md_init=-1.0,
                           adaptive=False)
    eng.m_state = jnp.full(eng.m_state.shape, -1.0, jnp.float32)
    eng._build()


@pytest.mark.parametrize("fault", [_alter_tokens, _decode_keeps_state,
                                   _fp4_everywhere],
                         ids=["token_altered", "decode_state_unchanged",
                              "fp4_beyond_policy"])
def test_fault_is_not_correct(tmp_path, fault):
    # FP4 flips about one served token in ten at these widths: twelve
    # requests (some 80 tokens) leave none unflipped but by rare chance
    res = _tiny.run(tmp_path, LIMIT, engine_hook=fault,
                    mix_over={"check_sample": 12})
    assert not res["correct"], res["checked"]
    assert res["checked"]["mean_logit_gap"]["value"] > LIMIT


def test_no_finished_request_is_not_correct(tmp_path):
    res = _tiny.run(tmp_path, LIMIT, seconds=0.05,
                    mix_over={"output_len": {"min": 60, "max": 60},
                              "prompt_len": {"min": 8, "max": 8}})
    assert res["checked"]["served_tokens_checked"]["value"] == 0
    assert not res["correct"]
