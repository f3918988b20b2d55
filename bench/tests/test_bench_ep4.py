"""A four-chip cell on four virtual CPU devices (``_ep4_worker.py``, in a
process of its own): the sharded draw is the one-device draw, the expert
stacks lie 4 experts a device, the program at EP=4 is correct with no
drop, and its fp8 control, a served token altered, the all-to-all
exchange left out and a decode that keeps its cache are not."""
import json
import subprocess
import sys

import pytest

import _paths

WORKER = _paths.BENCH / "tests" / "_ep4_worker.py"


@pytest.fixture(scope="module")
def ep4():
    r = subprocess.run([sys.executable, str(WORKER)], capture_output=True,
                       text=True, timeout=900, cwd=str(WORKER.parent))
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_sharded_draw_is_the_one_device_draw(ep4):
    assert ep4["draw_equal"] and ep4["leaves"] > 20


def test_expert_stacks_spread_over_the_chips(ep4):
    assert ep4["w_gate_shards"] == [[d, 4] for d in range(4)]


def test_program_at_ep4_is_correct_without_drops(ep4):
    got = ep4["program"]
    assert got["correct"], got
    assert got["drop_frac_max"] == 0.0 and got["failed"] == 0
    assert got["served_tokens_checked"] > 40


def test_fp8_control_at_ep4_is_not_correct(ep4):
    got = ep4["program"]
    assert got["control_correct"] is False
    assert got["mean_logit_gap_fp8_control"] \
        >= 3 * got["mean_logit_gap"], got


@pytest.mark.parametrize("fault", ["token_altered", "no_exchange",
                                   "decode_state_unchanged"])
def test_fault_at_ep4_is_not_correct(ep4, fault):
    assert ep4[fault]["correct"] is False, ep4[fault]
