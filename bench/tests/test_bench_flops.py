"""Operation and byte counts: real rows only, FP4 as stored (6 bits)."""
import json

import pytest

import _paths  # noqa: F401
from harness import arch, flops


def _arch(name):
    """(architecture module, sizes) of a configuration file."""
    return arch.of(json.loads(
        (_paths.BENCH / "configs" / f"{name}.json").read_text()))


def test_moonlight_active_params_by_hand():
    mod, a = _arch("moonlight-5l")
    d, f, fe = 2048, 11264, 1408
    attn = 4 * d * 16 * 128
    dense = attn + 3 * d * f
    moe = attn + (6 + 2) * 3 * d * fe + d * 64
    assert mod.active_params(a) == dense + 4 * moe


def test_olmoe_active_params_by_hand():
    mod, a = _arch("olmoe-8l")
    d, fe = 2048, 1024
    assert mod.active_params(a) == 8 * (4 * d * 16 * 128 + 8 * 3 * d * fe
                                          + d * 64)


@pytest.mark.parametrize("start,take", [(0, 1), (0, 37), (100, 64)])
def test_prefill_flops_sums_token_flops(start, take):
    mod, a = _arch("moonlight-5l")
    want = sum(mod.token_flops(a, p) for p in range(start, start + take))
    assert mod.prefill_flops(a, start, take) == pytest.approx(want)


def test_fp4_bytes_are_codes_plus_f32_group_scales():
    assert flops.FP4_BYTES * 8 == 6.0
    mod, a = _arch("moonlight-5l")
    f, b = mod.fp4_ffn_work(a, rows=100, experts=3)
    assert f == 100 * 6 * 2048 * 1408
    assert b == 3 * 3 * 2048 * 1408 * 0.75 + 100 * 2048 * 4
    assert mod.quantize_work(a, 64) == 3 * 64 * 2048 * 1408 * 2.75


def test_roofline_share_takes_the_larger_bound():
    pk = flops.peaks("TPU v5 lite")
    assert flops.roofline_share(197e12, 0.0, 2.0, pk) == pytest.approx(50.0)
    assert flops.roofline_share(1.0, 819e9, 4.0, pk) == pytest.approx(25.0)


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        flops.peaks("cpu")
