"""The readers on four chips: the kernel rooflines count the timed chip's
(EP rank 0's) experts, rows and FP4 flags, and the two four-chip readers
read every chip; on one synthetic trace of four devices."""
import json

import numpy as np
import pytest

import _paths
from harness import arch, flops, spec
from harness.context import Run
from harness.serve import StepRecord, Window
from harness.trace import Device, Op, Trace

E, D, F = 64, 2048, 1408
KERNEL = ("jit(chunk_step)/jit(main)/while/body/moe/expert_gemm/cond/"
          "pallas_call")
QUANT = "jit(chunk_step)/jit(main)/while/body/moe/quantize_fp4/pallas_call"
A2A_IN = "jit(chunk_step)/jit(main)/while/body/moe/dispatch/all_to_all"
A2A_OUT = "jit(chunk_step)/jit(main)/while/body/moe/combine/all_to_all"


def _step():
    """One chunk step of two MoE layers: rank 0 hot and vision-heavy in
    layer 0 (flagged), every rank alike in layer 1 (none flagged)."""
    ms = np.zeros((2, 2, 1, 4))
    ms[0, 0, 0] = [4000.0, 500.0, 500.0, 500.0]
    ms[0, 1, 0] = [3900.0, 0.0, 0.0, 0.0]
    ms[1, 0, 0] = [1000.0] * 4
    es = np.zeros((2, 2, E))
    es[:, 0, :10] = 1.0            # rank 0: 10 of its 16 experts used
    es[:, 0, 16:40] = 1.0          # the other ranks: 24
    return StepRecord("chunk", 0.5, [(0, 0, 64)], np.zeros((1, 4)),
                      {"moe_stats": ms, "expert_stats": es})


def _device(gemm_ms, kernel_ms=3.0, quant_ms=1.0, a2a_ms=0.5):
    ops = [Op(0.0, kernel_ms * 1e-3, "expert_gemm.1", KERNEL, kernel=True),
           Op(0.1, 0.1 + quant_ms * 1e-3, "quantize.2", QUANT, kernel=True),
           Op(0.2, 0.2 + (gemm_ms - kernel_ms) * 1e-3, "ragged-dot-none.3",
              "", kernel=True),
           Op(0.3, 0.3 + a2a_ms * 1e-3, "all-to-all.4", A2A_IN),
           Op(0.4, 0.4 + a2a_ms * 1e-3, "all-to-all.5", A2A_OUT),
           Op(0.5, 0.5 + 9e-3, "all-to-all.6",
              "jit(chunk_step)/jit(main)/attention/all_to_all")]
    dev = Device(ops=ops, modules=[("jit_chunk_step", 0.0, 1.0)])
    from harness.trace import _assign_modules
    _assign_modules(dev)
    return dev


def _run(devices, ep):
    conf = json.loads((_paths.BENCH / "configs"
                       / "moonlight-13l-ep4.json").read_text())
    mod, a = arch.of(conf)
    return Run(arch=a, counts=mod, chips=ep, peaks=flops.peaks("TPU v5 lite"),
               window=Window(0.0, 1.0, [], {}), due=[], steps=[_step()],
               iter_stats=[], policy_ranks=4, ep=ep,
               trace=Trace(devices, [("harness.traced", 0.0, 1.0)]),
               traced_steps=[_step()])


@pytest.fixture
def four():
    return _run({i: _device(g, a2a_ms=0.25 * (i + 1))
                 for i, g in enumerate([8.0, 4.0, 4.0, 4.0])}, ep=4)


def test_rank_zero_counts(four):
    assert four.fired(_step()) == [True, False]
    assert four.layer_rows(_step()) == [4000.0, 1000.0]
    assert four.layer_experts(_step()) == [10, 10]


def test_grouped_fp4_roofline_counts_one_ranks_experts_and_rows(four):
    pk = flops.peaks("TPU v5 lite")
    want_ops = 4000 * 6.0 * D * F
    want_bytes = 10 * 3.0 * D * F * 0.75 + 4000 * D * 4.0
    want = 100.0 * max(want_ops / pk["flops"], want_bytes / pk["hbm"]) / 3e-3
    got = spec.metric_reader("grouped_fp4_ffn_roofline")(four)
    assert got == pytest.approx(want)


def test_quantize_roofline_counts_one_ranks_stacks(four):
    want = 100.0 * (3.0 * 16 * D * F * 2.75 / 819e9) / 1e-3
    got = spec.metric_reader("quantize_fp4_roofline")(four)
    assert got == pytest.approx(want)
    one = _run({0: _device(8.0)}, ep=1)
    assert spec.metric_reader("quantize_fp4_roofline")(one) \
        == pytest.approx(4 * want)


def test_rank_gemm_skew_is_slowest_over_mean(four):
    # expert GEMM per chunk step 8, 4, 4, 4 ms: the slowest is 1.6x the mean
    assert spec.metric_reader("moe.rank_gemm_skew")(four) \
        == pytest.approx(160.0)
    assert spec.metric_reader("moe.rank_gemm_skew")(
        _run({0: _device(8.0)}, ep=1)) is None


def test_all_to_all_reads_dispatch_and_combine_on_every_chip(four):
    # two all-to-alls of 0.25 (i + 1) ms on chip i; attention's not counted
    want = np.mean([2 * 0.25 * (i + 1) for i in range(4)])
    assert spec.metric_reader("moe.all_to_all_ms")(four) \
        == pytest.approx(want)
    assert spec.metric_reader("moe.all_to_all_ms")(
        _run({0: _device(8.0)}, ep=1)) is None
