"""The trace reduction: busy time is the union of op intervals, idle gaps
go to the innermost labelled host span, phases come from name paths."""
import pytest

import _paths  # noqa: F401
from harness import trace as tr
from harness.trace import Device, Op, Trace

CHUNK = "jit(chunk_step)/jit(main)/while/body/moe/expert_gemm/cond/pallas_call"


def _device():
    ops = [Op(1.0, 2.0, "fusion.1", "jit(chunk_step)/attention/dot"),
           Op(2.0, 2.5, "expert_gemm.3", CHUNK, kernel=True),
           Op(2.5, 3.0, "fusion.7",
              "jit(chunk_step)/jit(main)/moe/expert_gemm/ragged_dot"),
           Op(5.0, 5.5, "fusion.9", "jit(decode)/moe/expert_gemm/dot"),
           Op(5.50005, 5.6, "fusion.10", "jit(decode)/ffn/dot")]
    dev = Device(ops=ops, modules=[("jit_chunk_step", 0.9, 3.1),
                                   ("jit_decode", 4.9, 5.7)])
    tr._assign_modules(dev)
    return dev


def test_union_and_busy():
    assert tr.union([(3, 4), (1, 2), (1.5, 2.5)]) == [(1, 2.5), (3, 4)]
    assert tr.busy_seconds(_device()) == pytest.approx(2.0 + 0.5 + 0.09995)


def test_modules_executions_and_phases():
    dev = _device()
    assert [o.module for o in dev.ops] == ["jit_chunk_step"] * 3 \
        + ["jit_decode"] * 2
    assert tr.executions(dev, "jit_chunk_step") == pytest.approx([2.2])
    assert tr.op_seconds(dev, "jit_chunk_step", "expert_gemm") \
        == pytest.approx(1.0)
    assert tr.op_seconds(dev, "jit_chunk_step", "expert_gemm",
                         custom_call=True) == pytest.approx(0.5)
    assert tr.op_seconds(dev, "jit_chunk_step", "quantize_fp4") == 0.0
    assert tr.scope_of(CHUNK) == "moe/expert_gemm"


def test_idle_gaps_by_host_span():
    dev = _device()
    host = [("harness.traced", 0.0, 6.0),
            ("harness.engine_step", 0.5, 6.0),
            ("host.sample_sync", 3.0, 4.8)]
    gaps = dict(tr.idle_gaps(dev, host, 0.0, 6.0))
    assert gaps["harness.engine_step"] == pytest.approx(1.0 + 0.4)
    assert gaps["host.sample_sync"] == pytest.approx(2.0)
    assert gaps["device.between_ops"] == pytest.approx(5e-5)
    t = Trace({0: dev}, host)
    assert t.window == (0.0, 6.0)


def test_loops_and_conds_count_once():
    ops = [Op(1.0, 4.0, "while.1", "jit(decode)/while"),
           Op(1.0, 2.0, "fusion.2", "jit(decode)/while/body/moe/expert_gemm/dot"),
           Op(2.5, 4.0, "cond.3", "jit(decode)/while/body/moe/expert_gemm/cond"),
           Op(2.5, 3.5, "fusion.4", "jit(decode)/while/body/moe/expert_gemm/cond/dot")]
    dev = Device(ops=ops, modules=[("jit_decode", 0.5, 4.5)])
    tr._assign_modules(dev)
    assert [o.container for o in dev.ops] == [True, False, True, False]
    assert tr.busy_seconds(dev) == pytest.approx(3.0)
    assert tr.op_seconds(dev, "jit_decode", "expert_gemm") == pytest.approx(2.0)
    assert sum(v for _, v in tr.top_ops(dev)) == pytest.approx(2.0)


def test_top_ops_names_program_phase_and_kernels():
    top = dict(tr.top_ops(_device()))
    assert top["jit_chunk_step:moe/expert_gemm:kernel"] == pytest.approx(0.5)
    assert top["jit_chunk_step:attention"] == pytest.approx(1.0)
    assert top["jit_decode:ffn"] == pytest.approx(0.09995)
    assert "jit_tiny_step:other/reduce_sum" in dict(tr.top_ops(
        tr.load(FIXTURE, launch=("host.sample_sync",)).devices[0]))


def test_expert_gemm_counts_the_unscoped_bf16_kernel():
    """XLA's ragged-dot kernel loses its name path on the TPU: the reader
    finds it by name, so the BF16 and FP4 expert GEMMs are counted alike."""
    from types import SimpleNamespace

    from harness import spec
    ops = [Op(1.0, 1.5, "fusion.1", "jit(chunk_step)/moe/expert_gemm/dot"),
           Op(1.5, 2.0, "ragged-dot-none.4", "", kernel=True),
           Op(2.0, 2.25, "fusion.2", "jit(chunk_step)/moe/combine/add"),
           Op(4.0, 4.5, "ragged-dot-none.4", "", kernel=True)]
    dev = Device(ops=ops, modules=[("jit_chunk_step", 0.9, 2.3),
                                   ("jit_chunk_step", 3.9, 4.6)])
    tr._assign_modules(dev)
    assert tr.op_seconds(dev, "jit_chunk_step", "expert_gemm") \
        == pytest.approx(0.5)
    assert tr.op_seconds(dev, "jit_chunk_step", "expert_gemm",
                         names=("ragged-dot",)) == pytest.approx(1.5)
    assert dict(tr.top_ops(dev))["jit_chunk_step:other/ragged-dot-none:kernel"] \
        == pytest.approx(1.0)
    read = spec.metric_reader("moe.expert_gemm_ms")
    assert read(SimpleNamespace(device=lambda: dev)) == pytest.approx(750.0)


FIXTURE = str(_paths.BENCH / "tests" / "data" / "tiny_tpu.xplane.pb")


def test_recorded_tpu_trace_gives_known_intervals():
    """A trace recorded on one TPU v5 lite: three executions of a jitted
    ``tiny_step`` (a matmul and a Pallas kernel under ``moe/expert_gemm``,
    then a reduction), each inside a ``host.sample_sync`` span, all inside
    ``harness.traced``."""
    t = tr.load(FIXTURE, launch=("host.sample_sync",))
    assert sorted(t.devices) == [0]
    dev = t.devices[0]
    assert tr.executions(dev, "jit_tiny_step") == pytest.approx(
        [767578e-12, 768828e-12, 766406e-12])
    assert len(dev.ops) == 15 and sum(o.kernel for o in dev.ops) == 3
    assert tr.op_seconds(dev, "jit_tiny_step", "expert_gemm",
                         custom_call=True) == pytest.approx(136250e-12)
    assert tr.op_seconds(dev, "jit_tiny_step", "expert_gemm") \
        == pytest.approx(978828e-12)
    assert tr.busy_seconds(dev) == pytest.approx(2277500e-12)
    lo, hi = t.window
    assert hi - lo == pytest.approx(2051500e-9)
    # after alignment every execution starts inside its launching span
    spans = sorted(a for n, a, _ in t.host if n == "host.sample_sync")
    ends = sorted(b for n, _, b in t.host if n == "host.sample_sync")
    for (_, a, b), s, e in zip(dev.modules, spans, ends):
        assert s - 1e-3 <= a and b <= e
    gaps = dict(tr.idle_gaps(dev, t.host, lo, hi))
    assert sum(gaps.values()) == pytest.approx(hi - lo - 2277500e-12)
    assert max(gaps, key=gaps.get) == "host.sample_sync"
    top = dict(tr.top_ops(dev))
    assert top["jit_tiny_step:moe/expert_gemm:kernel"] \
        == pytest.approx(136250e-12)
