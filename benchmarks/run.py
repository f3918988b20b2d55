"""Benchmark harness: one function per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run`` runs everything and prints one
CSV block per experiment, each prefixed by ``== <name> ==``.  A final
``name,us_per_call,derived`` summary row per experiment gives the harness
wall time and the experiment's headline quantity.
"""
from __future__ import annotations

import sys
import time


def _csv(rows):
    if not rows:
        print("(empty)")
        return
    cols = list(rows[0])
    print(",".join(cols))
    for r in rows:
        print(",".join(str(r[c]) for c in cols))


def _kernel_microbench():
    """Wall-clock of the jnp NVFP4 oracle ops on CPU + modeled v5e kernel
    times from the roofline constants."""
    import jax
    import jax.numpy as jnp

    from benchmarks import costmodel as cm
    from repro.core import quant

    rows = []
    n, k, m = 1408, 2048, 4096
    w = jax.random.normal(jax.random.PRNGKey(0), (k, n), jnp.float32) * 0.05
    x = jax.random.normal(jax.random.PRNGKey(1), (m, k), jnp.float32)
    qt = quant.quantize_fp4(w)

    f_q = jax.jit(lambda w: quant.quantize_fp4(w))
    f_mm = jax.jit(lambda x: quant.matmul_w4a4(x, qt))
    for name, f, arg, flops, bytes_ in (
            ("quantize_fp4", f_q, w, 0, n * k * 2.53),
            ("fp4_matmul_w4a4", f_mm, x, 2 * m * n * k,
             m * k * 2 + n * k * 0.53)):
        jax.block_until_ready(f(arg))
        t0 = time.perf_counter()
        for _ in range(3):
            jax.block_until_ready(f(arg))
        us = (time.perf_counter() - t0) / 3 * 1e6
        v5e_us = max(flops / cm.PEAK_INT8, bytes_ / cm.HBM_BW) * 1e6
        rows.append(dict(kernel=name, cpu_oracle_us=round(us, 1),
                         modeled_v5e_us=round(v5e_us, 2),
                         flops=flops, bytes=int(bytes_)))
    return rows


def main() -> None:
    from benchmarks import (fig2_routing_dynamics, fig4_lb_gate,
                            fig5_latency_breakdown, fig9_aimd, table1_main,
                            table4_prefill)

    summary = []

    def run_one(name, fn, derived_fn):
        t0 = time.perf_counter()
        rows = fn()
        dt = (time.perf_counter() - t0) * 1e6
        print(f"== {name} ==")
        _csv(rows)
        print()
        summary.append((name, dt, derived_fn(rows)))

    run_one("fig2_routing_dynamics", fig2_routing_dynamics.run,
            lambda r: f"device_imb_p95={max(x['device_imb_p95'] for x in r)}")
    run_one("table1_main", lambda: table1_main.run("main"),
            lambda r: "best_realb_speedup=" + str(max(
                x["speedup"] for x in r if x["strategy"] == "ReaLB")))
    run_one("table2_acc_ext", lambda: table1_main.run("ext", quality=True),
            lambda r: "worst_dacc=" + str(min(
                x["delta_acc_proxy"] for x in r
                if x["strategy"] == "ReaLB")))
    run_one("fig4_lb_gate", fig4_lb_gate.run,
            lambda r: "crossover_tokens=" + str(next(
                (x["tokens_per_rank"] for x in r if x["gemm_frac"] > 0.5),
                -1)))
    run_one("fig5_latency_breakdown", fig5_latency_breakdown.run,
            lambda r: "realb_e2e_reduction_pct=" + str(max(
                x["e2e_reduction_pct"] for x in r
                if x["strategy"] == "ReaLB")))
    run_one("fig9_aimd", fig9_aimd.run,
            lambda r: f"m_d_min={min(x['m_d_min'] for x in r)}")
    run_one("table4_prefill", table4_prefill.run,
            lambda r: "max_speedup=" + str(max(
                x["speedup_prefill_only"] for x in r)))
    run_one("kernel_microbench", _kernel_microbench,
            lambda r: "modeled_v5e_us=" + str(r[-1]["modeled_v5e_us"]))

    print("== summary (name,us_per_call,derived) ==")
    for name, us, derived in summary:
        print(f"{name},{us:.0f},{derived}")


if __name__ == "__main__":
    main()
