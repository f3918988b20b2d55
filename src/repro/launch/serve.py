"""Serving driver: workload-generated multimodal requests through the
chunked-prefill engine.

    PYTHONPATH=src python -m repro.launch.serve --arch moonshot-v1-16b-a3b \
        --preset tiny --requests 12 --max-new 8

Synthesizes a request stream from a named workload profile (the same
calibration the trace benchmarks use), runs the continuous-batching engine
with ReaLB live, and reports throughput, TTFT/TPOT percentiles and
per-iteration balance stats.  ``benchmarks/serve_bench.py`` is the full
open-loop experiment (arrival processes, virtual clock, record/replay);
this driver is the quick interactive entry point.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import ReaLBConfig, get_config, reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import mesh_for
from repro.models import transformer as tf
from repro.models.common import use_mesh
from repro.serving.engine import Engine
from repro.serving.telemetry import Telemetry
from repro.workloads import make_stream, profile
from repro.workloads.profiles import WORKLOADS


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="moonshot-v1-16b-a3b")
    ap.add_argument("--preset", default="tiny", choices=["tiny", "full"])
    ap.add_argument("--workload", default="MMMU", choices=sorted(WORKLOADS))
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-prompt", type=int, default=40)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prefill-budget", type=int, default=256,
                    help="tokens of batched prefill per iteration "
                         "(0 = legacy one-shot per-request prefill)")
    ap.add_argument("--mesh", default="none",
                    choices=["none", "host", "single_pod", "multi_pod"])
    ap.add_argument("--gate-gamma", type=int, default=8,
                    help="LB gate Γ (small default so tiny runs exercise it)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.preset == "tiny":
        cfg = reduced(cfg)
    mesh = None if args.mesh == "none" else mesh_for(args.mesh)
    rcfg = ReaLBConfig(gate_gamma=args.gate_gamma)

    prof = profile(args.workload,
                   prompt_len_mean=max(args.max_prompt * 2 // 3, 8),
                   prompt_len_std=args.max_prompt // 4,
                   prompt_len_min=8, prompt_len_max=args.max_prompt,
                   max_new_mean=args.max_new, max_new_min=args.max_new,
                   max_new_max=args.max_new)
    specs = make_stream(prof, np.zeros(args.requests), cfg.vocab_size,
                        seed=args.seed)

    with use_mesh(mesh):
        params = tf.init_model(cfg, jax.random.PRNGKey(0))
        max_len = args.max_prompt + args.max_new + 8
        telemetry = Telemetry()
        eng = Engine(cfg, params, rcfg, max_slots=args.slots,
                     max_len=max_len, prefill_budget=args.prefill_budget,
                     telemetry=telemetry)
        for spec in specs:
            req = spec.to_request()
            req.arrival_time = None    # stamp with the wall clock at submit
            eng.submit(req)
        t0 = time.perf_counter()
        done = eng.run()
        dt = time.perf_counter() - t0

    out_toks = sum(len(r.generated) for r in done)
    in_toks = sum(r.prompt_len for r in done)
    print(f"served {len(done)} requests, {in_toks} prompt + {out_toks} "
          f"generated tokens in {dt:.2f}s "
          f"({(in_toks + out_toks) / dt:.1f} tok/s)")
    if eng.stats:
        s = telemetry.summary()
        gates = [st.gate_open for st in eng.stats]
        print(f"iterations: {len(eng.stats)} "
              f"(prefill chunked={eng.chunked}), "
              f"mean IB_global="
              f"{np.mean([st.ib_global for st in eng.stats]):.2f}, "
              f"gate-open frac={np.mean(gates):.2f}, "
              f"gate duty prefill={s['gate_duty_prefill']:.2f}, "
              f"mean fp4 ranks="
              f"{np.mean([st.fp4_ranks for st in eng.stats]):.2f}")
        if s["ttft"]:
            print(f"TTFT p50/p99: {s['ttft']['p50']:.3f}/"
                  f"{s['ttft']['p99']:.3f}s  "
                  f"TPOT p50: {s['tpot'].get('p50', float('nan')):.4f}s")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
