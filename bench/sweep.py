"""Find a mix's knee once: the highest mean offered rate the engine
sustains without a growing backlog.

    python3 bench/sweep.py --workload <cell> --seconds 40 --rates 1 1.5 2 3

One engine serves one window per rate (the cell's mix with ``mean_rate``
replaced), draining between windows.  Per rate one JSON line: requests
due and finished, the backlog at the close, the largest share of routed
assignments a step dropped, and TTFT p90 over the window's first and
second halves (a backlog that grows shows as a second
half far above the first).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import run  # noqa: E402
from harness import arch as archs  # noqa: E402
from harness import model, stats, traffic, weights  # noqa: E402
from harness.serve import open_window  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--steady", action="store_true",
                    help="Poisson arrivals at each rate, no bursts")
    args = ap.parse_args(argv)
    cell = run.spec.load_cell(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("sweep: no TPU", file=sys.stderr)
        return 2
    run.enable_cache()
    from repro.models.common import use_mesh
    with use_mesh(model.mesh_of(cell.chips)):
        sweep(cell, args)
    return 0


def sweep(cell, args) -> None:
    mod, arch = archs.of(cell.config)
    cfg = mod.model_config(cell.config_name, arch)
    params = weights.draw(mod, arch, args.seed, model.shardings(cfg))
    eng = model.make_engine(cfg, params, cell.traffic["engine"],
                            time.perf_counter)
    del params
    run.warm(eng, cell.traffic)
    for rate in args.rates:
        mix = dict(cell.traffic, mean_rate=rate)
        if args.steady:
            mix.update(calm_mult=1.0, burst_mult=1.0)
        specs = traffic.open_loop(mix, args.seed, args.seconds, arch.vocab)
        n0 = len(eng.stats)
        w = open_window(eng, specs, args.seconds, time.perf_counter)
        mid = w.t0 + args.seconds / 2
        due = stats.due_in(w.tracks, w.t0, w.close)
        halves = [[t for t in due if t.due < mid],
                  [t for t in due if t.due >= mid]]
        p90 = [stats.percentile(stats.waits_until(h, w.close, "first"), 90)
               for h in halves]
        fin = sum(1 for t in due if t.tokens and t.tokens[-1] <= w.close
                  and t.done)
        print(json.dumps({
            "rate": rate, "due": len(due), "finished": fin,
            "backlog": len(due) - fin, "ttft_p90_first_half_s": p90[0],
            "ttft_p90_second_half_s": p90[1],
            "tokens_per_s": stats.tokens_in(w.tracks, w.t0, w.close)
            / args.seconds,
            "max_drop_frac": max((st.drop_frac for st in eng.stats[n0:]),
                                 default=0.0)}), flush=True)
        eng.run()                                  # drain


if __name__ == "__main__":
    raise SystemExit(main())
