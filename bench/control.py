"""Correctness readings on the chip, outside the benchmark's own runs.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3

For each seed, one run of the cell at its own size and load (a window of
``--seconds``), then the sampled requests through the reference twice: the
program's mean logit gap (its largest over the seeds is the lower reading
of the cell's limit) and that of the fp8 control, the reference computed
in the precision below the configuration's bf16 (its smallest is the
upper reading), each judged by the run's verdict (``correct`` and
``control_correct``; the control has to come out false).  One JSON line
per seed.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = run.spec.load_cell(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("control: no TPU", file=sys.stderr)
        return 2
    run.enable_cache()
    for seed in args.seeds:
        res = run.run_cell(cell, seed, args.seconds, False,
                           time.perf_counter(), devices, control=True)
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "control_correct": res.get("control_correct"),
                          **{k: v["value"] for k, v in res["checked"].items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
