"""Serving benchmark of the ReaLB engine on the chip: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<name>.json``, whose ``arch`` names its architecture
module ``bench/archs/<arch>.py``), a traffic mix
(``bench/traffic/<name>.json``) and its chips.  On more than one chip the
run builds the ``(1, chips)`` ``("data", "model")`` mesh, EP over every
chip.  The run draws the weights from the seed on the device (into their
shards), builds ``repro.serving.engine.Engine``, compiles every program
shape the mix uses (set-up), then offers the mix for ``--seconds`` on the
wall clock (the window).  With ``--trace 0`` the last line of stdout holds
the end-to-end metrics; with ``--trace 1`` the profiler records the tail of
the window and the line holds the per-layer metrics
(``bench/metrics/<name>.py``).  Either way the served tokens of a sample
of finished requests are checked against the architecture's plain
reference, and the numbers compared are printed with their limits as the
last lines of stderr.

Exits 2, printing no result, when JAX finds no TPU or fewer chips than the
cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Callable, Optional  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

import numpy as np  # noqa: E402

from harness import arch as archs  # noqa: E402
from harness import check, model, spec, stats, traffic, weights  # noqa: E402
from harness.context import Run  # noqa: E402
from harness.flops import peaks  # noqa: E402
from harness.serve import (Boundary, Recorder, closed_window,  # noqa: E402
                           open_window)


def say(*parts) -> None:
    print(*parts, flush=True)


class CompileClock:
    """Backend compile seconds and persistent-cache hits and misses."""

    def __init__(self):
        import jax
        self.seconds, self.hits, self.misses, self.compiles = 0.0, 0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration_secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return (self.seconds, self.compiles, self.hits, self.misses)


class TraceTail(Boundary):
    """Starts the profiler ``tail_s`` before the window closes, and labels
    the host stages (the harness's own and, through the Recorder, the
    engine's) for the trace's idle gaps."""

    def __init__(self, seconds: float, tail_s: float, out_dir: str):
        import jax
        self.jax = jax
        self.start_at = max(0.0, seconds - tail_s)
        self.out_dir = out_dir
        self.on = False
        self.t_on = self.t_off = 0.0
        self._window = None

    def at(self, elapsed: float) -> None:
        if not self.on and elapsed >= self.start_at:
            self.jax.profiler.start_trace(self.out_dir)
            self._window = self.jax.profiler.TraceAnnotation("harness.traced")
            self._window.__enter__()
            self.t_on = time.perf_counter()
            self.on = True

    def stop(self) -> None:
        if self.on:
            self.t_off = time.perf_counter()
            self._window.__exit__(None, None, None)
            self.jax.profiler.stop_trace()

    def span(self, name: str):
        return self.jax.profiler.TraceAnnotation(name)


def warm(eng, mix: dict) -> None:
    """Compile every program shape the mix can use, without touching the
    engine's state: each chunk bucket, the decode step, the sampler."""
    import jax
    import jax.numpy as jnp
    b = eng.max_slots
    i32 = jnp.int32
    for s in traffic.prompt_buckets(mix):
        out = eng._chunk(eng.params, eng.cache, eng.m_state,
                         jnp.zeros((b, s), i32), jnp.zeros((b,), i32),
                         jnp.zeros((b,), i32), jnp.zeros((b, s), bool),
                         eng._place_args())
        jax.block_until_ready(out)
    out = eng._decode(eng.params, eng.cache, eng.m_state,
                      jnp.zeros((b, 1), i32),
                      jnp.full((b,), eng.max_len, i32),
                      jnp.zeros((b, 1), bool), jnp.zeros((b, 1), bool),
                      eng._place_args())
    eng._sample(out[0])


def device_info(devices, chips: int) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def memory_peaks(devices) -> list:
    """Peak bytes in use on each device."""
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in devices]


def end_to_end(w, due, setup_s: float, seconds: float) -> dict:
    ttft = stats.percentile(stats.waits_until(due, w.close, "first"), 90)
    itl = stats.percentile(stats.inter_token_gaps(w.tracks, w.close), 90)
    n_tok = stats.tokens_in(w.tracks, w.t0, w.close)
    out = {"ttft_p90_s": (ttft, "s"),
           "itl_p90_ms": (None if itl is None else itl * 1e3, "ms"),
           "output_tok_per_s": (n_tok / seconds, "tokens/s"),
           "setup_s": (setup_s, "s")}
    return {k: {"value": v, "unit": u} for k, (v, u) in out.items()
            if v is not None}


def engine_lines(eng, w, due) -> None:
    st = eng.stats
    pre = [s for s in st if s.phase == "prefill"]
    gated = [s for s in pre if s.gate_open > 0]
    fin = sum(1 for t in due if t.done)
    say(f"[serve] requests due in the window {len(due)}, finished "
        f"{fin}; tokens stamped {sum(len(t.tokens) for t in w.tracks)}")
    late = np.asarray(w.late) if w.late else np.zeros(1)
    say(f"[serve] generator lateness (submit - due) s: median "
        f"{float(np.median(late))}, p90 {float(np.percentile(late, 90))}, "
        f"max {float(late.max())}")
    say(f"[serve] iterations: prefill {len(pre)}, decode "
        f"{sum(1 for s in st if s.phase == 'decode')}; gate_open duty over "
        f"prefill {float(np.mean([s.gate_open for s in pre])) if pre else 0.0}"
        f"; ib_global mean over gated prefill "
        f"{float(np.mean([s.ib_global for s in gated])) if gated else 0.0}; "
        f"prefill iterations with FP4 {sum(1 for s in pre if s.fp4_ranks > 0)}"
        f", decode iterations with FP4 "
        f"{sum(1 for s in st if s.phase == 'decode' and s.fp4_ranks > 0)}; "
        f"max drop_frac {max((s.drop_frac for s in st), default=0.0)}")


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, devices, root: Path = ROOT,
             engine_hook: Optional[Callable] = None,
             control: bool = False,
             clock: Callable[[], float] = time.perf_counter) -> dict:
    """One run; returns the result line.  ``engine_hook(engine)`` runs on
    the built engine before set-up ends (tests plant faults with it);
    ``control`` also judges the fp8 control by the same verdict
    (``control_correct``; bench/control.py); ``clock`` times the window."""
    from repro.models.common import use_mesh
    mesh = model.mesh_of(cell.chips)
    with use_mesh(mesh):
        return _run_cell(cell, seed, seconds, trace, t_start, devices, root,
                         engine_hook, control, clock, mesh)


def _run_cell(cell, seed, seconds, trace, t_start, devices, root,
              engine_hook, control, win_clock, mesh) -> dict:
    import jax

    clock = CompileClock()
    mix, eng_cfg = cell.traffic, cell.traffic["engine"]
    mod, arch = archs.of(cell.config, root)
    cfg = mod.model_config(cell.config_name, arch)
    pk = peaks(devices[0].device_kind) if devices[0].platform == "tpu" \
        else None
    shard = model.shardings(cfg)
    if mesh is not None:
        say(f"[setup] mesh {dict(mesh.shape)} over device ids "
            f"{[d.id for d in mesh.devices.flat]}")
    t0 = time.perf_counter()
    params = jax.block_until_ready(weights.draw(mod, arch, seed, shard))
    model.check_layout(cfg, params)
    say(f"[setup] weights drawn on the device in "
        f"{time.perf_counter() - t0} s")
    eng = model.make_engine(cfg, params, eng_cfg, time.perf_counter)
    del params
    if engine_hook is not None:
        engine_hook(eng)
    warm(eng, mix)
    if mix["kind"] == "closed":
        work = traffic.closed_loop(mix, seed, 64, arch.vocab)
    else:
        work = traffic.open_loop(mix, seed, seconds, arch.vocab)
    c_setup = clock.snapshot()
    setup_s = time.perf_counter() - t_start
    say(f"[setup] setup_s {setup_s}; compile s {c_setup[0]} over "
        f"{c_setup[1]} compiles; persistent cache hits {c_setup[2]}, "
        f"misses {c_setup[3]}")

    rec = Recorder(eng, annotate=trace)
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    boundary = TraceTail(seconds, mix["trace_tail_s"], trace_dir) \
        if trace else None
    try:
        if mix["kind"] == "closed":
            w = closed_window(eng, work, seconds, mix["think_s"],
                              win_clock, boundary)
        else:
            w = open_window(eng, work, seconds, win_clock, boundary)
    finally:
        if boundary is not None:
            boundary.stop()
    c_win = clock.snapshot()
    say(f"[window] compiles inside the window: "
        f"{c_win[1] - c_setup[1]} ({c_win[0] - c_setup[0]} s)")
    per_chip = memory_peaks(devices[:cell.chips])
    mem = max(per_chip)
    rec.to_host()
    due = stats.due_in(w.tracks, w.t0, w.close)
    engine_lines(eng, w, due)
    dev = device_info(devices, cell.chips)
    dev["memory_peak_bytes"] = mem
    say(f"[device] {dev['kind']} ({dev['platform']}), {cell.chips} chip(s); "
        f"peak bytes in use {mem} (by chip {per_chip})")

    result = {"correct": False, "attempted": len(due),
              "failed": sum(1 for t in due if t.failed)}
    if trace:
        from harness import trace as tr
        files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
        trc = tr.load(str(files[-1]))
        say(f"[trace] {files[-1].stat().st_size} bytes: {tr.describe(trc)}")
        shutil.rmtree(trace_dir, ignore_errors=True)
        run = Run(arch=arch, counts=mod, chips=cell.chips, peaks=pk,
                  window=w, due=due, steps=rec.steps, iter_stats=eng.stats,
                  policy_ranks=int(eng.m_state.shape[-1]), ep=cell.chips,
                  trace=trc,
                  traced_steps=[s for s in rec.steps
                                if boundary.t_on <= s.t_call
                                <= boundary.t_off])
        lo, hi = trc.window
        d0 = run.device()
        busy = [tr.busy_seconds(d) for d in trc.devices.values()]
        dev["busy_s"] = float(np.mean(busy)) if busy else 0.0
        dev["window_s"] = hi - lo
        vals = spec.read_metrics(cell.per_layer, run, root)
        units = {m["name"]: m["unit"] for m in cell.per_layer}
        result["metrics"] = {k: {"value": v, "unit": units[k]}
                             for k, v in vals.items() if v is not None}
        if d0 is not None:
            result["breakdown"] = {
                "device_ops": tr.top_ops(d0),
                "idle_gaps": tr.idle_gaps(d0, trc.host, lo, hi)}
        say(f"[trace] window {hi - lo} s, busy {dev['busy_s']} s, traced "
            f"steps {len(run.traced_steps)}; metrics missing: "
            f"{sorted(k for k, v in vals.items() if v is None)}")
    else:
        result["metrics"] = end_to_end(w, due, setup_s, seconds)
    result["device"] = dev

    # correctness: the program's state goes first, then the reference
    lim = cell.settings
    drop = check.max_drop(rec.steps)
    picked = check.sample(w.requests, mix["check_sample"], seed)
    try:
        seqs = check.sequences(picked, rec.steps, arch.n_moe, cell.chips)
    except ValueError as e:
        say(f"[check] {e}")
        seqs = []
    eng.params = eng.cache = None        # even if a cycle keeps eng alive
    del eng, rec
    gc.collect()
    checked = {"drop_frac_max": {"value": drop, "limit": 0.0}}
    if seqs:
        from harness import reference
        ref_params = weights.draw(mod, arch, seed, shard)
        t1 = time.perf_counter()
        got = reference.check_sequences(mod.forward, ref_params, arch, seqs,
                                        eng_cfg["max_len"], control=control)
        del ref_params
        gap = float(got["gap"].mean())
        say(f"[check] {len(seqs)} requests, {got['gap'].size} served tokens "
            f"against the reference in {time.perf_counter() - t1} s; gap "
            f"mean {gap}, widest {float(got['gap'].max())}, tokens not the "
            f"reference's best {float(np.mean(got['gap'] > 0))}")
        checked["mean_logit_gap"] = {"value": gap,
                                     "limit": lim["mean_logit_gap"]}
        checked["served_tokens_checked"] = {"value": int(got["gap"].size),
                                            "limit": 1}
        if control:
            gc_ = got["gap_control"]
            say(f"[check] fp8 control: gap mean {float(gc_.mean())}, widest "
                f"{float(gc_.max())}, tokens not the reference's best "
                f"{float(np.mean(gc_ > 0))}")
            checked["mean_logit_gap_fp8_control"] = {
                "value": float(gc_.mean()), "limit": lim["mean_logit_gap"]}
            result["control_correct"] = check.verdict(
                float(gc_.mean()), result["failed"], lim["mean_logit_gap"],
                drop)
        result["correct"] = check.verdict(gap, result["failed"],
                                          lim["mean_logit_gap"], drop)
    else:
        checked["served_tokens_checked"] = {"value": 0, "limit": 1}
    result["checked"] = checked
    for name, c in checked.items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    return result


def enable_cache() -> str:
    """JAX's persistent compile cache: where JAX_COMPILATION_CACHE_DIR
    says, else at the checkout's fixed ``.jax_cache/``; every program is
    written, however fast it compiled."""
    import jax
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cache


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = spec.load_cell(args.workload)
    except (spec.SpecError, OSError, KeyError, ValueError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: cell {cell.name} needs {cell.chips} TPU chip(s); JAX "
              f"sees {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    cache = enable_cache()
    say(f"[setup] device {devices[0].device_kind}, {len(devices)} visible, "
        f"cell {cell.name} on {cell.chips}; compile cache {cache}")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      T_START, devices)
    checked = result.pop("checked")
    result["checked"] = checked                 # the compared numbers last
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
