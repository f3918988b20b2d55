"""End-to-end open-loop serving benchmark: workload → engine → percentiles.

Drives the full request path (arrival process → multimodal prompt
synthesis → modality-aware admission → chunked batched prefill → decode)
with ReaLB live, and reports the paper's serving quantities: TTFT / TPOT
percentiles (overall and split by modality), ``ib_global`` distribution,
and LB-gate / FP4 duty cycles split by phase — batched prefill is where
the gate opens, which the v1 per-request prefill loop never reached.

    PYTHONPATH=src python benchmarks/serve_bench.py \
        --workload MMMU --arrivals bursty

Runs in *virtual time* by default: a seeded arrival stream plus a linear
per-iteration cost model make every latency number reproducible across
hosts (use ``--wall-time`` for real clocks).  ``--record``/``--replay``
pin the exact request stream for policy A/Bs:

    python benchmarks/serve_bench.py --workload MMMU --arrivals bursty \
        --record /tmp/mmmu.jsonl
    python benchmarks/serve_bench.py --replay /tmp/mmmu.jsonl --policy off

``--arm`` selects one of the comparison arms of the paper's baseline
axis (off / realb / placement / realb+placement / replicate /
realb+replicate, the ``/L`` per-layer variants that plan one table per
scanned MoE block with layer-diff migration, and the ``/async`` arms
that drain each staged plan as byte-budgeted per-layer slab chunks
overlapped with serving — ``--migrate-async`` /
``--migrate-bytes-per-iter``, stall vs hidden migration seconds split
out in the summary) and implies a virtual
EP topology (``--virtual-ep``, default 4) so IB_d, FP4 duty, token-split
duty and migration bytes are meaningful in a single-device virtual-time
run; the plain ``--policy`` flag keeps the original placement-free
behavior.  ``--arm all`` runs every arm head-to-head on the *same*
realized request stream in one deterministic invocation and prints a
comparison table; ``--json-out BENCH_serve.json`` writes the per-arm
summaries (throughput, TTFT/TPOT percentiles, IB, migration bytes —
per-layer migration bytes included) as a machine-readable CI artifact.

``--scenario kill-rejoin`` drives the elastic serving path: a replicate
arm runs twice on the same realized stream — once healthy, once with a
scripted rank loss at ``--fail-iter`` and a rejoin at ``--rejoin-iter``
(knobs: ``--fail-rank``, and ``--migrate-bytes-per-iter`` as the
recovery chunk budget).  The faulted run re-materializes stranded
singleton experts from a pre-kill checkpoint through the byte-budgeted
migration queue and reports ``recovery_s`` / ``availability`` /
``degraded_iters`` plus post-recovery throughput next to the healthy
arm's:

    python benchmarks/serve_bench.py --scenario kill-rejoin \
        --json-out BENCH_serve.json

Every arm runs with the hot-loop profiler live (FLOP/byte ledger →
``mfu`` / ``roofline_fraction`` / per-phase seconds / costmodel drift in
the summary and ``BENCH_serve.json``); ``--profile-out`` writes the
profile JSON that ``benchmarks/profile_report.py`` summarizes and
reconciles, and ``--xprof-out DIR`` captures a programmatic
``jax.profiler`` device trace with the MoE phases labeled by
``jax.named_scope``.
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Dict, List, Optional

import numpy as np

from repro.configs import (PlacementConfig, ReaLBConfig, ReplicationConfig,
                           get_config, reduced)
from repro.models import transformer as tf
from repro.placement import PlacementManager
from repro.replication import ReplicaManager, expand_moe_params
from repro.serving.engine import Engine
from repro.serving.telemetry import Telemetry
from repro.workloads import (ArrivalConfig, ClosedLoop, IterationCostModel,
                             VirtualClock, arrival_times, load_stream,
                             make_stream, profile, save_stream, stream_stats)
from repro.workloads.multimodal import RequestSpec, synth_request
from repro.workloads.profiles import WORKLOADS

# ReaLBConfig overrides per ablation arm
POLICIES = {
    "realb": {},
    "off": {"enabled": False},           # never compress
}

# the serving arms of the load-balancing comparison:
# (policy, expert-layout manager kind, per-layer tables, async migration)
ARMS = {
    "off": ("off", None, False, False),
    "realb": ("realb", None, False, False),
    "placement": ("off", "placement", False, False),
    "realb+placement": ("realb", "placement", False, False),
    "replicate": ("off", "replication", False, False),
    "realb+replicate": ("realb", "replication", False, False),
    # per-layer variants: one table per scanned MoE block, layer-diff
    # migration (changed layers only)
    "placement/L": ("off", "placement", True, False),
    "realb+placement/L": ("realb", "placement", True, False),
    "replicate/L": ("off", "replication", True, False),
    "realb+replicate/L": ("realb", "replication", True, False),
    # async overlapped migration: per-layer slab chunks drain one
    # byte-budgeted batch per iteration; stall vs hidden seconds split
    "placement/L/async": ("off", "placement", True, True),
    "replicate/L/async": ("off", "replication", True, True),
}


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default="MMMU", choices=sorted(WORKLOADS))
    ap.add_argument("--arrivals", default="poisson",
                    choices=["poisson", "bursty", "diurnal", "closed"])
    ap.add_argument("--policy", default="realb", choices=sorted(POLICIES))
    ap.add_argument("--arm", default=None,
                    choices=sorted(ARMS) + ["all"],
                    help="comparison arm; overrides --policy and enables "
                         "the expert-layout loop for the placement / "
                         "replicate arms.  'all' runs every arm on the "
                         "same realized stream in one deterministic run")
    ap.add_argument("--planner", default="least_loaded",
                    choices=["identity", "least_loaded", "modality_aware"])
    ap.add_argument("--replan-every", type=int, default=32,
                    help="engine iterations between placement replans")
    ap.add_argument("--per-layer", action="store_true",
                    help="per-MoE-layer placement/replication tables "
                         "(one table per scanned block, layer-diff "
                         "migration); the /L arms imply this")
    ap.add_argument("--migrate-async", action="store_true",
                    help="asynchronous overlapped migration: drain a "
                         "staged plan as byte-budgeted per-layer slab "
                         "chunks across serving iterations (each layer's "
                         "table commits as its slab lands) instead of one "
                         "synchronous whole-plan stall; the /async arms "
                         "imply this")
    ap.add_argument("--migrate-bytes-per-iter", type=int, default=0,
                    help="explicit async chunk budget in bytes per "
                         "iteration (0 = derive from the measured "
                         "bytes/s EWMA x recent iteration seconds)")
    ap.add_argument("--decode-replan-every", type=int, default=0,
                    help="decode iterations between decode-regime "
                         "replans, planned from the predictor's decode "
                         "window (0 = prefill cadence only)")
    ap.add_argument("--decode-halflife", type=float, default=8.0,
                    help="decode-window EWMA half-life in decode "
                         "iterations (used when --decode-replan-every "
                         "is set)")
    ap.add_argument("--spare-per-rank", type=int, default=1,
                    help="replica slots per rank beyond E // ranks "
                         "(replicate arms)")
    ap.add_argument("--max-replicas", type=int, default=2,
                    help="replica cap per logical expert (replicate arms)")
    ap.add_argument("--replica-capacity-margin", type=float, default=0.0,
                    help="replica-aware dispatch capacity: shrink "
                         "capacity_factor to margin x the post-split "
                         "predicted peak rank load at each committed "
                         "replan (0 = static capacity_factor)")
    ap.add_argument("--cost-gate", action="store_true",
                    help="gate replans on the analytic cost model: fire "
                         "only when predicted layer-time savings over the "
                         "replan interval exceed the migration time")
    ap.add_argument("--cost-gate-calibrated", action="store_true",
                    help="like --cost-gate, but tokens/iter is calibrated "
                         "from measured engine IterStats instead of the "
                         "static roofline constant")
    ap.add_argument("--scenario", default="steady",
                    choices=["steady", "kill-rejoin"],
                    help="kill-rejoin: run a replicate arm healthy and "
                         "again with a scripted rank loss + rejoin on "
                         "the same stream; emits recovery_s / "
                         "availability / degraded_iters")
    ap.add_argument("--fail-iter", type=int, default=8,
                    help="engine iteration of the scripted rank loss "
                         "(kill-rejoin scenario)")
    ap.add_argument("--rejoin-iter", type=int, default=48,
                    help="engine iteration of the scripted rank rejoin "
                         "(kill-rejoin scenario)")
    ap.add_argument("--fail-rank", type=int, default=1,
                    help="virtual EP rank to kill (kill-rejoin scenario)")
    ap.add_argument("--virtual-ep", type=int, default=None,
                    help="virtual EP topology for the policy statistics on "
                         "a single device (default: 4 when --arm is given, "
                         "else off)")
    ap.add_argument("--arch", default="moonshot-v1-16b-a3b")
    ap.add_argument("--preset", default="tiny", choices=["tiny", "full"])
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--rate", type=float, default=12.0,
                    help="mean arrivals per (virtual) second")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=512)
    ap.add_argument("--prefill-budget", type=int, default=1024)
    ap.add_argument("--gate-gamma", type=int, default=512,
                    help="LB gate Γ on *real* routed tokens; sized so "
                         "multi-request prefill chunks cross it while "
                         "decode batches stay far below")
    ap.add_argument("--md-init", type=float, default=None, metavar="M",
                    help="override ReaLB AIMD threshold start m_d "
                         "(default: config's md_init; 0 makes every "
                         "hot vision-heavy rank eligible for FP4 from "
                         "iteration one)")
    ap.add_argument("--no-aimd", action="store_true",
                    help="freeze m_d at its start value (adaptive=False) "
                         "— used by the profiled CI arm to keep the FP4 "
                         "duty cycle deterministic")
    ap.add_argument("--fused", default="auto",
                    choices=["auto", "pallas", "interpret", "jnp"],
                    help="FP4 expert-FFN backend (kernels/ops.py): fused "
                         "Pallas grouped kernel (native / interpret) or "
                         "the jnp oracle; auto = pallas on TPU, jnp on "
                         "CPU")
    ap.add_argument("--text-reserve", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--wall-time", action="store_true",
                    help="use wall clocks instead of the virtual clock")
    ap.add_argument("--record", default=None, metavar="PATH",
                    help="save the realized request stream to JSONL")
    ap.add_argument("--replay", default=None, metavar="PATH",
                    help="replay a recorded JSONL stream (overrides "
                         "--workload/--arrivals/--requests)")
    ap.add_argument("--json", action="store_true",
                    help="emit one machine-readable JSON summary line")
    ap.add_argument("--json-out", default=None, metavar="PATH",
                    help="write per-arm summaries to a JSON file "
                         "(e.g. BENCH_serve.json as a CI artifact)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome-trace/Perfetto JSON of the run's "
                         "spans (iter / admit / launch.* / "
                         "migration.drain / replan.* / elastic.*); also "
                         "attaches the replan audit log.  Deterministic "
                         "under the virtual clock.  Summarize with "
                         "benchmarks/trace_report.py; under --arm all / "
                         "kill-rejoin the trace covers the last "
                         "(faulted) run only")
    ap.add_argument("--audit-out", default=None, metavar="PATH",
                    help="write the replan-decision audit log (one JSON "
                         "event per maybe_replan verdict: cadence, "
                         "warmup, min-gain, cost-gate numbers, must-plan) "
                         "as JSONL")
    ap.add_argument("--log-every", type=int, default=0, metavar="N",
                    help="print one structured JSONL log line every N "
                         "serving iterations (iter, phase, tokens, "
                         "ib_global, fp4_ranks, mfu, per-phase seconds, "
                         "migration stall/hidden, unroutable) for "
                         "long-run debugging without a trace viewer")
    ap.add_argument("--profile-out", default=None, metavar="PATH",
                    help="write the hot-loop profiler's phase/FLOP/drift "
                         "JSON (schema repro.profile.v1); summarize and "
                         "reconcile with benchmarks/profile_report.py. "
                         "Under --arm all / kill-rejoin the profile "
                         "covers the last run only (like --trace-out)")
    ap.add_argument("--sentinel", action="store_true",
                    help="arm the repro.analysis runtime sentinel for "
                         "the run: guard the hot loop against "
                         "unsanctioned device->host syncs and count jit "
                         "compiles per engine entry point")
    ap.add_argument("--sentinel-out", default=None, metavar="PATH",
                    help="write the sentinel report JSON (implies "
                         "--sentinel)")
    ap.add_argument("--xprof-out", default=None, metavar="DIR",
                    help="capture a programmatic jax.profiler device "
                         "trace of the serve loop into DIR (open with "
                         "xprof/tensorboard); the jax.named_scope phase "
                         "annotations in core/ep_moe.py label the MoE "
                         "stages in the timeline")
    return ap.parse_args(argv)


def build_stream(args, vocab_size: int, max_prompt: int
                 ) -> List[RequestSpec]:
    prof = profile(args.workload)
    acfg = ArrivalConfig(kind=args.arrivals, rate=args.rate,
                         n_requests=args.requests, seed=args.seed,
                         concurrency=min(args.slots, args.requests))
    return make_stream(prof, arrival_times(acfg), vocab_size,
                       seed=args.seed + 1, max_prompt=max_prompt)


def resolve_arm(args):
    """Apply --arm to (policy, manager kind, per-layer, async migration,
    virtual_ep) in place; returns the manager kind."""
    kind = None
    if args.arm is not None and args.arm != "all":
        args.policy, kind, per_layer, migrate_async = ARMS[args.arm]
        args.per_layer = args.per_layer or per_layer
        args.migrate_async = args.migrate_async or migrate_async
        if args.virtual_ep is None:
            args.virtual_ep = 4
    return kind


def make_cost_gate(args, cfg, ep: int):
    """An analytic-cost-model replan gate for this model's MoE geometry
    (``--cost-gate-calibrated`` swaps the static tokens/iter constant for
    a window of measured engine iterations)."""
    try:
        from benchmarks import costmodel as cm
    except ImportError:     # run as `python benchmarks/serve_bench.py`:
        import pathlib      # sys.path[0] is benchmarks/, not the repo root
        import sys
        sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
        from benchmarks import costmodel as cm
    n_moe = max(sum(1 for f in cfg.ffn_kinds() if f == "moe"), 1)
    geom = cm.MoEGeometry(cfg.name, cfg.d_model, cfg.moe.d_ff,
                          cfg.moe.num_experts, cfg.moe.top_k, n_moe)
    if args.cost_gate_calibrated:
        return cm.CalibratedReplanCostGate(
            geom, ep, horizon_iters=args.replan_every,
            default_tokens=float(args.prefill_budget))
    return cm.ReplanCostGate(geom, ep, horizon_iters=args.replan_every,
                             tokens_per_iter=float(args.prefill_budget))


def serve(args, cfg, params, specs: List[RequestSpec],
          inject_faults: bool = False):
    """Run the open-loop experiment; returns (telemetry, engine, realized
    specs, wall seconds).  ``inject_faults`` arms the kill-rejoin
    scenario: a pre-kill checkpoint, an :class:`ElasticCoordinator` over
    the replica manager and a scripted :class:`FaultInjector`."""
    kind = resolve_arm(args)
    from repro.kernels import ops as kops
    kops.set_ffn_backend(args.fused)
    pol = dict(POLICIES[args.policy])
    if args.md_init is not None:
        pol["md_init"] = args.md_init
    if args.no_aimd:
        pol["adaptive"] = False
    rcfg = ReaLBConfig(gate_gamma=args.gate_gamma, **pol)
    manager = None
    vep = args.virtual_ep or 4
    gate = make_cost_gate(args, cfg, vep) \
        if ((args.cost_gate or args.cost_gate_calibrated)
            and kind is not None) else None
    decode_hl = args.decode_halflife if args.decode_replan_every else 0.0
    if kind == "placement":
        pcfg = PlacementConfig(planner=args.planner,
                               replan_every=args.replan_every,
                               per_layer=args.per_layer,
                               decode_halflife=decode_hl,
                               decode_replan_every=args.decode_replan_every)
        manager = PlacementManager(cfg, pcfg, ep=vep, cost_gate=gate)
    elif kind == "replication":
        rpcfg = ReplicationConfig(replan_every=args.replan_every,
                                  spare_per_rank=args.spare_per_rank,
                                  max_replicas=args.max_replicas,
                                  per_layer=args.per_layer,
                                  decode_halflife=decode_hl,
                                  decode_replan_every=args.decode_replan_every)
        manager = ReplicaManager(cfg, rpcfg, ep=vep, cost_gate=gate)
        # lay the logical expert rows out into the replica slot space
        # (each scanned block by its own layer's set when per-layer)
        params = expand_moe_params(params, manager.rsets)
    if inject_faults and kind != "replication":
        raise SystemExit("--scenario kill-rejoin needs a replicate arm "
                         "(replicas are the availability mechanism); "
                         f"got arm={args.arm!r}")
    telemetry = Telemetry()
    # hot-loop profiler: FLOP/byte ledger + per-phase attribution +
    # costmodel drift, on every arm; it shares the telemetry registry so
    # mfu / roofline_fraction / phase seconds surface in summary() and
    # every arm's BENCH_serve.json
    profiler = None
    if cfg.moe is not None:
        from repro.obs import FlopByteLedger, Profiler
        profiler = Profiler(FlopByteLedger(cfg, ep=vep,
                                           fused=kops.ffn_fused()),
                            registry=telemetry.registry)
    if args.wall_time:
        # zero the wall clock at run start so it is comparable with the
        # stream's arrival times (seconds from 0) and paces the open loop
        t_start = time.monotonic()
        clock = lambda: time.monotonic() - t_start  # noqa: E731
    else:
        clock = VirtualClock()
    cost = IterationCostModel() if not args.wall_time else None
    # observability (opt-in): spans on the run clock — deterministic
    # under the virtual clock — and the replan-decision audit log
    trace_out = getattr(args, "trace_out", None)
    audit_out = getattr(args, "audit_out", None)
    tracer = None
    if trace_out:
        from repro.obs import Tracer
        tracer = Tracer(clock=clock)
    if manager is not None and (trace_out or audit_out):
        from repro.obs import ReplanAudit
        manager.audit = ReplanAudit()
    elastic = injector = None
    if inject_faults:
        import tempfile

        from repro.checkpoint import ckpt as ckpt_lib
        from repro.runtime.fault_tolerance import FaultInjector
        from repro.serving.elastic import ElasticCoordinator

        # the re-materialization source for singleton experts stranded
        # by the kill: the expanded slot-space params plus the manager's
        # replica tables, saved before any fault
        ckpt_dir = tempfile.mkdtemp(prefix="serve_bench_elastic_")
        ckpt_lib.save(ckpt_dir, 0,
                      {"serving": {"params": params},
                       manager.ckpt_group: manager.state_dict()})
        elastic = ElasticCoordinator(manager, ckpt_dir=ckpt_dir,
                                     clock=clock, telemetry=telemetry)
        injector = FaultInjector([(args.fail_iter, "fail", args.fail_rank),
                                  (args.rejoin_iter, "rejoin",
                                   args.fail_rank)])
    sentinel = None
    if getattr(args, "sentinel", False) or getattr(args, "sentinel_out",
                                                   None):
        from repro.analysis.sentinel import Sentinel
        sentinel = Sentinel()
        sentinel.arm()
    eng = Engine(cfg, params, rcfg, max_slots=args.slots,
                 max_len=args.max_len, prefill_budget=args.prefill_budget,
                 text_reserve=args.text_reserve, clock=clock,
                 telemetry=telemetry, cost_model=cost,
                 placement=manager, virtual_ep=args.virtual_ep,
                 capacity_margin=(args.replica_capacity_margin or None)
                 if kind == "replication" else None,
                 migrate_async=args.migrate_async,
                 migrate_bytes_per_iter=args.migrate_bytes_per_iter
                 or None,
                 elastic=elastic, fault_injector=injector, tracer=tracer,
                 profiler=profiler, sentinel=sentinel)

    xprof_out = getattr(args, "xprof_out", None)
    if xprof_out:
        import jax
        jax.profiler.start_trace(xprof_out)

    closed = None
    prof = profile(args.workload)
    spec_rng = np.random.default_rng(args.seed + 2)
    next_uid = len(specs)
    if args.arrivals == "closed" and args.replay is None:
        closed = ClosedLoop(ArrivalConfig(
            kind="closed", rate=args.rate, n_requests=args.requests,
            seed=args.seed, concurrency=min(args.slots, args.requests)))

    pending = sorted(specs, key=lambda s: s.arrival)
    realized: List[RequestSpec] = []
    n_total = args.requests if closed else len(pending)
    n_finished_seen = 0
    t0 = time.monotonic()
    max_prompt = args.max_len - prof.max_new_max - 1
    iters = 0
    while len(eng.scheduler.finished) < n_total:
        iters += 1
        assert iters < 200_000, "serve loop failed to converge"
        if eng.scheduler.idle and not pending:
            break                     # nothing left to do (replay shorter?)
        now = clock()
        while pending and pending[0].arrival <= now:
            spec = pending.pop(0)
            realized.append(spec)
            eng.submit(spec.to_request(d_model=cfg.d_model))
        if eng.scheduler.idle and pending:
            # idle gap: jump the event clock to the next arrival
            if isinstance(clock, VirtualClock):
                clock.advance(pending[0].arrival - now)
            else:
                time.sleep(max(pending[0].arrival - now, 0.0))
            continue
        eng.step()   # the engine advances the virtual clock per forward
        log_every = getattr(args, "log_every", 0)
        if log_every and iters % log_every == 0 and eng.stats:
            print(json.dumps(iter_log_record(eng, iters), default=float))
        if closed is not None:
            # every completion re-arms one user after a think time
            for req in eng.scheduler.finished[n_finished_seen:]:
                nxt = closed.next_arrival(req.finish_time)
                if nxt is not None:
                    spec = synth_request(prof, next_uid, nxt, spec_rng,
                                         cfg.vocab_size,
                                         max_prompt=max_prompt)
                    next_uid += 1
                    pending.append(spec)
            pending.sort(key=lambda s: s.arrival)
            n_finished_seen = len(eng.scheduler.finished)
    # finish any in-flight async chunk queue so the migration accounting
    # is complete and the engine is left in a checkpointable state
    eng.drain_migrations()
    if xprof_out:
        import jax
        jax.profiler.stop_trace()
        print(f"wrote xprof device trace -> {xprof_out}")
    profile_out = getattr(args, "profile_out", None)
    if profile_out and profiler is not None:
        from repro.kernels import ops as kops
        profiler.write(profile_out, metadata=dict(
            arm=args.arm or args.policy, arch=cfg.name,
            workload=args.workload, virtual_time=not args.wall_time,
            ffn_backend=kops.ffn_backend(), fused=kops.ffn_fused(),
            n_iters=int(telemetry.n_iters)))
        print(f"wrote profile ({profiler.n_iters} iters) -> {profile_out}")
    if tracer is not None:
        # the run totals travel with the trace so trace_report.py can
        # reconcile summed migration.drain span durations against them
        # without the JSON artifact
        tracer.write(trace_out, metadata=dict(
            arm=args.arm or args.policy,
            n_iters=int(telemetry.n_iters),
            virtual_time=not args.wall_time,
            migration_s_total=float(eng.migration_stall_s),
            migration_hidden_s_total=float(eng.migration_hidden_s),
            migration_bytes_total=int(eng.migration_bytes_moved)))
        print(f"wrote {len(tracer)} trace events -> {trace_out}")
    if audit_out and manager is not None \
            and getattr(manager, "audit", None) is not None:
        manager.audit.to_jsonl(audit_out)
        print(f"wrote {len(manager.audit)} replan decisions -> {audit_out}")
    if sentinel is not None:
        sentinel.disarm()
        rep = sentinel.report()
        print(f"sentinel: ok={rep['ok']} "
              f"syncs={len(rep['violations'])} "
              f"compiles={rep['compile_counts']} "
              f"rebuilds={len(rep['rebuilds'])}")
        sent_out = getattr(args, "sentinel_out", None)
        if sent_out:
            with open(sent_out, "w") as f:
                json.dump(rep, f, indent=2)
            print(f"wrote sentinel report -> {sent_out}")
    return telemetry, eng, realized, time.monotonic() - t0


def iter_log_record(eng: Engine, it: int) -> Dict:
    """One greppable JSONL log line from the engine's last recorded
    iteration (``--log-every``): long-run debugging without a trace
    viewer."""
    st = eng.stats[-1]
    rec = dict(iter=it, t=round(float(st.t_wall), 6), phase=st.phase,
               n_active=int(st.n_active), tokens=int(st.tokens),
               ib_global=round(float(st.ib_global), 4),
               fp4_ranks=float(st.fp4_ranks),
               gate_open=float(st.gate_open),
               migration_s=float(st.migration_s),
               migration_hidden_s=float(st.migration_hidden_s),
               n_unroutable=int(st.n_unroutable))
    prof = eng.profiler
    if prof.enabled and getattr(prof, "last", None) is not None:
        rec["mfu"] = round(prof.mfu(), 6)
        rec["time_scale"] = round(prof.time_scale(), 4)
        rec["phase_s"] = {ph: round(v, 6)
                          for ph, v in prof.phase_seconds().items()}
    return rec


def summarize_run(telemetry: Telemetry, eng: Engine, wall: float) -> Dict:
    """Flat per-arm summary (table / JSON-artifact friendly)."""
    done = eng.scheduler.finished
    out_toks = sum(len(r.generated) for r in done)
    in_toks = sum(r.prompt_len for r in done)
    s = telemetry.summary()
    s["n_requests_served"] = len(done)
    s["prompt_tokens"] = in_toks
    s["generated_tokens"] = out_toks
    s["throughput_tok_per_s"] = (in_toks + out_toks) / max(wall, 1e-9)
    s["wall_s"] = wall
    # engine-side cumulative accounting covers tail drains (e.g. the
    # post-loop drain_migrations()) that never reached a recorded
    # iteration — telemetry only sees IterStats, so its totals would
    # under-count async arms and disagree with migration_bytes_per_layer
    s["migration_bytes_total"] = int(eng.migration_bytes_moved)
    s["migration_stall_s"] = eng.migration_stall_s
    s["migration_s_total"] = eng.migration_stall_s
    s["migration_hidden_s"] = eng.migration_hidden_s
    mgr = eng._placement
    if mgr is not None:
        # per-layer migration traffic: [n_tables] cumulative bytes, so
        # the CI perf trajectory captures WHERE the migration cost lands
        # (changed layers only under layer-diff plans); byte counts are
        # integral end-to-end
        s["n_tables"] = int(getattr(mgr, "n_tables", 1))
        # disambiguated counters: telemetry's n_migrations counts
        # ITERATIONS that carried migration traffic (chunk batches under
        # async drain), the manager's counts COMMITTED PLANS.  The legacy
        # "n_migrations" key keeps its historical manager-side meaning.
        s["n_migrations"] = int(mgr.n_migrations)
        s["n_plans_committed"] = int(mgr.n_migrations)
        s["n_migration_iters"] = int(telemetry.n_migrations)
        if getattr(mgr, "audit", None) is not None:
            s["replan_decisions"] = mgr.audit.counts()
        s["migration_bytes_per_layer"] = [
            int(b) for b in getattr(mgr, "migrated_bytes_per_layer", [])]
        s["migration_bw_measured"] = float(mgr.bandwidth) \
            if mgr.bandwidth.calibrated else None
    return s


def windowed_tok_per_s(eng: Engine, t0: float) -> Optional[float]:
    """Throughput over the recorded iterations strictly after engine
    time ``t0`` — the post-recovery window when ``t0`` is the recovery
    stamp (both arms share the clock model, so the same window is
    comparable across the healthy and faulted runs)."""
    stats = [s for s in eng.stats if s.t_wall > t0]
    if len(stats) < 2:
        return None
    return sum(s.tokens for s in stats) / max(stats[-1].t_wall - t0, 1e-9)


def write_json_out(args, results: Dict[str, Dict]) -> None:
    payload = {
        "meta": dict(workload=args.workload, arrivals=args.arrivals,
                     arch=args.arch, preset=args.preset,
                     requests=args.requests, rate=args.rate,
                     seed=args.seed, slots=args.slots,
                     prefill_budget=args.prefill_budget,
                     gate_gamma=args.gate_gamma, planner=args.planner,
                     replan_every=args.replan_every,
                     virtual_ep=args.virtual_ep or 4,
                     spare_per_rank=args.spare_per_rank,
                     max_replicas=args.max_replicas,
                     per_layer=args.per_layer,
                     migrate_async=args.migrate_async,
                     migrate_bytes_per_iter=args.migrate_bytes_per_iter,
                     decode_replan_every=args.decode_replan_every,
                     replica_capacity_margin=args.replica_capacity_margin,
                     cost_gate=args.cost_gate,
                     cost_gate_calibrated=args.cost_gate_calibrated,
                     scenario=args.scenario, fail_iter=args.fail_iter,
                     rejoin_iter=args.rejoin_iter,
                     fail_rank=args.fail_rank,
                     replay=args.replay),
        "arms": results,
    }
    with open(args.json_out, "w") as f:
        json.dump(payload, f, indent=2, default=float)
    print(f"wrote {len(results)} arm summar"
          f"{'ies' if len(results) != 1 else 'y'} -> {args.json_out}")


def print_comparison(results: Dict[str, Dict]) -> None:
    def q(d, k, sub, default=float("nan")):
        v = d.get(k, {})
        return v.get(sub, default) if isinstance(v, dict) else default

    print(f"\n{'arm':18s} {'tok/s':>8s} {'ttft p50':>9s} {'ttft p99':>9s} "
          f"{'tpot p50':>9s} {'IB mean':>8s} {'IB p99':>7s} {'fp4':>5s} "
          f"{'split':>6s} {'mig MB':>7s} {'stall ms':>9s} {'hidden ms':>9s}")
    for name, s in results.items():
        print(f"{name:18s} {s['throughput_tok_per_s']:8.0f} "
              f"{q(s, 'ttft', 'p50'):9.4f} {q(s, 'ttft', 'p99'):9.4f} "
              f"{q(s, 'tpot', 'p50'):9.4f} "
              f"{q(s, 'ib_global', 'mean'):8.3f} "
              f"{q(s, 'ib_global', 'p99'):7.3f} "
              f"{s['fp4_duty']:5.2f} {s['split_duty']:6.2f} "
              f"{s['migration_bytes_total'] / 1e6:7.2f} "
              f"{s['migration_stall_s'] * 1e3:9.2f} "
              f"{s['migration_hidden_s'] * 1e3:9.2f}")


def main(argv=None) -> int:
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    args = parse_args(argv)
    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.preset == "tiny":
        cfg = reduced(cfg)
    prof = profile(args.workload)
    max_prompt = args.max_len - prof.max_new_max - 1

    if args.replay:
        meta, specs = load_stream(args.replay)
        args.requests = len(specs)
        if args.arrivals == "closed":
            args.arrivals = meta.get("arrivals", "poisson")
        print(f"replaying {len(specs)} requests from {args.replay} "
              f"(meta: {meta})")
    else:
        specs = build_stream(args, cfg.vocab_size, max_prompt)

    params = tf.init_model(cfg, jax.random.PRNGKey(args.seed))

    if args.scenario == "kill-rejoin":
        if args.arm == "all":
            raise SystemExit("--scenario kill-rejoin takes one replicate "
                             "arm, not 'all'")
        if args.arm is None:
            args.arm = "replicate/L/async"
        if ARMS[args.arm][1] != "replication":
            raise SystemExit("--scenario kill-rejoin needs a replicate "
                             f"arm; got arm={args.arm!r}")
        if args.migrate_bytes_per_iter == 0:
            # small per-iteration chunk budget so recovery visibly
            # streams across iterations (one layer slab per drain batch)
            # instead of landing whole inside the kill iteration
            args.migrate_bytes_per_iter = 4096
        resolve_arm(args)        # pin meta before the per-run copies
        print(f"kill-rejoin scenario: arm={args.arm} "
              f"fail_rank={args.fail_rank} fail_iter={args.fail_iter} "
              f"rejoin_iter={args.rejoin_iter} "
              f"budget={args.migrate_bytes_per_iter}B/iter")
        print(f"stream: {stream_stats(specs)}")
        results: Dict[str, Dict] = {}
        healthy_args = argparse.Namespace(**vars(args))
        # the trace/audit artifacts cover the faulted run (the one with
        # elastic events worth inspecting), not the healthy baseline
        healthy_args.trace_out = healthy_args.audit_out = None
        telemetry, eng, _, wall = serve(healthy_args, cfg, params, specs)
        results["healthy"] = summarize_run(telemetry, eng, wall)
        telemetry2, eng2, _, wall2 = serve(
            argparse.Namespace(**vars(args)), cfg, params, specs,
            inject_faults=True)
        s2 = summarize_run(telemetry2, eng2, wall2)
        co = eng2._elastic
        s2["elastic_events"] = [dict(e) for e in co.events]
        rec = [e for e in co.events if e["kind"] == "recovered"]
        t_rec = rec[-1]["t"] if rec else None
        if t_rec is not None:
            s2["post_recovery_tok_per_s"] = windowed_tok_per_s(eng2, t_rec)
            results["healthy"]["post_recovery_tok_per_s"] = \
                windowed_tok_per_s(eng, t_rec)
        results["kill-rejoin"] = s2
        print_comparison(results)
        print(f"\nelastic: recovery_s={s2.get('recovery_s')} "
              f"availability={s2.get('availability', 1.0):.4f} "
              f"degraded_iters={s2.get('degraded_iters')} "
              f"lost_tokens={s2.get('lost_tokens_total', 0.0):.0f} "
              f"events={[e['kind'] for e in co.events]}")
        healthy_post = results["healthy"].get("post_recovery_tok_per_s")
        if s2.get("post_recovery_tok_per_s") and healthy_post:
            print(f"post-recovery throughput: "
                  f"{s2['post_recovery_tok_per_s']:.0f} tok/s vs healthy "
                  f"{healthy_post:.0f} tok/s "
                  f"({s2['post_recovery_tok_per_s'] / healthy_post:.3f}x)")
        if args.json_out:
            write_json_out(args, results)
        if args.json:
            print(json.dumps(results, default=float))
        return 0

    if args.arm == "all":
        # every arm head-to-head on the same realized stream, one
        # deterministic invocation (shared logical params, fresh engine
        # state per arm; migration gathers never mutate the shared tree)
        if args.virtual_ep is None:
            args.virtual_ep = 4
        print(f"comparing {len(ARMS)} arms: workload={args.workload} "
              f"arrivals={args.arrivals} arch={cfg.name} "
              f"requests={len(specs)} virtual_ep={args.virtual_ep}")
        print(f"stream: {stream_stats(specs)}")
        results: Dict[str, Dict] = {}
        realized = specs
        for name in ARMS:
            sub = argparse.Namespace(**vars(args))
            # per-layer / async are the arm's own properties here: a
            # sticky --per-layer or --migrate-async would silently turn
            # the baseline arms into mislabeled duplicates of the /L and
            # /async arms
            sub.arm, sub.record = name, None
            sub.per_layer, sub.migrate_async = False, False
            telemetry, eng, realized, wall = serve(sub, cfg, params, specs)
            results[name] = summarize_run(telemetry, eng, wall)
            print(f"  {name}: {results[name]['n_requests_served']} served, "
                  f"{results[name]['throughput_tok_per_s']:.0f} tok/s, "
                  f"{wall:.1f}s wall")
        if args.record:
            save_stream(args.record, realized,
                        meta=dict(workload=args.workload,
                                  arrivals=args.arrivals, seed=args.seed,
                                  policy="all"))
            print(f"recorded {len(realized)} requests -> {args.record}")
        print_comparison(results)
        if args.json_out:
            write_json_out(args, results)
        if args.json:
            print(json.dumps(results, default=float))
        return 0

    resolve_arm(args)     # idempotent; serve() resolves again
    print(f"workload={args.workload} arrivals={args.arrivals} "
          f"policy={args.policy} arch={cfg.name} "
          f"slots={args.slots} budget={args.prefill_budget} "
          f"gate_gamma={args.gate_gamma}"
          + (f" arm={args.arm} planner={args.planner} "
             f"replan_every={args.replan_every} "
             f"virtual_ep={args.virtual_ep}" if args.arm else ""))
    print(f"stream: {stream_stats(specs)}")

    telemetry, eng, realized, wall = serve(args, cfg, params, specs)

    if args.record:
        save_stream(args.record, realized,
                    meta=dict(workload=args.workload,
                              arrivals=args.arrivals, seed=args.seed,
                              policy=args.policy))
        print(f"recorded {len(realized)} requests -> {args.record}")

    s = summarize_run(telemetry, eng, wall)
    if args.json_out:
        write_json_out(args, {args.arm or args.policy: s})
    if args.json:
        print(json.dumps(s, default=float))
        return 0

    def fmt(d):
        return " ".join(f"{k}={v:.4f}" for k, v in d.items()) or "(none)"

    print(f"served {s['n_requests_served']} requests, "
          f"{s['prompt_tokens']} prompt + {s['generated_tokens']} "
          f"generated tokens in {wall:.1f}s wall "
          f"({s['throughput_tok_per_s']:.0f} tok/s), "
          f"{s['n_iters']} iterations")
    print(f"TTFT        {fmt(s['ttft'])}")
    print(f"TTFT vision {fmt(s['ttft_vision'])}")
    print(f"TTFT text   {fmt(s['ttft_text'])}")
    print(f"TPOT        {fmt(s['tpot'])}")
    print(f"IB_global   {fmt(s['ib_global'])}")
    print(f"drop_frac   {fmt(s['drop_frac'])}")
    print(f"gate duty: prefill={s['gate_duty_prefill']:.2f} "
          f"decode={s['gate_duty_decode']:.2f}; "
          f"fp4 duty: all={s['fp4_duty']:.2f} "
          f"prefill={s['fp4_duty_prefill']:.2f}; "
          f"split duty: {s['split_duty']:.2f}")
    print(f"migration: {s['n_migrations']} events, "
          f"{s['migration_bytes_total'] / 1e6:.2f} MB moved, "
          f"{s['migration_stall_s'] * 1e3:.2f} ms stalled, "
          f"{s['migration_hidden_s'] * 1e3:.2f} ms hidden")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
