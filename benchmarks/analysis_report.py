"""Hot-loop invariant report: lint + jaxpr audit + collective-census
reconciliation + runtime sentinel, one JSON artifact, non-zero exit on
any violation.

    PYTHONPATH=src python benchmarks/analysis_report.py \
        --out invariant_report.json

Sections (``--only`` filters, comma-separated):

* **lint** — ``repro.analysis.lint`` over ``src/repro``: zero
  unsuppressed RPL findings.
* **audit** — trace the single-host FP4-active MoE step under both
  expert-FFN backends (``jnp`` and the Pallas ``interpret`` kernel) and
  walk the jaxpr: no host callbacks, no f64, every float widening on
  the dispatch/expert path allowlisted, zero collectives on the local
  path.
* **census** — the dispatch path on the (2,4) mesh: the traced jaxpr
  census, the post-XLA HLO census and the FlopByteLedger graph
  prediction must reconcile (jaxpr == ledger exactly; HLO user-slice
  all-to-all exact, all-reduce within the loop-hoisting tolerance).
* **sentinel** — a two-pass serve on the FP4-active profiled arm
  (realb+placement, Γ=8, m_d=0, AIMD off, interpret kernels, tracer and
  profiler live): pass 1 warms every jit entry, an identical pass 2
  must hit the caches exactly — zero recompiles, zero unsanctioned
  device→host syncs.

``--tamper sync`` injects a ``float()`` host pull into the decode hot
window and ``--tamper psum`` an extra collective into the census
harness; both must flip the exit code (pinned by
``tests/test_analysis_report.py``).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

# the census section needs the 8-device fake CPU topology, which must be
# pinned before jax initializes
if "--xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "src"))
sys.path.insert(0, _ROOT)

SECTIONS = ("lint", "audit", "census", "sentinel")


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="write the JSON invariant report here")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of sections "
                         f"({', '.join(SECTIONS)})")
    ap.add_argument("--tamper", default=None, choices=["sync", "psum"],
                    help="deliberately break an invariant (CI pins that "
                         "the report catches it): 'sync' = host pull in "
                         "the decode hot window, 'psum' = extra "
                         "collective in the census harness")
    ap.add_argument("--requests", type=int, default=6,
                    help="requests per sentinel serving pass")
    return ap.parse_args(argv)


def _section(fn):
    """Run one section; any exception becomes a failing entry."""
    try:
        out = fn()
        out.setdefault("ok", False)
        return out
    except Exception as e:                       # noqa: BLE001
        return {"ok": False, "error": f"{type(e).__name__}: {e}"}


def run_lint() -> dict:
    from repro.analysis.lint import lint_paths, summarize
    s = summarize(lint_paths([os.path.join(_ROOT, "src", "repro")]))
    s["ok"] = s.pop("files_ok")
    # the per-finding dicts stay; CI surfaces them in the artifact
    return s


def run_audit() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.analysis.jaxpr_audit import audit_jaxpr
    from repro.configs import ReaLBConfig, get_config, reduced
    from repro.core import ep_moe
    from repro.kernels import ops as kops

    cfg = reduced(get_config("olmoe-1b-7b"))
    e = cfg.moe
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    D, E, F = cfg.d_model, e.num_experts, e.d_ff
    p = {"router": jax.random.normal(ks[0], (D, E)) * 0.2,
         "w_gate": jax.random.normal(ks[1], (E, D, F)) / np.sqrt(D),
         "w_up": jax.random.normal(ks[2], (E, D, F)) / np.sqrt(D),
         "w_down": jax.random.normal(ks[3], (E, F, D)) / np.sqrt(F)}
    x = jax.random.normal(ks[4], (2, 16, D)) * 0.5
    mod = jax.random.bernoulli(ks[5], 0.6, (2, 16))
    rcfg = ReaLBConfig(gate_gamma=1e-6)          # policy ON: FP4 live
    m = jnp.full((1, 1), 0.9)

    backends = {}
    ok = True
    for backend in ("jnp", "interpret"):
        kops.set_ffn_backend(backend)
        closed = jax.make_jaxpr(
            lambda p_, x_, m_: ep_moe.ep_moe_forward(
                p_, x_, cfg, rcfg, m_, mod, mode="dispatch"))(p, x, m)
        rep = audit_jaxpr(closed)
        b_ok = rep.ok and not rep.census
        ok = ok and b_ok
        backends[backend] = {
            "ok": b_ok, "n_eqns": rep.n_eqns,
            "n_widenings": len(rep.widenings),
            "violations": [v.format() for v in rep.violations],
            "census": rep.census,      # local path: must be empty
        }
    kops.set_ffn_backend("auto")
    return {"ok": ok, "backends": backends}


def run_census(tamper: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.analysis.jaxpr_audit import collective_census_jaxpr
    from repro.configs import ReaLBConfig, get_config, reduced
    from repro.core import ep_moe
    from repro.launch.hlo_analysis import collective_census
    from repro.launch.mesh import make_mesh
    from repro.models.common import use_mesh
    from repro.obs.ledger import FlopByteLedger

    cfg = reduced(get_config("olmoe-1b-7b"))
    e = cfg.moe
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    D, E, F = cfg.d_model, e.num_experts, e.d_ff
    p = {"router": jax.random.normal(ks[0], (D, E)) * 0.2,
         "w_gate": jax.random.normal(ks[1], (E, D, F)) / np.sqrt(D),
         "w_up": jax.random.normal(ks[2], (E, D, F)) / np.sqrt(D),
         "w_down": jax.random.normal(ks[3], (E, F, D)) / np.sqrt(F)}
    x = jax.random.normal(ks[4], (4, 16, D)) * 0.5
    mod = jax.random.bernoulli(ks[5], 0.6, (4, 16))
    rcfg = ReaLBConfig(gate_gamma=10 ** 9)
    L = 3
    mesh = make_mesh((2, 4), ("data", "model"))
    P = jax.sharding.PartitionSpec

    def fwd(p, x, m):
        def step(carry, _):
            x_c, m_c = carry
            y, m_n, aux = ep_moe.ep_moe_forward(p, x_c, cfg, rcfg, m_c,
                                                mod, mode="dispatch")
            if tamper:      # one extra collective per layer
                extra = jax.shard_map(lambda a: jax.lax.psum(a, "model"),
                                      mesh=mesh, in_specs=P(), out_specs=P(),
                                      check_vma=False)(aux["drop_frac"])
                y = y + extra * 0.0
            return (y, m_n), aux
        return jax.lax.scan(step, (x, m), None, length=L)

    with use_mesh(mesh):
        m = jnp.full(ep_moe.moe_state_shape(mesh, 4), 0.9)
        closed = jax.make_jaxpr(fwd)(p, x, m)
        hlo = jax.jit(fwd).lower(p, x, m).compile().as_text()

    jx = collective_census_jaxpr(closed)
    led = FlopByteLedger(cfg, ep=4).predict_graph_census(
        t_local=8, layers=L, itemsize=x.dtype.itemsize)
    hl = collective_census(hlo)
    a2a = hl["user"].get("all-to-all", {"count": 0, "bytes": 0})
    ar = hl["user"].get("all-reduce", {"count": 0, "bytes": 0})

    checks = {
        # jaxpr == ledger, exactly (same capacity formula, same shapes)
        "jaxpr_eq_ledger": all(jx.get(k) == led[k]
                               for k in ("all_to_all", "psum")),
        # HLO user slice: a2a exact; psum lowers to all-reduce, XLA may
        # merge and hoist loop-invariant scalars (count <=, bytes ~5%)
        "hlo_a2a_exact": a2a == led["all_to_all"],
        "hlo_ar_count": 0 < ar["count"] <= led["psum"]["count"],
        "hlo_ar_bytes_tol": abs(ar["bytes"] - led["psum"]["bytes"])
        / led["psum"]["bytes"] <= 0.05,
        "hlo_layers": hl["layers"] == L,
    }
    return {"ok": all(checks.values()), "checks": checks,
            "jaxpr": jx, "ledger": led,
            "hlo_user": hl["user"], "hlo_total": hl["total"]}


def run_sentinel(n_requests: int, tamper: bool) -> dict:
    import jax

    from repro.analysis.sentinel import Sentinel
    from repro.configs import (PlacementConfig, ReaLBConfig, get_config,
                               reduced)
    from repro.kernels import ops as kops
    from repro.models import transformer as tf
    from repro.obs import FlopByteLedger, Profiler, Tracer
    from repro.placement import PlacementManager
    from repro.serving.engine import Engine
    from repro.serving.telemetry import Telemetry
    from repro.workloads import (ArrivalConfig, IterationCostModel,
                                 VirtualClock, arrival_times, make_stream,
                                 profile)

    # the profiled CI arm: realb+placement, deterministic FP4 duty
    cfg = reduced(get_config("moonshot-v1-16b-a3b"))
    kops.set_ffn_backend("interpret")
    rcfg = ReaLBConfig(gate_gamma=8, md_init=0.0, adaptive=False)
    prof = profile("MMMU")
    max_len = 256
    specs = make_stream(
        prof, arrival_times(ArrivalConfig(kind="bursty", rate=12.0,
                                          n_requests=n_requests, seed=0)),
        cfg.vocab_size, seed=1, max_prompt=max_len - prof.max_new_max - 1)
    params = tf.init_model(cfg, jax.random.PRNGKey(0))
    manager = PlacementManager(
        cfg, PlacementConfig(planner="least_loaded", replan_every=8), ep=4)
    telemetry = Telemetry()
    clock = VirtualClock()
    sent = Sentinel()
    sent.arm()
    try:
        eng = Engine(cfg, params, rcfg, max_slots=4, max_len=max_len,
                     prefill_budget=128, clock=clock, telemetry=telemetry,
                     cost_model=IterationCostModel(), placement=manager,
                     virtual_ep=4, tracer=Tracer(clock=clock),
                     profiler=Profiler(FlopByteLedger(
                         cfg, ep=4, fused=kops.ffn_fused()),
                         registry=telemetry.registry),
                     sentinel=sent)
        if tamper:
            orig = eng._decode

            def tampered(*a, **kw):
                out = orig(*a, **kw)
                float(out[0].sum())      # host pull inside the hot window
                return out

            eng._decode = tampered

        def one_pass():
            for spec in specs:
                eng.submit(spec.to_request(d_model=cfg.d_model))
            eng.run()
            eng.drain_migrations()

        one_pass()                       # warmup: every entry compiles
        warm = sent.mark_warm()
        one_pass()                       # identical stream: caches only
    finally:
        sent.disarm()
        kops.set_ffn_backend("auto")
    rep = sent.report()
    rep["warm_counts"] = warm
    rep["n_requests_per_pass"] = n_requests
    return rep


def main(argv=None) -> int:
    args = parse_args(argv)
    only = set((args.only or ",".join(SECTIONS)).split(","))
    unknown = only - set(SECTIONS)
    if unknown:
        raise SystemExit(f"unknown section(s): {sorted(unknown)}")

    report = {"schema": "repro.analysis.v1",
              "tamper": args.tamper, "sections": {}}
    if "lint" in only:
        report["sections"]["lint"] = _section(run_lint)
    if "audit" in only:
        report["sections"]["audit"] = _section(run_audit)
    if "census" in only:
        report["sections"]["census"] = _section(
            lambda: run_census(tamper=args.tamper == "psum"))
    if "sentinel" in only:
        report["sections"]["sentinel"] = _section(
            lambda: run_sentinel(args.requests,
                                 tamper=args.tamper == "sync"))
    report["ok"] = all(s["ok"] for s in report["sections"].values())

    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2, default=str)
        print(f"wrote invariant report -> {args.out}")
    for name, s in report["sections"].items():
        detail = s.get("error", "")
        print(f"  {name}: {'ok' if s['ok'] else 'VIOLATION'}"
              + (f" ({detail})" if detail else ""))
    print(f"invariants: {'ok' if report['ok'] else 'VIOLATED'}")
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
