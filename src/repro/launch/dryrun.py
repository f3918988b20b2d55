import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture × input-shape ×
mesh) cell against ShapeDtypeStruct inputs on the production meshes.

The two lines above MUST stay the first statements of this module — jax
locks the device count at first initialisation, and the dry-run (and only
the dry-run) needs 512 placeholder host devices.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch olmoe-1b-7b \
        --shape train_4k --mesh single_pod
    PYTHONPATH=src python -m repro.launch.dryrun --all   # subprocess/cell

Each cell writes a JSON record (memory analysis, cost analysis, collective
bytes, roofline terms) under experiments/dryrun/; --all skips cells whose
record already exists, so the sweep is resumable.
"""

import argparse
import json
import pathlib
import subprocess
import sys
import time
import traceback


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             realb_overrides=None) -> dict:
    import jax

    from repro.configs import (ReaLBConfig, get_config, get_shape,
                               shape_supported)
    from repro.launch import hlo_analysis, roofline
    from repro.launch.mesh import mesh_for
    from repro.launch.steps import lower_cell

    cfg = get_config(arch)
    shape = get_shape(shape_name)
    ok, why = shape_supported(cfg, shape)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "params": cfg.param_count(), "active_params":
           cfg.active_param_count()}
    if not ok:
        rec.update(status="skipped", reason=why)
        return rec

    mesh = mesh_for(mesh_kind)
    n_dev = mesh.devices.size
    rcfg = ReaLBConfig(**(realb_overrides or {}))
    t0 = time.perf_counter()
    lowered = lower_cell(cfg, shape, mesh, rcfg=rcfg)
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()

    mem = compiled.memory_analysis()
    print(mem)  # proves it fits
    cost = compiled.cost_analysis()
    print({k: v for k, v in cost.items()
           if k in ("flops", "bytes accessed")})

    hlo = compiled.as_text()
    # XLA:CPU cost_analysis does not scale while-loop bodies by trip count;
    # analyze the post-SPMD HLO ourselves (dots, fusion IO, collectives).
    an = hlo_analysis.analyze(hlo)
    flops_dev = float(an["flops"])
    bytes_dev = int(an["traffic_bytes"])
    coll_total = int(an["collective_bytes"])
    terms = roofline.roofline_terms(flops_dev, bytes_dev, coll_total)
    mf = roofline.model_flops(cfg, shape)
    hlo_total_flops = flops_dev * n_dev
    rec.update(
        status="ok",
        n_devices=n_dev,
        lower_s=round(t1 - t0, 2),
        compile_s=round(t2 - t1, 2),
        memory=dict(
            argument_bytes=int(getattr(mem, "argument_size_in_bytes", 0)),
            output_bytes=int(getattr(mem, "output_size_in_bytes", 0)),
            temp_bytes=int(getattr(mem, "temp_size_in_bytes", 0)),
            alias_bytes=int(getattr(mem, "alias_size_in_bytes", 0)),
        ),
        flops_per_device=flops_dev,
        bytes_per_device=bytes_dev,
        collective_bytes_per_device=int(coll_total),
        collective_by_kind={k: int(v) for k, v
                            in an["collective_by_kind"].items()},
        top_collectives=hlo_analysis.top_collectives(hlo, 8),
        top_traffic=hlo_analysis.top_traffic(hlo, 10),
        xla_cost_analysis={
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        },
        roofline=terms,
        model_flops_global=mf,
        hlo_flops_global=hlo_total_flops,
        useful_flop_ratio=(mf / hlo_total_flops) if hlo_total_flops else 0.0,
        hlo_bytes_chars=len(hlo),
    )
    return rec


def _out_path(outdir: pathlib.Path, arch, shape, mesh, tag="") -> pathlib.Path:
    t = f".{tag}" if tag else ""
    return outdir / f"{arch}__{shape}__{mesh}{t}.json"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single_pod",
                    choices=["single_pod", "multi_pod"])
    ap.add_argument("--all", action="store_true",
                    help="sweep all cells × both meshes, one subprocess each")
    ap.add_argument("--meshes", default="single_pod,multi_pod")
    ap.add_argument("--tag", default="", help="record suffix (perf variants)")
    ap.add_argument("--realb", default="",
                    help="comma k=v ReaLB overrides, e.g. gate_gamma=1024")
    ap.add_argument("--outdir", default="experiments/dryrun")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    outdir = pathlib.Path(args.outdir)
    outdir.mkdir(parents=True, exist_ok=True)

    if args.all:
        from repro.configs import all_cells
        cells = all_cells()
        rc = 0
        for arch, shape, ok, why in cells:
            for mesh in args.meshes.split(","):
                path = _out_path(outdir, arch, shape, mesh, args.tag)
                if path.exists() and not args.force:
                    continue
                if not ok:
                    path.write_text(json.dumps(
                        {"arch": arch, "shape": shape, "mesh": mesh,
                         "status": "skipped", "reason": why}, indent=1))
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape, "--mesh", mesh,
                       "--outdir", str(outdir)]
                if args.tag:
                    cmd += ["--tag", args.tag]
                if args.realb:
                    cmd += ["--realb", args.realb]
                print(f"=== {arch} × {shape} × {mesh} ===", flush=True)
                r = subprocess.run(cmd)
                if r.returncode != 0:
                    rc = 1
        return rc

    overrides = {}
    for kv in filter(None, args.realb.split(",")):
        k, v = kv.split("=")
        overrides[k] = {"True": True, "False": False}.get(v) or (
            float(v) if "." in v else int(v))
    try:
        rec = run_cell(args.arch, args.shape, args.mesh, overrides)
    except Exception as e:  # record the failure for the sweep report
        rec = {"arch": args.arch, "shape": args.shape, "mesh": args.mesh,
               "status": "error", "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-4000:]}
        print(rec["error"], file=sys.stderr)
    path = _out_path(pathlib.Path(args.outdir), args.arch, args.shape,
                     args.mesh, args.tag)
    path.write_text(json.dumps(rec, indent=1))
    print(json.dumps({k: v for k, v in rec.items()
                      if k not in ("traceback",)}, indent=1))
    return 0 if rec.get("status") in ("ok", "skipped") else 1


if __name__ == "__main__":
    sys.exit(main())
