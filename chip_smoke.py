"""Smoke run of the ReaLB serving path on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # expert parallelism over four chips

One chip: serve 8 seeded MMMU requests through ``Engine`` (ReaLB live,
wall clocks, ``virtual_ep=4``), check that the compiled chunk step holds
the Pallas kernels, that FP4 fired on a prefill iteration, that every
logit is finite and every request finished, then run the grouped FP4 FFN
kernel against its jnp oracle on the device.

Four chips, on a ``(1, 4)`` ``("data", "model")`` mesh with the default
sharding rules (EP=4, 16 experts per chip): compare the first step's
logits with the gate held shut against the same parameters and tokens on
``devices[0]`` (1 dense + 1 MoE layer, f32), then serve the same requests
with the gate open and check that FP4 fires.

The model is the repo's Moonlight-width approximation of
moonshot-v1-16b-a3b: the published widths, random bf16 parameters made
from a seed on the device, depth cut to 1 dense + 4 MoE layers.  Both
serves start ReaLB's modality threshold open (``md_init=0``) so that FP4
fires on the first gated prefill iteration.

Earlier lines report each phase; the last line of stdout is one JSON
object, ``{"ok": true, "device": {...}}``.  The script exits non-zero and
prints no such line when JAX sees no TPU or any check fails.  It runs
every phase in this one process and starts no other.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
N_REQUESTS = 8
MAX_LEN = 512           # MMMU prompts (<= 384 tokens) + up to 32 new tokens
# prompt tokens per prefill iteration: above the LB gate's Γ = 2048 routed
# assignments, so a full chunk opens the gate
PREFILL_BUDGET = 4096
SEED = 0
# first-step logits, EP=4 mesh vs one device, gate shut, f32 parameters
# and matmuls: the two programs then differ only in the order of f32 sums
LOGIT_REL_L2 = 1e-3
LOGIT_PEAK = 1e-2
# grouped FP4 FFN kernel vs the jnp oracle: bitwise equal on a v5e; a few
# f32 ulps of h (a different XLA sigmoid or sum order) move single FP4
# levels, which moves y by about 1e-4 rel L2 and 1e-2 of its peak
KERNEL_REL_L2 = 1e-3
KERNEL_PEAK = 2e-2


class CheckFailed(Exception):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


def say(*parts) -> None:
    print(*parts, flush=True)


def memory(phase: str, devices) -> None:
    """Device memory in use after ``phase``, and the peak so far."""
    stats = [d.memory_stats() or {} for d in devices]
    say(f"[memory] after {phase}: bytes_in_use "
        f"{[m.get('bytes_in_use') for m in stats]}, peak_bytes_in_use "
        f"{[m.get('peak_bytes_in_use') for m in stats]}")


class CompileClock:
    """Seconds of XLA backend compilation (persistent-cache reads
    included), and the persistent-cache hits and misses."""

    def __init__(self):
        import jax
        self.seconds, self.hits, self.misses = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration_secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def smoke_config():
    """moonshot-v1-16b-a3b at its published widths, 1 dense + 4 MoE layers."""
    from repro.configs import get_config
    return dataclasses.replace(get_config("moonshot-v1-16b-a3b"), n_layers=5)


def requests(cfg):
    """N_REQUESTS seeded MMMU requests, all arriving at once."""
    from repro.workloads import make_stream, profile
    specs = make_stream(profile("MMMU"), np.zeros(N_REQUESTS),
                        cfg.vocab_size, seed=SEED)
    out = []
    for spec in specs:
        req = spec.to_request()
        req.arrival_time = None        # stamped by the engine's wall clock
        out.append(req)
    return out


def init_params(cfg, shardings=None):
    """Parameters made from ``SEED`` on the device (sharded if given)."""
    import jax
    from repro.models import transformer as tf
    init = jax.jit(tf.init_model, static_argnums=0, out_shardings=shardings)
    return jax.block_until_ready(init(cfg, jax.random.PRNGKey(SEED)))


class StepWatch:
    """Wraps an engine's jitted chunk and decode steps: keeps the first
    chunk call (arguments and logits) and whether every logit was finite."""

    def __init__(self, eng):
        import jax.numpy as jnp
        self._jnp = jnp
        self.chunk_fn = eng._chunk
        self.first_chunk = None
        self._finite = []
        eng._chunk = self._wrap(eng._chunk, chunk=True)
        eng._decode = self._wrap(eng._decode, chunk=False)

    def _wrap(self, fn, chunk: bool):
        def call(*args):
            out = fn(*args)
            self._finite.append(self._jnp.isfinite(out[0]).all())
            if chunk and self.first_chunk is None:
                self.first_chunk = (args, out[0])
            return out
        return call

    def all_finite(self) -> bool:
        return bool(all(bool(f) for f in self._finite))

    def first_logits(self):
        """First chunk's logits of the rows that held prompt tokens."""
        args, logits = self.first_chunk
        rows = np.asarray(args[5]) > 0            # chunk_len per row
        return np.asarray(logits, np.float32)[rows]


def make_engine(cfg, params, rcfg, mesh_ep: bool):
    from repro.serving.engine import Engine
    return Engine(cfg, params, rcfg, max_len=MAX_LEN,
                  prefill_budget=PREFILL_BUDGET,
                  virtual_ep=None if mesh_ep else 4)


def serve(eng, reqs, clock: CompileClock):
    """Serve ``reqs`` to completion; returns (finished, wall s, compile s)."""
    for req in reqs:
        eng.submit(req)
    c0, t0 = clock.seconds, time.perf_counter()
    done = eng.run()
    wall = time.perf_counter() - t0
    return done, wall, clock.seconds - c0


def report_serve(tag, eng, reqs, done, wall, compile_s, watch):
    pre = [s for s in eng.stats if s.phase == "prefill"]
    dec = [s for s in eng.stats if s.phase == "decode"]
    fp4 = [s for s in pre if s.fp4_ranks > 0]
    say(f"[{tag}] requests completed: {len(done)}/{len(reqs)}; tokens "
        f"generated: {sum(len(r.generated) for r in done)}; prompt tokens: "
        f"{sum(r.prompt_len for r in done)}")
    say(f"[{tag}] iterations: prefill {len(pre)}, decode {len(dec)}; "
        f"iterations with fp4_ranks > 0: prefill {len(fp4)}, decode "
        f"{sum(1 for s in dec if s.fp4_ranks > 0)}; max drop_frac "
        f"{max((s.drop_frac for s in eng.stats), default=0.0)}")
    say(f"[{tag}] wall seconds {wall}: compile {compile_s}, serve "
        f"{wall - compile_s}")
    check(len(done) == len(reqs) and all(r.done for r in done),
          f"{tag}: a request was left unfinished")
    check(watch.all_finite(), f"{tag}: non-finite logits")
    check(fp4, f"{tag}: FP4 never fired on a prefill iteration")


def kernel_parity(g=64, d=2048, f=1408, m=4096):
    """Grouped FP4 FFN kernel vs the jnp oracle at the given widths, on
    ragged groups with empty slots.  Returns the output's rel L2 and peak
    relative error, and the share of packed bytes in which the quantize
    kernel differs from the oracle's quantize."""
    import jax
    import jax.numpy as jnp
    from repro.configs import ReaLBConfig
    from repro.core import ep_moe, quant
    from repro.kernels import ops as kops

    rng = np.random.default_rng(SEED)
    counts = rng.multinomial(m, rng.dirichlet(np.full(g, 0.3)))
    counts[::5] = 0                              # empty slots
    counts[-1] += m - counts.sum()
    gs = jnp.asarray(counts, jnp.int32)
    keys = jax.random.split(jax.random.PRNGKey(SEED), 4)
    shapes = dict(w_gate=(g, d, f), w_up=(g, d, f), w_down=(g, f, d))

    @functools.partial(jax.jit, static_argnums=1)
    def weights(key, shape):
        return (jax.random.normal(key, shape, jnp.float32)
                / np.sqrt(shape[1])).astype(jnp.bfloat16)

    @jax.jit
    def differing_codes(w, q):
        ref = quant.quantize_fp4(w, global_scale=q.global_scale)
        return jnp.mean((ref.packed != q.packed).astype(jnp.float32))

    wq, code_mismatch = {}, 0.0
    for key, (name, shape) in zip(keys, shapes.items()):
        w = weights(key, shape)
        wq[name] = kops.quantize_experts_fp4(w)
        code_mismatch = max(code_mismatch,
                            float(differing_codes(w, wq[name])))
    xs = jax.random.normal(keys[3], (m, d), jnp.float32).astype(jnp.bfloat16)
    y = kops.grouped_fp4_ffn(xs, gs, wq)
    prev = kops.ffn_backend()
    kops.set_ffn_backend("jnp")
    try:
        y_ref = jax.jit(lambda *a: ep_moe._grouped_ffn_fp4(
            *a, ReaLBConfig(), jax.nn.silu))(xs, gs, wq)
    finally:
        kops.set_ffn_backend(None if prev == "pallas" else prev)
    ya, ra = np.asarray(y, np.float32), np.asarray(y_ref, np.float32)
    rel_l2 = float(np.linalg.norm(ya - ra) / max(np.linalg.norm(ra), 1e-30))
    peak = float(np.abs(ya - ra).max() / max(np.abs(ra).max(), 1e-30))
    say(f"[kernel] grouped FP4 FFN vs jnp oracle, G={g} D={d} F={f} M={m}, "
        f"{int((counts == 0).sum())} empty slots: oracle max |y| "
        f"{float(np.abs(ra).max())}, max abs err "
        f"{float(np.abs(ya - ra).max())}, rel L2 {rel_l2}, peak rel "
        f"{peak}; packed codes differing from the oracle's quantize: "
        f"{code_mismatch}")
    check(np.isfinite(ya).all() and np.isfinite(ra).all()
          and np.abs(ra).max() > 0, "kernel parity: zero or non-finite output")
    return rel_l2, peak, code_mismatch


def one_chip(cfg, clock: CompileClock) -> None:
    """Serve through the engine on one chip, then the kernel parity."""
    import jax
    from repro.configs import ReaLBConfig
    from repro.kernels import ops as kops

    backend = kops.ffn_backend()
    say(f"[serve] FFN backend: {backend}")
    check(backend == "pallas", f"FFN backend is {backend!r}, not 'pallas'")
    c0, t0 = clock.seconds, time.perf_counter()
    params = init_params(cfg)
    say(f"[serve] parameters made on the device in "
        f"{time.perf_counter() - t0} s (compile {clock.seconds - c0} s)")
    memory("parameter init", jax.devices()[:1])
    # md_init=0: the modality threshold starts open, so the first gated
    # prefill iteration compresses its hot virtual ranks
    rcfg = ReaLBConfig(md_init=0.0)
    eng = make_engine(cfg, params, rcfg, mesh_ep=False)
    watch = StepWatch(eng)
    reqs = requests(cfg)
    done, wall, compile_s = serve(eng, reqs, clock)
    report_serve("serve", eng, reqs, done, wall, compile_s, watch)
    memory("serve", jax.devices()[:1])

    args, _ = watch.first_chunk
    hlo = watch.chunk_fn.lower(*args).compile().as_text()
    n_kernel = hlo.count("tpu_custom_call")
    say(f"[serve] compiled chunk step: {n_kernel} tpu_custom_call ops")
    check(n_kernel > 0, "no Pallas kernel (tpu_custom_call) in the chunk step")
    memory("chunk step AOT compile", jax.devices()[:1])

    rel_l2, peak, code_mismatch = kernel_parity(
        g=cfg.moe.num_experts, d=cfg.d_model, f=cfg.moe.d_ff)
    memory("kernel parity", jax.devices()[:1])
    check(rel_l2 < KERNEL_REL_L2 and peak < KERNEL_PEAK,
          f"kernel parity: rel L2 {rel_l2}, peak {peak}")
    check(code_mismatch < 1e-2,
          f"quantize kernel differs from the oracle on {code_mismatch} of "
          "the packed bytes")


def parity_config(cfg):
    """The served config cut to 1 dense + 1 MoE layer, f32 parameters, and
    capacity for every assignment (no dispatch drop on either side)."""
    return dataclasses.replace(
        cfg, n_layers=cfg.n_dense_layers + 1, param_dtype="float32",
        moe=dataclasses.replace(cfg.moe, capacity_factor=4.0))


def first_step_logits(cfg, mesh=None):
    """First chunk's logits with the LB gate held shut, from an engine on
    ``devices[0]`` (``mesh=None``) or over ``mesh``; f32 matmuls."""
    import gc

    import jax
    from repro.configs import ReaLBConfig
    from repro.models import transformer as tf
    from repro.models.common import use_mesh

    shut = ReaLBConfig(gate_gamma=2 ** 30)
    with use_mesh(mesh), jax.default_matmul_precision("highest"):
        shardings = None if mesh is None else jax.tree.map(
            lambda s: s.sharding, tf.abstract_model(cfg))
        params = init_params(cfg, shardings)
        eng = make_engine(cfg, params, shut, mesh_ep=mesh is not None)
        watch = StepWatch(eng)
        for req in requests(cfg):
            eng.submit(req)
        eng.step()
        check(eng.stats[0].drop_frac == 0.0,
              f"first step dropped tokens (mesh {mesh is not None})")
        logits = watch.first_logits()
    del eng, watch, params
    gc.collect()
    return logits


def four_chips(cfg, clock: CompileClock) -> None:
    """EP=4 on a (1, 4) mesh: parity with one device, then an FP4 serve."""
    import jax
    from repro.configs import ReaLBConfig
    from repro.launch.mesh import make_mesh
    from repro.models import transformer as tf
    from repro.models.common import use_mesh

    mesh = make_mesh((1, 4), ("data", "model"))
    # A random bf16 model turns rounding differences between two valid
    # programs into different top-k routing, layer after layer; in f32 the
    # comparison sees the EP path's logic instead.
    pcfg = parity_config(cfg)
    ref = first_step_logits(pcfg)
    memory("ep4 one-device parity step", jax.devices()[:4])
    got = first_step_logits(pcfg, mesh)
    memory("ep4 mesh parity step", jax.devices()[:4])
    diff = np.abs(got - ref)
    rel_l2 = float(np.linalg.norm(got - ref) / np.linalg.norm(ref))
    peak = float(diff.max() / np.abs(ref).max())
    agree = float(np.mean(got.argmax(-1) == ref.argmax(-1)))
    say(f"[ep4] first-step logits, gate shut, {pcfg.n_layers} layers in "
        f"{pcfg.param_dtype}, EP=4 vs one device: {got.shape[0]} rows, max "
        f"abs diff {float(diff.max())}, rel L2 {rel_l2}, peak rel {peak}, "
        f"argmax agreement {agree}")
    check(rel_l2 < LOGIT_REL_L2 and peak < LOGIT_PEAK,
          "EP=4 logits differ from the one-device logits")

    with use_mesh(mesh):
        shardings = jax.tree.map(lambda s: s.sharding, tf.abstract_model(cfg))
        params = init_params(cfg, shardings)
        w = params["blocks"]["layer0"]["moe"]["w_gate"]
        spread = sorted((s.device.id, s.data.shape[1])
                        for s in w.addressable_shards)
        say(f"[ep4] w_gate {w.shape} shards (device, experts): {spread}")
        check(len({d for d, _ in spread}) == 4
              and all(n == cfg.moe.num_experts // 4 for _, n in spread),
              "expert weights are not spread 16 per device over 4 devices")
        # md_init=0 as on one chip: FP4 fires on the first gated iteration
        eng = make_engine(cfg, params, ReaLBConfig(md_init=0.0),
                          mesh_ep=True)
        watch = StepWatch(eng)
        reqs = requests(cfg)
        done, wall, compile_s = serve(eng, reqs, clock)
        report_serve("ep4", eng, reqs, done, wall, compile_s, watch)
        say(f"[ep4] max fp4_ranks in one layer-mean: "
            f"{max(s.fp4_ranks for s in eng.stats)}")
        memory("ep4 FP4 serve", jax.devices()[:4])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the EP=4 mesh phase and its one-device "
                         "comparison, and nothing else")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU chip found (JAX sees {len(devices)} "
              f"{dev.platform} device(s)); this check runs only on a TPU",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"chips, JAX sees {len(devices)}", file=sys.stderr)
        return 2

    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    clock = CompileClock()
    say(f"device: {dev.device_kind} ({dev.platform}), {len(devices)} "
        f"device(s)")
    cfg = smoke_config()
    say(f"model: the repo's Moonlight-width approximation of {cfg.name} — "
        f"d_model {cfg.d_model}, {cfg.moe.num_experts} routed experts "
        f"top-{cfg.moe.top_k} at width {cfg.moe.d_ff}, "
        f"{cfg.moe.n_shared_experts} shared, dense d_ff {cfg.d_ff}, vocab "
        f"{cfg.vocab_size}, {cfg.param_dtype} parameters from seed {SEED}")
    say(f"model: depth cut to {cfg.n_layers} layers ({cfg.n_dense_layers} "
        f"dense + {cfg.n_layers - cfg.n_dense_layers} MoE); attention is "
        "the repo's GQA stand-in for the published MLA")
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            four_chips(cfg, clock)
        else:
            one_chip(cfg, clock)
    except CheckFailed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    memory("all phases", devices[:args.chips])
    say(f"total seconds {time.perf_counter() - t0}, of which compile "
        f"{clock.seconds}; compile cache {cache_dir}: {clock.hits} hits, "
        f"{clock.misses} misses")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": args.chips}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
