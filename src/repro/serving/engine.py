"""Batched serving engine v2: chunked token-budgeted prefill + decode with
ReaLB active.

The engine holds one device-resident cache of ``max_slots`` sequences and
drives the scheduler loop.  Prefill is *chunked and batched*: each
iteration packs up to ``prefill_budget`` prompt tokens across every slot
with pending prefill work into one rectangular forward (per-slot chunk
continuation state), so prefill batches reach the large-token regime where
ReaLB's LB gate opens — instead of the v1 per-request batch-1 loop that
never crossed ``gate_gamma``.  Decode remains one batched step across all
decode-ready slots.  The AIMD ``m_state`` of ReaLB persists across both
kinds of iteration, exactly like the controller in the paper's serving
deployment; per-iteration routing/imbalance stats — prefill iterations
included — are recorded for the benchmarks and streamed to an optional
:class:`~repro.serving.telemetry.Telemetry` collector.

Architectures whose caches cannot be continued mid-prompt (MLA latent,
SSM state, enc-dec memory, VLM embed injection) fall back to the v1
one-shot batch-1 prefill per request; everything downstream (timing,
telemetry, modality-aware decode) is shared.

Expert placement & live migration: when constructed with a
:class:`~repro.placement.PlacementManager`, the engine feeds it per-
iteration expert-load stats, and at the manager's replan cadence applies
the returned weight-slab permutation to ``self.params`` (gather-by-table;
KV caches, AIMD M-state and telemetry are untouched).  Migration bytes
and virtual-time seconds are charged to the clock and recorded in the
next :class:`IterStats`, so the zero-overhead property of ReaLB vs. the
migration cost of placement is directly measurable.  ``virtual_ep``
provisions the ReaLB policy statistics over a virtual EP topology on a
single device (see ``repro.core.ep_moe``).

Redundant experts: a :class:`~repro.replication.ReplicaManager` rides the
same loop, but its weight arrays hold ``S >= E`` physical slots (expand
the logical params with ``repro.replication.expand_moe_params`` before
construction) and its plans are *staged*: the engine gathers the slabs
first and only then calls ``manager.commit(plan)``, so a replica becomes
routable (visible to the traced dispatch table) strictly after its slab
landed in ``self.params`` — the consistency rule that keeps a crashed
apply from routing tokens into garbage weights.

Per-layer tables: managers constructed with ``per_layer=True`` return
stacked ``[n_blocks, ...]`` tables from ``device_tables()``; the model
threads the per-layer slice through its layer scan, and the manager's
plans are layer-diffs whose slab traffic covers changed layers only.
The engine code is identical either way — ``_place_args``/
``_maybe_migrate`` are shape-agnostic.

Async overlapped migration (``migrate_async=True``): instead of landing
a staged plan's whole slab permutation between two iterations, the
engine drains it through a :class:`~repro.serving.async_migrate.
MigrationExecutor` — one byte-budgeted batch of per-layer chunks per
iteration, each landed layer's table committed independently
(``manager.commit_layers``), so serving routes old tables for layers
still in flight and new tables for landed ones.  Transfer seconds that
fit the budget are *hidden* (overlapped with the iteration's forward —
not charged to a virtual clock), only the excess *stalls*; both are
split out in :class:`IterStats` (``migration_s`` = stall,
``migration_hidden_s``).  Every apply — sync or async — is wall-timed
and fed into the manager's measured-bandwidth EWMA, which prices
``migration_seconds``, the chunk budget and the calibrated replan gate.
While a plan is draining no new replan can fire, and checkpointing
refuses cleanly (the in-flight params/table mix is not a restorable
state).
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.sentinel import NULL_SENTINEL
from repro.configs.base import ModelConfig, ReaLBConfig
from repro.core import ep_moe
from repro.models import transformer as tf
from repro.models.common import current_mesh
from repro.obs.profiler import NULL_PROFILER
from repro.obs.trace import DEFAULT_TRACER
from repro.serving.scheduler import Request, Scheduler
from repro.serving.telemetry import Telemetry


@dataclasses.dataclass
class IterStats:
    """Per-iteration routing/balance diagnostics (benchmark input)."""
    n_active: int
    tokens: int                  # real (non-padding) tokens this iteration
    ib_global: float
    fp4_ranks: float
    gate_open: float
    phase: str = "decode"        # "prefill" | "decode"
    t_wall: float = 0.0          # engine clock at record time
    batch_tokens: int = 0        # tokens the MoE actually saw (incl. pad)
    vis_frac: float = 0.0        # vision fraction of routed assignments
    drop_frac: float = 0.0       # capacity-dropped fraction of routed tokens
    migration_bytes: int = 0     # expert weight bytes moved before this
    #                              iter (integral end-to-end: plans count
    #                              whole weight bytes, never fractions)
    migration_s: float = 0.0     # migration seconds that STALLED serving
    #                              (charged to a virtual clock; measured
    #                              wall seconds under wall clocks)
    migration_hidden_s: float = 0.0  # transfer seconds hidden under the
    #                              iteration's forward (async overlap)
    split_frac: float = 0.0      # routed fraction served by a non-primary
    #                              replica (0 under a bijective table)
    n_unroutable: int = 0        # logical experts with no live replica
    #                              (elastic degraded mode; 0 when healthy)
    lost_tokens: float = 0.0     # tokens this iteration routed to an
    #                              unroutable expert (they landed on the
    #                              dead rank's zeroed slots)


#: Layer-summed scalars of ``aux`` the per-iteration statistics read.
STAT_SCALARS = ("ib_global", "fp4_ranks", "gate_open", "drop_frac",
                "split_frac")


def _bucket(n: int, lo: int = 8) -> int:
    """Round a chunk length up to a power of two (bounds jit recompiles)."""
    b = lo
    while b < n:
        b *= 2
    return b


class Engine:
    def __init__(self, cfg: ModelConfig, params, rcfg: ReaLBConfig,
                 max_slots: int = 8, max_len: int = 256,
                 temperature: float = 0.0, seed: int = 0,
                 prefill_budget: int = 256, text_reserve: int = 1,
                 clock: Callable[[], float] = time.monotonic,
                 telemetry: Optional[Telemetry] = None,
                 cost_model=None, placement=None,
                 virtual_ep: Optional[int] = None,
                 capacity_margin: Optional[float] = None,
                 migrate_async: bool = False,
                 migrate_bytes_per_iter: Optional[int] = None,
                 elastic=None, fault_injector=None, tracer=None,
                 profiler=None, sentinel=None):
        self.cfg, self.params, self.rcfg = cfg, params, rcfg
        # invariant sentinel (repro.analysis.sentinel.Sentinel); None ->
        # the shared no-op under the tracer/profiler null-object
        # discipline.  When armed it guards the iteration hot window
        # against unsanctioned device->host syncs and counts per-entry
        # jit compilations (zero recompiles after warmup).
        self.sentinel = NULL_SENTINEL if sentinel is None else sentinel
        # span tracer (repro.obs.trace.Tracer); None -> the shared
        # default, whose spans only annotate the profiler (a no-op unless
        # a trace is being collected): it records nothing and reads no
        # clock — an untraced engine is bitwise identical to a traced
        # one.  When given, the tracer is shared with the manager and
        # the elastic coordinator so their spans land on the same
        # timeline.
        self.tracer = DEFAULT_TRACER if tracer is None else tracer
        if tracer is not None:
            if placement is not None:
                placement.tracer = tracer
            if elastic is not None:
                elastic.tracer = tracer
        # hot-loop profiler (repro.obs.profiler.Profiler); None -> the
        # shared no-op singleton under the same discipline as the tracer:
        # no stats conversion, no clock math, bitwise-identical outputs.
        self.profiler = NULL_PROFILER if profiler is None else profiler
        if profiler is not None and placement is not None:
            # wire the measured/predicted drift EWMA into the replan cost
            # gate's savings side (same idiom as the manager's bandwidth
            # auto-wiring): replans are priced at how fast the hardware
            # actually runs the analytic model's seconds
            gate = getattr(placement, "cost_gate", None)
            if gate is not None \
                    and getattr(gate, "time_scale", False) is None:
                gate.time_scale = profiler.time_scale
        self.max_slots, self.max_len = max_slots, max_len
        self.temperature = temperature
        self.prefill_budget = prefill_budget
        # chunk continuation needs a pure GQA/MQA decoder stack
        self.chunked = (prefill_budget > 0 and cfg.mla is None
                        and cfg.ssm is None and not cfg.is_encdec
                        and cfg.layer_pattern == "attn"
                        and cfg.family != "vlm")
        self.scheduler = Scheduler(max_slots, text_reserve=text_reserve)
        self.clock = clock
        self.telemetry = telemetry
        # virtual-time mode: an object with .cost(batch_tokens) -> seconds,
        # paired with a clock exposing .advance(dt).  The clock is advanced
        # right after each forward, *before* first-token/finish timestamps
        # are stamped, so TTFT includes the iteration that produced the
        # token — not just queueing delay.
        self.cost_model = cost_model
        # expert placement: a repro.placement.PlacementManager (or None).
        # virtual_ep sizes the policy-statistics topology on meshless runs
        # (defaults to the manager's EP group when one is given).
        self._placement = placement
        mesh = current_mesh()
        if placement is not None and mesh is not None:
            mesh_ep = dict(zip(mesh.axis_names,
                               mesh.devices.shape)).get("model", 1)
            assert placement.ep == mesh_ep, \
                f"placement plans {placement.ep} ranks, mesh EP={mesh_ep}"
        if placement is not None and mesh is None \
                and virtual_ep is not None:
            # the table's slots are strided by E // placement.ep; a
            # different policy topology would break the pos bijection
            assert placement.ep == virtual_ep, \
                f"placement plans {placement.ep} ranks, virtual_ep={virtual_ep}"
        if virtual_ep is None and placement is not None and mesh is None:
            virtual_ep = placement.ep
        if placement is not None and cfg.moe is not None:
            # replica managers route over S >= E physical weight slots;
            # refuse a params tree that was not laid out for the manager
            # (forgotten expand_moe_params would silently misroute)
            from repro.placement.migrate import moe_param_paths
            tables = placement.device_tables()
            want = int(tables[2].shape[-1]) if len(tables) >= 3 \
                else cfg.moe.num_experts
            paths = moe_param_paths(params)
            if paths:
                g0, l0 = paths[0]
                got = params[g0][l0]["moe"]["w_gate"].shape[-3]
                assert got == want, \
                    (f"params hold {got} expert slots but the manager "
                     f"routes over {want}; lay the weights out with "
                     "repro.replication.expand_moe_params first")
        # replica-aware dispatch capacity: with a margin set, every
        # committed replica replan re-derives capacity_factor from the
        # post-split predicted peak rank load and re-jits the steps —
        # the dispatch buffer shrinks to the flattened topology
        self.capacity_margin = capacity_margin
        self._base_capacity = cfg.moe.capacity_factor if cfg.moe else 0.0
        # async overlapped migration: drain staged plans as byte-budgeted
        # per-layer chunks instead of one synchronous whole-plan apply
        self.migrate_async = migrate_async
        self.migrate_bytes_per_iter = migrate_bytes_per_iter
        self._mig = None                  # active MigrationExecutor
        self._iter_s: Optional[float] = None  # EWMA of iteration seconds
        # (bytes:int, stall_s, hidden_s) staged for the next IterStats
        self._pending_migration = (0, 0.0, 0.0)
        # cumulative engine-side accounting (survives telemetry windows
        # and tail drains — e.g. drain_migrations() after the last
        # request — that never reach a _record)
        self.migration_bytes_moved = 0
        self.migration_stall_s = 0.0
        self.migration_hidden_s = 0.0
        # elastic serving: an ElasticCoordinator over the same manager
        # turns rank loss/rejoin into between-iteration events; a
        # FaultInjector scripts them (polled once per step)
        self._elastic = elastic
        self._fault = fault_injector
        if elastic is not None:
            assert placement is not None and elastic.manager is placement, \
                "elastic coordinator must wrap this engine's manager"
        self._place_cache = None                  # device copy of the table
        self._it = 0
        # (admitted, chunk rows, chunk tokens, decode rows) of the last
        # iteration: the iter span's args
        self._iter_rows = (0, 0, 0, 0)
        self.cache = tf.init_cache(cfg, max_slots, max_len)
        groups, ep = ep_moe.moe_state_shape(current_mesh(), max_slots,
                                            virtual_ep=virtual_ep)
        self.m_state = jnp.full((groups, ep), rcfg.md_init, jnp.float32)
        self.pos = np.zeros(max_slots, np.int32)      # next write position
        self.last_tok = np.zeros(max_slots, np.int32)
        self.active_mask = np.zeros(max_slots, bool)
        self.decode_ready = np.zeros(max_slots, bool)
        self.mod_state = np.zeros(max_slots, bool)    # decode-token modality
        self._prefill_fifo: List[int] = []            # slots mid-prefill
        # aux scalars come back summed over the layer scan; normalize to
        # per-MoE-layer means so duty cycles / IB read as true fractions
        self._n_moe = max(sum(1 for f in cfg.ffn_kinds() if f == "moe"), 1)
        self.stats: List[IterStats] = []
        self.key = jax.random.PRNGKey(seed)
        self._build()

    # -- jitted steps -------------------------------------------------------
    def _build(self):
        cfg, rcfg = self.cfg, self.rcfg

        @jax.jit
        def prefill_one(params, m_state, batch, place):
            res = tf.prefill_forward(params, cfg, rcfg, batch, m_state,
                                     cache_len=self.max_len, placement=place)
            return res.logits, res.cache, res.m_state, res.aux

        @jax.jit
        def chunk_step(params, cache, m_state, tokens, start, chunk_len,
                       modality, place):
            batch = {"tokens": tokens, "start": start,
                     "chunk_len": chunk_len, "modality": modality}
            res = tf.chunk_forward(params, cfg, rcfg, batch, cache, m_state,
                                   placement=place)
            return res.logits, res.cache, res.m_state, res.aux

        @jax.jit
        def decode(params, cache, m_state, tokens, pos, modality, valid,
                   place):
            batch = {"tokens": tokens, "pos": pos, "modality": modality,
                     "valid": valid}
            res = tf.decode_forward(params, cfg, rcfg, batch, cache, m_state,
                                    placement=place)
            return res.logits, res.cache, res.m_state, res.aux

        self._prefill_one = prefill_one
        self._chunk = chunk_step
        self._decode = decode
        if self.sentinel.enabled:
            self.sentinel.register_entry("prefill", prefill_one)
            self.sentinel.register_entry("chunk", chunk_step)
            self.sentinel.register_entry("decode", decode)

    def _place_args(self):
        """The traced table of the current plan — (e2r, local_slot) for a
        bijective manager, (rep_pos, n_rep, slot_owner) for a replica
        manager, None = the identity mapping (bitwise-identical to a
        placement-free engine).  Cached on device; invalidated when a
        committed migration changes the routable table."""
        if self._placement is None:
            return None
        if self._place_cache is None:
            self._place_cache = tuple(
                jnp.asarray(a) for a in self._placement.device_tables())
        return self._place_cache

    # -- live migration ------------------------------------------------------
    def _maybe_migrate(self):
        """The per-iteration migration state machine.

        Draining: advance the in-flight chunk queue by one byte-budgeted
        batch (no new replan can fire — the manager guards it).  Idle:
        ask the manager for a staged plan; apply it synchronously, or
        start an async executor and drain its first batch."""
        if self._placement is None or self.cfg.moe is None:
            return
        if self._mig is not None:
            self._drain_migration()
            return
        plan = self._placement.maybe_replan(self._it)
        if plan is None:
            return
        if self.migrate_async:
            from repro.serving.async_migrate import MigrationExecutor
            prio = patch = None
            if self._elastic is not None:
                # recovery chunks (re-materializing unroutable experts)
                # drain ahead of optimization chunks; the patch drops
                # checkpoint rows into the landed slots pre-commit
                prio = self._elastic.recovery_layers(plan)
                patch = self._elastic.patch_params
            self._mig = MigrationExecutor(
                self._placement, plan,
                bytes_per_iter=self.migrate_bytes_per_iter,
                priority_layers=prio, patch_fn=patch)
            self._drain_migration()
            return
        # synchronous path: the whole slab permutation lands between two
        # iterations, wall-timed so the measured-bandwidth EWMA (and the
        # charged seconds under wall clocks) reflect the real transfer
        from repro.placement import migrate
        t0 = time.perf_counter()
        try:
            new_params = migrate.apply_to_params(self.params, plan)
            jax.block_until_ready(new_params)
        except BaseException:
            # drop the staged plan so the old set stays routable and
            # a later cadence point can replan, then surface the error
            self._placement.abort()
            raise
        wall = time.perf_counter() - t0
        self._placement.bandwidth.observe(plan.moved_bytes, wall)
        layers = self._placement.plan_layers(plan)
        if self._elastic is not None:
            # lost experts' slabs were gathered from the dead (zeroed)
            # slots; overwrite them with checkpoint rows BEFORE the new
            # tables flip routable (staged-commit rule) — outside the
            # timed window so ckpt reads don't pollute the bandwidth EWMA
            try:
                new_params = self._elastic.patch_params(new_params, plan,
                                                        layers)
                jax.block_until_ready(new_params)
            except BaseException:
                self._placement.abort()
                raise
        self.params = new_params
        # staged plans become routable only after the slab gather above
        # produced the new weights (consistency rule)
        self._placement.commit(plan)
        self._place_cache = None                  # table changed
        if hasattr(self.clock, "advance"):
            secs = self._placement.migration_seconds(plan.moved_bytes)
            self.clock.advance(secs)
        else:
            # wall clocks: the move is real work already on the wall —
            # record the measured seconds, not 0
            secs = wall
        self._charge_migration(int(plan.moved_bytes), secs, 0.0)
        trc = self.tracer
        if trc.enabled:
            # one migration.drain span per charge: summed durations
            # reconcile exactly with stall + hidden telemetry totals
            trc.complete("migration.drain", self.clock() - secs, secs,
                         cat="migration",
                         args={"mode": "sync",
                               "bytes": int(plan.moved_bytes),
                               "stall_s": secs, "hidden_s": 0.0,
                               "layers": len(layers)})
            trc.instant("table.commit", cat="migration",
                        args={"layers": len(layers), "done": True})
        self._notify_plan_committed()
        if self._elastic is not None:
            self._elastic.on_layers_landed(plan, layers)

    def _drain_migration(self):
        """One budgeted chunk batch of the in-flight plan: land the
        slabs, commit exactly those layers, split the transfer seconds
        into hidden (fits the budget — overlapped with this iteration's
        forward) and stall (the excess, charged to a virtual clock)."""
        plan = self._mig.plan
        try:
            self.params, rep = self._mig.drain(self.params, self._iter_s)
        except BaseException:
            # the executor aborted the staged remainder; landed layers
            # stay routable (their slabs did land)
            self._mig = None
            self._place_cache = None
            raise
        self._place_cache = None              # landed layers' tables flipped
        if hasattr(self.clock, "advance"):
            stall = self._placement.migration_seconds(rep.excess_bytes)
            hidden = self._placement.migration_seconds(
                rep.nbytes - rep.excess_bytes)
            self.clock.advance(stall)
        else:
            # single-threaded wall-clock serving cannot actually overlap
            # the host-side apply: the whole batch is an honest stall
            stall, hidden = rep.wall_s, 0.0
        if rep.done:
            self._mig = None
        self._charge_migration(rep.nbytes, stall, hidden)
        trc = self.tracer
        if trc.enabled:
            # span starts at the stall charge and extends through the
            # hidden (forward-overlapped) share; dur = stall + hidden so
            # summed drain spans reconcile with the telemetry totals
            trc.complete("migration.drain", self.clock() - stall,
                         stall + hidden, cat="migration",
                         args={"mode": "async", "bytes": int(rep.nbytes),
                               "stall_s": stall, "hidden_s": hidden,
                               "layers": len(rep.layers),
                               "done": bool(rep.done)})
            if rep.layers:
                trc.instant("table.commit", cat="migration",
                            args={"layers": len(rep.layers),
                                  "done": bool(rep.done)})
        if rep.done:
            self._notify_plan_committed()
        if self._elastic is not None and rep.layers:
            # landed layers' lost experts are re-materialized (the
            # executor's patch_fn ran pre-commit); clear them and stamp
            # recovery_s / warm-up completion
            self._elastic.on_layers_landed(plan, rep.layers)

    def _charge_migration(self, nbytes: int, stall_s: float,
                          hidden_s: float):
        b, s, h = self._pending_migration
        self._pending_migration = (b + int(nbytes), s + stall_s,
                                   h + hidden_s)
        self.migration_bytes_moved += int(nbytes)
        self.migration_stall_s += stall_s
        self.migration_hidden_s += hidden_s

    def _notify_plan_committed(self):
        """A staged plan fully landed: count the commit and open a fresh
        prediction-accuracy window stamped with the predictor's per-layer
        rank loads under the new tables (read-only — no engine state)."""
        if self.telemetry is None:
            return
        self.telemetry.record_plan_commit()
        if self._placement is not None \
                and hasattr(self._placement, "predicted_rank_loads"):
            self.telemetry.open_prediction_window(
                self._it, self._placement.predicted_rank_loads())

    @property
    def migration_draining(self) -> bool:
        """A staged plan's chunk queue is mid-flight."""
        return self._mig is not None and self._mig.draining

    def drain_migrations(self, max_iters: int = 10_000) -> None:
        """Finish any in-flight migration without serving (e.g. before a
        checkpoint): budget-sized batches keep landing until the queue
        is empty."""
        it = 0
        while self.migration_draining:
            it += 1
            assert it <= max_iters, "migration drain failed to converge"
            self._drain_migration()

    # -- elastic serving events ----------------------------------------------
    def _abort_migration(self) -> None:
        """Drop any in-flight or staged plan (a fault invalidates it: the
        plan was computed against the pre-fault rank set).  Landed layers
        stay routable — their slabs did land."""
        if self._mig is not None:
            self._mig.cancel()
            self._mig = None
        elif getattr(self._placement, "in_flight", None) is not None:
            self._placement.abort()
        self._place_cache = None

    def fail_rank(self, rank: int) -> None:
        """The fault-injection hook: simulate the loss of EP ``rank``
        between iterations.  The in-flight plan (if any) is aborted, the
        dead rank is masked out of the routable tables (experts with a
        surviving replica stay routable this same iteration), its weight
        slabs are zeroed, and the coordinator arms an event-triggered
        recovery replan."""
        assert self._elastic is not None, \
            "fail_rank requires an ElasticCoordinator"
        self._abort_migration()
        self.params = self._elastic.fail_rank(rank, self.params)
        self._place_cache = None                  # tables were masked

    def rejoin_rank(self, rank: int) -> None:
        """The returning rank becomes plannable; it turns routable layer
        by layer as the warm-up plan's slabs land (staged commit)."""
        assert self._elastic is not None, \
            "rejoin_rank requires an ElasticCoordinator"
        self._elastic.rejoin_rank(rank)

    def _maybe_resize_capacity(self):
        """Replica-aware capacity: shrink (or restore) the dispatch
        ``capacity_factor`` to the post-split predicted peak rank load.
        Re-checked every iteration — not only on committed migrations —
        so load drifting under an unchanged replica set (replan rejected
        or noop) re-grows the buffer before it overflows.  The factor is
        jit-static, so a change re-builds the step fns; the 5% band
        keeps drift from re-jitting every step."""
        if (self.capacity_margin is None or self.cfg.moe is None
                or not hasattr(self._placement, "capacity_factor")):
            return
        eff = min(self._placement.capacity_factor(self.capacity_margin),
                  self._base_capacity)
        cur = self.cfg.moe.capacity_factor
        if abs(eff - cur) / max(cur, 1e-9) < 0.05:
            return
        self.cfg = dataclasses.replace(
            self.cfg, moe=dataclasses.replace(self.cfg.moe,
                                              capacity_factor=eff))
        # a deliberate re-jit: declare it so the sentinel's recompile
        # report attributes the fresh compilations to the resize band
        self.sentinel.note_rebuild(
            f"capacity_factor {cur:.4f}->{eff:.4f}")
        self._build()

    # -- cache slot insertion ----------------------------------------------
    def _insert_cache(self, slot: int, new_cache):
        """Copy a batch-1 prefill cache into slot `slot` of the engine cache.

        Stacked block entries are [n_blocks, B, ...] (batch axis 1); prefix
        entries are [B, ...] (axis 0).
        """
        def set_slot(axis):
            def f(dst, src):
                idx = [slice(None)] * dst.ndim
                idx[axis] = slice(slot, slot + 1)
                return dst.at[tuple(idx)].set(src.astype(dst.dtype))
            return f

        self.cache["blocks"] = jax.tree.map(set_slot(1),
                                            self.cache["blocks"],
                                            new_cache["blocks"])
        if "prefix" in self.cache:
            self.cache["prefix"] = jax.tree.map(set_slot(0),
                                                self.cache["prefix"],
                                                new_cache["prefix"])

    # -- public API ----------------------------------------------------------
    def submit(self, req: Request):
        assert req.prompt_len + req.max_new_tokens <= self.max_len, \
            (req.prompt_len, req.max_new_tokens, self.max_len)
        if req.arrival_time is None:
            req.arrival_time = self.clock()
        self.scheduler.submit(req)

    def _sample(self, logits: jax.Array) -> np.ndarray:
        # sampling is a sanctioned sync: the generated token must reach
        # the host to extend the sequence (the one pull serving requires);
        # it waits for the program that produced the logits
        with self.tracer.span("sample", cat="engine"), \
                self.sentinel.sanctioned("sample"):
            if self.temperature <= 0:
                return np.asarray(jnp.argmax(logits, axis=-1), np.int32)
            self.key, sub = jax.random.split(self.key)
            return np.asarray(jax.random.categorical(
                sub, logits / self.temperature, axis=-1), np.int32)

    def _tick(self, batch_tokens: int):
        """Advance a virtual clock by the modeled cost of one forward."""
        if self.cost_model is not None and hasattr(self.clock, "advance"):
            self.clock.advance(self.cost_model.cost(batch_tokens))

    def _record(self, *, phase: str, n_active: int, tokens: int,
                batch_tokens: int, aux: Dict[str, Any],
                fwd_s: float = 0.0):
        # the statistics pull is a sanctioned sync point: routing stats
        # must land on host between forwards — they feed the predictor,
        # the replan gates and the AIMD policy's observers
        with self.sentinel.sanctioned("telemetry"):
            self._record_stats(phase=phase, n_active=n_active,
                               tokens=tokens, batch_tokens=batch_tokens,
                               aux=aux, fwd_s=fwd_s)

    def _record_stats(self, *, phase: str, n_active: int, tokens: int,
                      batch_tokens: int, aux: Dict[str, Any],
                      fwd_s: float = 0.0):
        trc = self.tracer
        with trc.span("stats.pull", cat="engine") as sp:
            host = self._pull_stats(aux)
            if trc.collecting:
                sp.set(transfers=len(host))
        with trc.span("stats.observe", cat="engine"):
            self._observe_stats(phase=phase, n_active=n_active,
                                tokens=tokens, batch_tokens=batch_tokens,
                                host=host, fwd_s=fwd_s)

    def _pull_stats(self, aux: Dict[str, Any]) -> Dict[str, Any]:
        """Every device->host transfer of ``aux`` the statistics read, one
        per entry of the result: the stacked ``moe_stats``, the
        layer-summed scalars, and the expert / slot loads where a manager
        or a recovery reads them."""
        # moe_stats: [n_blocks, 2, groups, ep] stacked (load_d, vis_d) rows
        host = {"moe_stats": np.asarray(aux["moe_stats"], np.float64)}
        for k in STAT_SCALARS:
            if k in aux:
                host[k] = float(aux[k])
        pl = self._placement
        recovering = self._elastic is not None and self._elastic.recovering
        if "expert_stats" in aux and (pl is not None or recovering):
            host["expert_stats"] = np.asarray(aux["expert_stats"])
        if pl is not None and "expert_stats" in aux and "slot_stats" in aux \
                and (hasattr(pl, "observe_slots")
                     or (self.telemetry is not None
                         and hasattr(pl, "rank_heatmap"))):
            host["slot_stats"] = np.asarray(aux["slot_stats"])
        return host

    def _observe_stats(self, *, phase: str, n_active: int, tokens: int,
                       batch_tokens: int, host: Dict[str, Any],
                       fwd_s: float):
        ms = host["moe_stats"]
        load_sum, vis_sum = float(ms[:, 0].sum()), float(ms[:, 1].sum())
        mig_bytes, mig_s, mig_hidden = self._pending_migration
        self._pending_migration = (0, 0.0, 0.0)
        stat = IterStats(
            n_active=n_active, tokens=tokens,
            ib_global=host["ib_global"] / self._n_moe,
            fp4_ranks=host["fp4_ranks"] / self._n_moe,
            gate_open=host["gate_open"] / self._n_moe,
            phase=phase, t_wall=self.clock(), batch_tokens=batch_tokens,
            vis_frac=vis_sum / max(load_sum, 1.0),
            drop_frac=host["drop_frac"] / self._n_moe,
            migration_bytes=mig_bytes, migration_s=mig_s,
            migration_hidden_s=mig_hidden,
            split_frac=host.get("split_frac", 0.0) / self._n_moe)
        if self._elastic is not None and self._elastic.recovering:
            stat.n_unroutable = int(self._elastic.lost_experts.size)
            if "expert_stats" in host:
                stat.lost_tokens = self._elastic.lost_token_count(
                    host["expert_stats"])
        self.stats.append(stat)
        if self._placement is not None and "expert_stats" in host:
            # [n_blocks, 2, E] per-MoE-layer expert loads -> predictor
            # (decode iterations feed the decode window when configured)
            self._placement.observe(host["expert_stats"],
                                    decode=(phase == "decode"))
            if hasattr(self._placement, "observe_slots") \
                    and "slot_stats" in host:
                # [n_blocks, 2, S] post-split physical-slot loads ->
                # replica-utilization accounting
                self._placement.observe_slots(host["slot_stats"])
            gate = getattr(self._placement, "cost_gate", None)
            if gate is not None and hasattr(gate, "observe_iter"):
                # calibrated replan gate: measured routed tokens (and the
                # engine clock) replace the static roofline constant
                gate.observe_iter(tokens, stat.t_wall)
            if self.telemetry is not None \
                    and hasattr(self._placement, "rank_heatmap"):
                # realized [n_blocks, ep] rank loads under the routable
                # tables -> expert-load heatmap + prediction accuracy
                self.telemetry.record_rank_heatmap(
                    self._placement.rank_heatmap(host["expert_stats"],
                                                 host.get("slot_stats")))
        if self.telemetry is not None:
            self.telemetry.record_iter(stat)
        if self.profiler.enabled:
            # FLOP/byte ledger + drift EWMA off the stats array already
            # in hand; fwd_s is this forward's engine-clock seconds
            # (virtual charge or wall time alike)
            self.profiler.observe_iter(
                moe_stats=ms, fp4_layers=stat.fp4_ranks, tokens=tokens,
                batch_tokens=batch_tokens, fwd_s=fwd_s, phase=phase)
        trc = self.tracer
        if trc.enabled:
            trc.instant("dispatch.policy", cat="policy",
                        args={"it": self._it, "phase": phase,
                              "tokens": tokens,
                              "ib_global": stat.ib_global,
                              "fp4_ranks": stat.fp4_ranks,
                              "gate_open": stat.gate_open,
                              "drop_frac": stat.drop_frac})

    def _finish(self, req: Request):
        req.finish_time = self.clock()
        if self.telemetry is not None:
            self.telemetry.record_request(req)

    # -- prefill paths -------------------------------------------------------
    def _first_token(self, req: Request, tok: int, t: float):
        """``t``: the engine clock right after the sample that brought
        ``tok`` to the host."""
        req.generated.append(tok)
        req.token_times.append(t)
        req.first_token_time = t
        self.pos[req.slot] = req.prompt_len
        self.last_tok[req.slot] = tok
        self.decode_ready[req.slot] = True
        if req.done:
            self._finish(req)

    def _prefill_oneshot(self, req: Request):
        """v1 path: whole prompt, batch of 1, full-row cache insert."""
        batch = {
            "tokens": jnp.asarray(req.tokens, jnp.int32)[None],
            "modality": jnp.asarray(req.modality, bool)[None],
        }
        if req.vision_embeds is not None:
            batch["vision_embeds"] = jnp.asarray(
                req.vision_embeds, jnp.dtype(self.cfg.param_dtype))[None]
        if self.cfg.is_encdec:
            batch["enc_embeds"] = jnp.asarray(
                req.vision_embeds if req.vision_embeds is not None
                else np.zeros((self.cfg.enc_seq_len, self.cfg.d_model),
                              np.float32),
                jnp.dtype(self.cfg.param_dtype))[None]
        t_fwd = self.clock()
        with self.tracer.span("launch.prefill", cat="engine") as sp:
            logits, new_cache, self.m_state, aux = self._prefill_one(
                self.params, self.m_state, batch, self._place_args())
            self._tick(req.prompt_len)
            if self.tracer.collecting:
                sp.set(tokens=req.prompt_len)
        fwd_s = self.clock() - t_fwd
        self._insert_cache(req.slot, new_cache)
        req.prefill_pos = req.prompt_len
        tok = int(self._sample(logits)[0])
        self._first_token(req, tok, self.clock())
        self._record(phase="prefill", n_active=1, tokens=req.prompt_len,
                     batch_tokens=req.prompt_len, aux=aux, fwd_s=fwd_s)

    def _plan_chunks(self) -> List:
        """Allocate the token budget over slots with pending prefill work,
        oldest admission first; at most one partial chunk per iteration."""
        budget = self.prefill_budget
        plan = []
        for slot in self._prefill_fifo:
            if budget <= 0:
                break
            req = self.scheduler.active[slot]
            take = min(req.prompt_len - req.prefill_pos, budget)
            plan.append((slot, take))
            budget -= take
        return plan

    def _chunk_prefill_step(self) -> Tuple[int, int]:
        """One batched prefill chunk; returns its (rows, prompt tokens)."""
        trc = self.tracer
        with trc.span("chunk.inputs", cat="engine"):
            plan = self._plan_chunks()
            if not plan:
                return 0, 0
            s_bucket = _bucket(max(take for _, take in plan))
            b = self.max_slots
            tokens = np.zeros((b, s_bucket), np.int32)
            modality = np.zeros((b, s_bucket), bool)
            start = np.zeros(b, np.int32)
            chunk_len = np.zeros(b, np.int32)
            for slot, take in plan:
                req = self.scheduler.active[slot]
                p0 = req.prefill_pos
                tokens[slot, :take] = req.tokens[p0:p0 + take]
                modality[slot, :take] = req.modality[p0:p0 + take]
                start[slot] = p0
                chunk_len[slot] = take
            args = (jnp.asarray(tokens), jnp.asarray(start),
                    jnp.asarray(chunk_len), jnp.asarray(modality),
                    self._place_args())
        t_fwd = self.clock()
        with trc.span("launch.chunk", cat="engine") as sp:
            logits, self.cache, self.m_state, aux = self._chunk(
                self.params, self.cache, self.m_state, *args)
            self._tick(b * s_bucket)
            if trc.collecting:
                sp.set(slots=len(plan), batch_tokens=b * s_bucket)
        fwd_s = self.clock() - t_fwd
        completing = [slot for slot, take in plan
                      if self.scheduler.active[slot].prefill_pos + take
                      >= self.scheduler.active[slot].prompt_len]
        if completing:
            toks = self._sample(logits)
            t_tok = self.clock()
        n_tok = 0
        for slot, take in plan:
            req = self.scheduler.active[slot]
            req.prefill_pos += take
            n_tok += take
            if req.prefill_pos >= req.prompt_len:
                self._prefill_fifo.remove(slot)
                self._first_token(req, int(toks[slot]), t_tok)
        self._record(phase="prefill", n_active=len(plan), tokens=n_tok,
                     batch_tokens=b * s_bucket, aux=aux, fwd_s=fwd_s)
        return len(plan), n_tok

    # -- the iteration --------------------------------------------------------
    def step(self) -> int:
        """One continuous-batching iteration. Returns #active sequences."""
        trc = self.tracer
        with trc.span("iter", cat="engine") as sp:
            n = self._step()
            if trc.collecting:
                admitted, chunk_rows, chunk_tokens, decode_rows = \
                    self._iter_rows
                sp.set(it=self._it, n_active=n, admitted=admitted,
                       chunk_rows=chunk_rows, chunk_tokens=chunk_tokens,
                       decode_rows=decode_rows, **self.profiler.span_args())
        return n

    def _step(self) -> int:
        self._it += 1
        # -2) scripted rank faults fire between iterations — the event
        # boundary of the elastic subsystem (dispatch tables, params and
        # plans are all quiescent here)
        if self._fault is not None:
            for ev in self._fault.due(self._it):
                if ev.kind == "fail":
                    self.fail_rank(ev.rank)
                else:
                    self.rejoin_rank(ev.rank)
        # weighted token splitting re-derives its per-replica schedule
        # from the latest residual-capacity prediction at the manager's
        # cadence — a pure table refresh, no weights move
        if self._placement is not None and \
                getattr(self._placement, "wants_table_refresh",
                        lambda it: False)(self._it):
            self._place_cache = None
        # -1) placement: apply a due replan before any forward of this
        # iteration sees the weights (plan and slabs move atomically),
        # then re-derive the replica-aware dispatch capacity from the
        # current prediction (migrated or not — drift under an unchanged
        # set must still re-grow a shrunk buffer)
        if self._placement is not None:
            with self.tracer.span("migrate", cat="migration"):
                self._maybe_migrate()
                self._maybe_resize_capacity()
        # everything up to here is the sanctioned between-iteration
        # window (faults, migration drains, resize re-jits); the rest of
        # the iteration is the hot loop the sentinel guards against
        # unsanctioned device->host syncs
        with self.sentinel.hot("iter"):
            return self._step_hot()

    def _step_hot(self) -> int:
        # the overlap window starts AFTER the migration charges: the
        # async budget must size against forward compute only — folding
        # a stall into the window would let the stall grow next
        # iteration's "hidden" budget, flattering the bounded-stall claim
        t_step0 = self.clock()
        # 0) purge slots freed by a mid-prefill retirement (e.g. a
        # max_new_tokens=0 request) before they can be re-admitted
        if self._prefill_fifo:
            self._prefill_fifo = [s for s in self._prefill_fifo
                                  if s in self.scheduler.active]
        # 1) admit new requests; route each to the chunked or one-shot path
        trc = self.tracer
        oneshot = []
        with trc.span("admit", cat="engine") as sp:
            admitted = self.scheduler.admit()
            t_admit = self.clock() if admitted else 0.0
            for req in admitted:
                req.admit_time = t_admit
                self.active_mask[req.slot] = True
                self.decode_ready[req.slot] = False
                self.mod_state[req.slot] = req.decode_modality
                if self.chunked and req.vision_embeds is None:
                    req.prefill_pos = 0
                    self._prefill_fifo.append(req.slot)
                else:
                    oneshot.append(req)
            if trc.collecting:
                sp.set(admitted=len(admitted))
        for req in oneshot:
            self._prefill_oneshot(req)

        # 2) one batched chunk of prefill work across all pending slots
        chunk_rows = chunk_tokens = 0
        if self._prefill_fifo:
            chunk_rows, chunk_tokens = self._chunk_prefill_step()

        with trc.span("retire", cat="engine"):
            self.scheduler.retire()
            for s in range(self.max_slots):
                self.active_mask[s] = s in self.scheduler.active
                if not self.active_mask[s]:
                    self.decode_ready[s] = False
        self._iter_rows = (len(admitted), chunk_rows, chunk_tokens, 0)

        if not self.scheduler.active:
            self._observe_iter_s(t_step0)
            return 0

        # 3) batched decode over decode-ready slots (others run dummies whose
        # cache writes land out of bounds and are dropped — a mid-prefill
        # slot's cache must never be touched by the decode scatter)
        ready = self.decode_ready & self.active_mask
        n_active = 0
        if ready.any():
            with trc.span("decode.inputs", cat="engine"):
                args = (jnp.asarray(self.last_tok[:, None], jnp.int32),
                        jnp.asarray(np.where(ready, self.pos, self.max_len),
                                    jnp.int32),
                        jnp.asarray(
                            np.where(ready, self.mod_state, False)[:, None]),
                        jnp.asarray(ready[:, None]), self._place_args())
            t_fwd = self.clock()
            with trc.span("launch.decode", cat="engine") as sp:
                logits, self.cache, self.m_state, aux = self._decode(
                    self.params, self.cache, self.m_state, *args)
                self._tick(self.max_slots)
                if trc.collecting:
                    sp.set(batch_tokens=self.max_slots,
                           ready=int(ready.sum()))
            fwd_s = self.clock() - t_fwd
            toks = self._sample(logits)
            t_tok = self.clock()
            for slot, req in list(self.scheduler.active.items()):
                if ready[slot] and not req.done:
                    req.generated.append(int(toks[slot]))
                    req.token_times.append(t_tok)
                    self.last_tok[slot] = int(toks[slot])
                    self.pos[slot] += 1
                    n_active += 1
                    if req.done:
                        self._finish(req)
            self._record(phase="decode", n_active=n_active, tokens=n_active,
                         batch_tokens=self.max_slots, aux=aux, fwd_s=fwd_s)
        with trc.span("retire", cat="engine"):
            self.scheduler.retire()
        self._iter_rows = (len(admitted), chunk_rows, chunk_tokens, n_active)
        self._observe_iter_s(t_step0)
        return max(n_active, len(self._prefill_fifo))

    def _observe_iter_s(self, t_step0: float):
        """EWMA of one iteration's seconds on the engine clock (virtual
        charges or wall time alike) — the overlap window the async
        migration budget sizes its chunk batches against."""
        dt = self.clock() - t_step0
        if dt <= 0:
            return
        self._iter_s = dt if self._iter_s is None \
            else 0.75 * self._iter_s + 0.25 * dt

    def run(self, max_iters: int = 10_000) -> List[Request]:
        it = 0
        while not self.scheduler.idle and it < max_iters:
            self.step()
            it += 1
        return self.scheduler.finished

    # -- checkpointing --------------------------------------------------------
    def save_checkpoint(self, ckpt_dir: str, step: int, keep: int = 3) -> str:
        """Persist params + AIMD state (+ the chosen placement plan /
        replica set and predictor state, under the manager's own group) so
        a restored engine resumes with the same expert layout instead of
        silently reverting to identity.

        Refused while an async migration is draining: the params hold a
        mix of landed and not-yet-landed layer slabs whose in-flight
        plan is not part of the manager's persisted state — call
        :meth:`drain_migrations` first."""
        self._refuse_mid_flight("save")
        from repro.checkpoint import ckpt
        state = {"serving": {"params": self.params, "m_state": self.m_state}}
        if self._placement is not None:
            state[self._placement.ckpt_group] = self._placement.state_dict()
        return ckpt.save(ckpt_dir, step, state, keep=keep)

    def _refuse_mid_flight(self, what: str) -> None:
        if self.migration_draining \
                or getattr(self._placement, "in_flight", None) is not None:
            raise RuntimeError(
                f"cannot {what} a checkpoint while a migration is "
                "draining (params hold a partially-landed slab layout); "
                "call drain_migrations() first")
        if self._elastic is not None and self._elastic.recovering:
            raise RuntimeError(
                f"cannot {what} a checkpoint mid-recovery (params hold "
                "zeroed slabs for unroutable experts a restore would "
                "resurrect); let the recovery plan land first")

    def load_checkpoint(self, ckpt_dir: str,
                        step: Optional[int] = None) -> int:
        self._refuse_mid_flight("load")
        from repro.checkpoint import ckpt
        templates = {"serving": {"params": self.params,
                                 "m_state": self.m_state}}
        step, out = ckpt.restore(ckpt_dir, templates, step)

        def group_state(name):
            if not ckpt.has_group(ckpt_dir, name, step):
                return None
            return ckpt.restore_group(ckpt_dir, name, step)

        # the saved params are laid out for the writer's manager kind: a
        # bijective permutation ("placement") or a replica-slot order with
        # S >= E rows ("replication").  Any mismatched reader — manager-
        # free, or the other kind — would silently route its own table
        # through foreign weights, so refuse instead of desynchronizing.
        own = None if self._placement is None \
            else self._placement.ckpt_group
        for name, kind in (("placement", "a placement engine"),
                           ("replication", "a replication engine")):
            if name != own and group_state(name) is not None:
                raise ValueError(
                    f"checkpoint {ckpt_dir} step {step} was written by "
                    f"{kind} (weights are in its placed physical order); "
                    "construct this Engine with the matching manager to "
                    "restore it")
        self.params = out["serving"]["params"]
        self.m_state = out["serving"]["m_state"]
        if self._placement is not None:
            state = group_state(own)
            if state is None:
                # written by a manager-free engine: logical-order weights
                # and no layout state to resume — reset to a fresh
                # identity state (replica engines re-expand the logical
                # rows into their physical slot layout)
                self._placement.reset()
                if own == "replication":
                    from repro.replication import expand_moe_params
                    self.params = expand_moe_params(self.params,
                                                    self._placement.rsets)
            else:
                self._placement.load_state_dict(state)
            self._place_cache = None
        return step
