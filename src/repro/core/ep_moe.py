"""Expert-parallel MoE layer with ReaLB runtime load balancing.

This is the paper's contribution as a composable JAX module.  The MoE layer
runs under a fully-manual ``jax.shard_map`` over the whole mesh; the EP
group is the "model" axis (each (pod, data) row of model-ranks forms an
independent EP group, mirroring the paper's DP-attention + EP-MoE
deployment generalized to a 2/3-D mesh).

Two execution paths:

* ``dispatch`` (train / prefill, large token counts): capacity-packed
  ``all_to_all`` token exchange over the EP axis, local re-sort by expert,
  grouped GEMM via ``lax.ragged_dot`` (per-rank time scales with the true
  received load — straggler dynamics are preserved on TPU), ``all_to_all``
  combine.  ReaLB's metadata collection (psum of routing counts) and the
  conditional BF16→FP4 weight transformation have **no data dependency on
  the dispatch all_to_all**, so XLA's latency-hiding scheduler overlaps
  them with communication — the paper's pipeline orchestration (§4.3),
  expressed structurally.  ``overlap=False`` (ReaLB-seq) inserts an
  artificial dependency to serialise, for the ablation.

* ``broadcast`` (decode, small token counts): tokens are replicated over
  the EP axis; each rank computes only its local experts' contributions and
  a ``psum`` combines.  This is the standard small-batch EP regime where
  the paper's LB gate keeps ReaLB off.

The per-rank precision decision is a *traced* ``lax.cond`` whose predicate
is rank-local — SPMD HLO ``conditional``, each EP rank dynamically takes
the FP4 or BF16 branch with zero host round-trips.

Expert placement
----------------
Both paths route through a traced :class:`Placement` table instead of the
hardwired contiguous expert→rank mapping: ``e2r[e]`` is the EP rank that
owns logical expert ``e`` and ``local_slot[e]`` its position in that
rank's weight slab.  The expert weight arrays are stored in *placed*
(physical) order — row ``r * e_loc + s`` holds the expert with
``e2r == r, local_slot == s`` — so live migration (see
:mod:`repro.placement`) is a host-side gather of the weight slabs plus a
new table; the traced graph never recompiles.  With the identity table
(the default) every index equals the old ``flat_e // e_loc`` arithmetic,
so outputs are bitwise-identical to the pre-placement layer.  Routing
counts, capacity packing, the per-rank load/vision statistics and the
ReaLB policy all observe the *placed* loads.

On a single device the physical EP group is 1, but the policy statistics
can still be computed over a *virtual* EP topology (``m_state`` of shape
``[1, vep]``): per-virtual-rank placed loads drive the ReaLB policy and
its AIMD state, which makes IB_d / FP4-duty / placement experiments
meaningful in CPU virtual-time serving runs.

Redundant experts (replication)
-------------------------------
The bijective table generalizes to a traced :class:`Replication` set
(see :mod:`repro.replication`): each logical expert owns up to ``R``
physical weight slots on distinct ranks, out of ``S >= E`` statically
shaped slots (``slots_per_rank`` may exceed ``E // n_ranks`` — the spare
slots hold replicas of hot experts).  Routed assignments are split
across an expert's replicas by a *deterministic round-robin* rule — the
``i``-th local assignment of expert ``e`` goes to replica
``i mod n_rep[e]`` — i.e. a proportional 1/c token split with no
randomness and no host round-trip.  Everything downstream observes the
*post-split physical* loads: capacity packing, ``load_d``/``vis_d``, the
LB gate, IB_d, and therefore the FP4 decision and the AIMD update react
to the balanced physical topology, not the logical one.  With the
identity set (one replica per expert, ``S == E``) every intermediate
equals the bijective-placement path bitwise.

Per-layer tables
----------------
This layer always consumes ONE table — the table of the layer being
computed.  Per-layer placement/replication (multimodal routing skew is
per-layer; paper Fig. 2) is realized one level up: the transformer stacks
the tables along a leading ``[n_blocks]`` axis and threads the slice
through its ``lax.scan`` xs alongside the block params (see
``repro.models.transformer.split_placement``), so each scanned block
routes through its own table while this module stays table-shape
agnostic.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from repro.configs.base import ModelConfig, MoEConfig, ReaLBConfig
from repro.core import quant
from repro.core.policy import realb_policy
from repro.kernels import nvfp4
from repro.kernels import ops as kops
from repro.models.common import P, current_mesh, resolve_spec

Params = Dict[str, jax.Array]
F32 = jnp.float32


# --------------------------------------------------------------------------
# expert placement table
# --------------------------------------------------------------------------
class Placement(NamedTuple):
    """Traced logical-expert → (rank, slot) assignment.

    ``e2r [E]`` — owning EP rank per logical expert; ``local_slot [E]`` —
    index into that rank's weight slab.  Together they must form a
    bijection onto ``rank * e_loc + slot`` (each rank owns exactly
    ``E // n_ranks`` experts — slabs are fixed-size).
    """
    e2r: jax.Array
    local_slot: jax.Array


def identity_placement(num_experts: int, n_ranks: int) -> Placement:
    """The contiguous mapping (expert ``e`` on rank ``e // e_loc``)."""
    ar = jnp.arange(num_experts, dtype=jnp.int32)
    e_loc = num_experts // n_ranks
    return Placement(ar // e_loc, ar % e_loc)


def _placed_index(place: Placement, e_loc: int) -> jax.Array:
    """[E] logical expert -> placed position ``rank * e_loc + slot``."""
    return place.e2r.astype(jnp.int32) * e_loc \
        + place.local_slot.astype(jnp.int32)


def _placed_inverse(pos_e: jax.Array) -> jax.Array:
    """[E] placed position -> logical expert (inverse permutation)."""
    e = pos_e.shape[0]
    return jnp.zeros((e,), jnp.int32).at[pos_e].set(
        jnp.arange(e, dtype=jnp.int32))


# --------------------------------------------------------------------------
# expert replication (redundant experts, token-split dispatch)
# --------------------------------------------------------------------------
class Replication(NamedTuple):
    """Traced logical-expert → physical-replica-slot ownership matrix.

    ``rep_pos [E, R]`` — physical slot (``rank * s_loc + slot``) of each
    replica; entries at ``j >= n_rep[e]`` repeat the primary.
    ``n_rep [E]`` — valid replica count per expert (>= 1).
    ``slot_owner [S]`` — logical expert resident in each physical slot
    (``-1`` = empty spare; such slots are never routed to).

    The host-numpy twin is :class:`repro.replication.ReplicaSet`.
    """
    rep_pos: jax.Array
    n_rep: jax.Array
    slot_owner: jax.Array


class WeightedReplication(NamedTuple):
    """:class:`Replication` plus a weighted-split schedule:
    ``split_sched [E, Q]`` sends the ``occ``-th routed token of expert
    ``e`` to replica ``split_sched[e, occ % Q]`` (host-built deficit
    round-robin over residual-capacity weights; the plain 3-field
    ``Replication`` keeps the equal-share ``occ % n_rep`` split)."""
    rep_pos: jax.Array
    n_rep: jax.Array
    slot_owner: jax.Array
    split_sched: jax.Array


def identity_replication(num_experts: int, n_ranks: int) -> Replication:
    """One replica per expert, no spare slots ≡ the identity placement."""
    ar = jnp.arange(num_experts, dtype=jnp.int32)
    return Replication(ar[:, None], jnp.ones_like(ar), ar)


def _rep_from_entries(entries):
    if len(entries) == 4:
        return WeightedReplication(*entries)
    return Replication(*entries)


def _as_replication(placement, num_experts: int, pol_ep: int) -> Replication:
    """Normalize the user-facing ``placement`` argument: None (identity),
    a bijective ``Placement``/2-tuple, or a ``Replication``/3- or
    4-tuple (the 4th entry is the weighted-split schedule)."""
    if placement is None:
        return identity_replication(num_experts, pol_ep)
    if isinstance(placement, (Replication, WeightedReplication)):
        return placement
    entries = tuple(placement)
    if len(entries) in (3, 4):
        return _rep_from_entries(entries)
    place = placement if isinstance(placement, Placement) \
        else Placement(*entries)
    pos_e = _placed_index(place, num_experts // pol_ep)
    return Replication(pos_e[:, None],
                       jnp.ones((num_experts,), jnp.int32),
                       _placed_inverse(pos_e))


def _occurrence_index(flat_e: jax.Array, num_experts: int) -> jax.Array:
    """[n] per-assignment rank among same-expert assignments (original
    order) — the deterministic round-robin counter of the token split.
    Entries equal to ``num_experts`` (masked-out assignments) count only
    against each other, never against real experts."""
    n = flat_e.shape[0]
    ord_e = jnp.argsort(flat_e, stable=True)
    counts = jnp.bincount(flat_e, length=num_experts + 1)
    offs = (jnp.cumsum(counts) - counts).astype(jnp.int32)
    occ_sorted = jnp.arange(n, dtype=jnp.int32) - jnp.take(offs,
                                                           flat_e[ord_e])
    return jnp.zeros((n,), jnp.int32).at[ord_e].set(occ_sorted)


def _split_assignments(rep: Replication, flat_e: jax.Array,
                       valid_flat: jax.Array
                       ) -> Tuple[jax.Array, jax.Array]:
    """(flat_pos [n], is_secondary [n]): the physical slot each routed
    assignment is dispatched to, round-robin over the expert's replicas.

    The counter runs over *valid* assignments only — invalid ones
    (chunk-bucket padding, dummy decode rows) pin to the primary replica
    and are excluded from the count, so padding neither shifts which
    replica serves a real token nor moves the post-split policy stats
    (the invariant the valid-weighted counts established in PR 1).
    """
    if rep.rep_pos.shape[1] == 1:      # bijective: skip the counter
        flat_p = jnp.take(rep.rep_pos[:, 0], flat_e)
        return flat_p, jnp.zeros(flat_e.shape, jnp.bool_)
    e = rep.rep_pos.shape[0]
    occ = _occurrence_index(jnp.where(valid_flat, flat_e, e), e)
    sched = getattr(rep, "split_sched", None)
    if sched is not None:
        # weighted split: the schedule row encodes the replica shares
        # (deficit round-robin, host-built by ReplicaSet.split_schedule)
        q = sched.shape[1]
        ridx = jnp.where(valid_flat, sched[flat_e, occ % q], 0)
    else:
        ridx = jnp.where(valid_flat, occ % jnp.take(rep.n_rep, flat_e), 0)
    flat_p = rep.rep_pos[flat_e, ridx]
    return flat_p, ridx > 0


# --------------------------------------------------------------------------
# parameter declaration
# --------------------------------------------------------------------------
def moe_spec(cfg: ModelConfig) -> Dict[str, P]:
    e = cfg.moe
    d = cfg.d_model
    return {
        "router": P((d, e.num_experts), (None, None), dtype="float32"),
        "w_gate": P((e.num_experts, d, e.d_ff), ("expert", "embed", "ffn")),
        "w_up": P((e.num_experts, d, e.d_ff), ("expert", "embed", "ffn")),
        "w_down": P((e.num_experts, e.d_ff, d), ("expert", None, "embed")),
    }


# --------------------------------------------------------------------------
# communication abstraction (lets the same math run without a mesh)
# --------------------------------------------------------------------------
class Comm(NamedTuple):
    ep: int
    my_rank: Any                                   # traced int or 0
    psum_model: Callable[[jax.Array], jax.Array]
    all_gather_model: Callable[[jax.Array], jax.Array]   # adds leading ep dim
    a2a: Callable[[jax.Array], jax.Array]                # over leading ep dim
    fsdp_gather: Callable[[jax.Array, int], jax.Array]   # all-gather 'data'


def _dist_comm(ep: int, fsdp: bool) -> Comm:
    return Comm(
        ep=ep,
        my_rank=jax.lax.axis_index("model"),
        psum_model=lambda x: jax.lax.psum(x, "model"),
        all_gather_model=lambda x: jax.lax.all_gather(x, "model"),
        a2a=lambda x: jax.lax.all_to_all(x, "model", 0, 0, tiled=True),
        fsdp_gather=(lambda x, ax: jax.lax.all_gather(
            x, "data", axis=ax, tiled=True)) if fsdp
        else (lambda x, ax: x),
    )


def _local_comm() -> Comm:
    return Comm(ep=1, my_rank=0,
                psum_model=lambda x: x,
                all_gather_model=lambda x: x[None],
                a2a=lambda x: x,
                fsdp_gather=lambda x, ax: x)


def _gather_weights(p: Params, comm: Comm) -> Dict[str, jax.Array]:
    """FSDP all-gather of the locally-owned expert slab (ZeRO layout)."""
    return {"w_gate": comm.fsdp_gather(p["w_gate"], 1),
            "w_up": comm.fsdp_gather(p["w_up"], 1),
            "w_down": comm.fsdp_gather(p["w_down"], 2)}


# --------------------------------------------------------------------------
# routing
# --------------------------------------------------------------------------
def _route(router_w: jax.Array, x_t: jax.Array, e_cfg: MoEConfig
           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """returns (gates [t,K] f32, eidx [t,K] i32, probs [t,E] f32)."""
    logits = x_t.astype(F32) @ router_w.astype(F32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = jax.lax.top_k(probs, e_cfg.top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, eidx.astype(jnp.int32), probs


def _aux_losses(probs: jax.Array, counts_global: jax.Array,
                group_tokens: jax.Array, e_cfg: MoEConfig,
                psum: Callable) -> Dict[str, jax.Array]:
    """GShard-style load-balance + router z losses (per EP group)."""
    e = e_cfg.num_experts
    f = counts_global / jnp.maximum(group_tokens * e_cfg.top_k, 1.0)
    p_mean = psum(probs.sum(0)) / jnp.maximum(group_tokens, 1.0)
    lb = e * jnp.sum(f * p_mean)
    lse = jax.scipy.special.logsumexp(
        jnp.log(jnp.maximum(probs, 1e-20)), axis=-1)
    z = psum(jnp.sum(lse ** 2)) / jnp.maximum(group_tokens, 1.0)
    return {"lb_loss": lb, "z_loss": z}


# --------------------------------------------------------------------------
# grouped expert compute (bf16 / fp4 branches)
# --------------------------------------------------------------------------
def _rdot(lhs, rhs, gs):
    return jax.lax.ragged_dot(lhs, rhs, gs,
                              preferred_element_type=F32).astype(lhs.dtype)


def _grouped_ffn(xs, gs, w_gate, w_up, w_down, act):
    """xs [m,D] sorted by group; gs [G]; w_* [G,.,.] (contraction on dim 1)."""
    g = _rdot(xs, w_gate.astype(xs.dtype), gs)
    u = _rdot(xs, w_up.astype(xs.dtype), gs)
    h = act(g.astype(F32)).astype(xs.dtype) * u
    return _rdot(h, w_down.astype(xs.dtype), gs)


def _grouped_ffn_fp4(xs, gs, wq: Dict[str, quant.QTensor],
                     rcfg: ReaLBConfig, act):
    """NVFP4 W4A4 grouped FFN, backend-switched at trace time.

    With ``kernels.ops.ffn_backend() != "jnp"`` this is the fused Pallas
    grouped kernel (native on TPU, interpret-mode in tests): packed weights
    stream HBM→VMEM and the intermediate ``h`` never round-trips HBM.  The
    jnp fallback below is the numerics oracle the kernel is pinned against
    — same dynamic per-group-16 activation fake-quant
    (``nvfp4.fake_quant_a4``), dequantize + ``ragged_dot``.
    """
    if kops.ffn_backend() != "jnp":
        return kops.grouped_fp4_ffn(xs, gs, wq, group=rcfg.group_size,
                                    act=act)
    dq = {n: quant.dequantize_fp4(q, xs.dtype) for n, q in wq.items()}
    xq = nvfp4.fake_quant_a4(xs, rcfg.group_size).astype(xs.dtype)
    g = jax.lax.ragged_dot(xq, dq["w_gate"], gs, preferred_element_type=F32)
    u = jax.lax.ragged_dot(xq, dq["w_up"], gs, preferred_element_type=F32)
    # h stays f32 up to its a4: XLA may skip a bf16 rounding of h (excess
    # precision) where Mosaic keeps it, and the piecewise-constant a4
    # would turn that into whole-level jumps between kernel and oracle
    hq = nvfp4.fake_quant_a4(act(g) * u, rcfg.group_size).astype(xs.dtype)
    return _rdot(hq, dq["w_down"], gs)


def _quantize_experts(w: Dict[str, jax.Array], use_fp4: jax.Array,
                      rcfg: ReaLBConfig,
                      overlap_token: Optional[jax.Array]) -> Dict[str, Any]:
    """③ on-the-fly BF16→FP4 transformation, conditional on the plan.

    Consumes only resident weights plus the routing-metadata predicate, so
    the HLO has no dependency path from the dispatch all_to_all into these
    ops — XLA overlaps them with communication.  ``overlap_token``
    (ReaLB-seq ablation) injects a fake dependency on the a2a output to
    serialise the transformation after dispatch.
    """

    def do_quant(ws):
        out = {}
        use_kernel = kops.ffn_backend() != "jnp"
        for name, wt in ws.items():      # [G, K, N]: quantize along K
            if overlap_token is not None:
                wt = wt + overlap_token.astype(wt.dtype)
            if use_kernel:
                # Pallas quantize kernel — bitwise-identical to the jnp
                # recipe, but streams the slab once.
                out[name] = kops.quantize_experts_fp4(
                    wt, group=rcfg.group_size)
            else:
                out[name] = quant.quantize_fp4(wt, rcfg.group_size)
        return out

    def no_quant(ws):
        # zeros derived from the weights so the varying-manual-axes (VMA)
        # type matches the quantizing branch under shard_map
        out = {}
        for name, wt in ws.items():
            out[name] = quant.QTensor(
                (wt[..., ::2, :] * 0).astype(jnp.uint8),
                (wt[..., ::rcfg.group_size, :] * 0).astype(F32),
                (wt.reshape(-1)[0] * 0 + 1).astype(F32))
        return out

    return jax.lax.cond(use_fp4, do_quant, no_quant, w)


# --------------------------------------------------------------------------
# dispatch path (train / prefill)
# --------------------------------------------------------------------------
def _moe_dispatch(x_t, mod_t, val_t, p, m_vec, cfg, rcfg, comm, act, rep,
                  pol_ep, train):
    """x_t [t,D] local tokens; mod_t [t] vision flags; val_t [t] real-token
    flags (False = batch padding); m_vec [pol_ep] AIMD; rep maps logical
    experts onto replica slots strided over ``pol_ep`` policy ranks
    (== comm.ep on a real EP mesh; a virtual topology when comm.ep == 1)."""
    e_cfg = cfg.moe
    ep, e = comm.ep, cfg.moe.num_experts
    n_slots = rep.slot_owner.shape[0]    # physical weight slots (>= E)
    s_loc = n_slots // ep                # physical slab size per rank
    s_pol = n_slots // pol_ep            # policy-topology slab size
    t, d = x_t.shape
    k = e_cfg.top_k

    # ① routing + metadata (the lightweight "S" collection) ---------------
    with jax.named_scope("route"):
        gates, eidx, probs = _route(p["router"], x_t, e_cfg)
        flat_e = eidx.reshape(t * k)
        # deterministic round-robin token split over each expert's replicas
        # (valid assignments only — padding pins to the primary)
        val_flat = jnp.repeat(val_t.astype(bool), k)
        flat_p, secondary = _split_assignments(rep, flat_e, val_flat)
        # counts are valid-weighted so the LB gate, IB_d, the AIMD update
        # and the dispatch packing all see only real tokens — chunk-bucket
        # padding neither moves the policy nor claims expert capacity
        w_val = jnp.repeat(val_t.astype(F32), k)
        w_vis = jnp.repeat((mod_t & val_t).astype(F32), k)
        counts_stat = jnp.bincount(flat_e, weights=w_val, length=e)
        vis_local = jnp.bincount(flat_e, weights=w_vis, length=e)
        counts_global = comm.psum_model(counts_stat)          # [E] logical
        vis_global = comm.psum_model(vis_local)
        # per-physical-slot *post-split* loads: the policy, the packing and
        # the diagnostics all observe the replica-balanced topology
        slot_stat = jnp.bincount(flat_p, weights=w_val, length=n_slots)
        slot_load = comm.psum_model(slot_stat)                # [S] physical
        slot_vis = comm.psum_model(
            jnp.bincount(flat_p, weights=w_vis, length=n_slots))
        load_d = slot_load.reshape(pol_ep, s_pol).sum(-1)
        vis_d = slot_vis.reshape(pol_ep, s_pol).sum(-1)
        split = comm.psum_model(jnp.sum(secondary.astype(F32) * w_val))

        # ② modality-aware LB scheduling (AIMD policy) ---------------------
        dec = realb_policy(load_d, vis_d, m_vec, rcfg)
        if ep == pol_ep:
            use_fp4_rank = dec.use_fp4[comm.my_rank]
        else:  # virtual policy topology on one physical rank: any -> all
            use_fp4_rank = jnp.any(dec.use_fp4)
        use_fp4_me = jnp.asarray(False) if train else use_fp4_rank

    with jax.named_scope("weight_gather"):
        w = _gather_weights(p, comm)

    # ③ conditional on-the-fly quantization (overlaps with a2a below) ------
    wq = None
    if not train and rcfg.overlap:
        with jax.named_scope("quantize_fp4"):
            wq = _quantize_experts(w, use_fp4_me, rcfg, None)

    # dispatch --------------------------------------------------------------
    # padding tokens are sorted to the back and never claim a capacity
    # slot, so they cannot crowd real tokens out of the per-rank cap (the
    # cap itself is provisioned from the static t, which over- rather than
    # under-provisions when chunks underfill the bucket)
    with jax.named_scope("dispatch"):
        dest = flat_p // s_loc
        valid_flat = val_flat
        order = jnp.argsort(jnp.where(valid_flat, dest, ep), stable=True)
        dest_s = dest[order]
        valid_s = valid_flat[order]
        send_counts = slot_stat.reshape(ep, s_loc).sum(-1) \
            .astype(jnp.int32)                                 # [ep] valid
        offsets = jnp.cumsum(send_counts) - send_counts
        pos_in_rank = jnp.arange(t * k, dtype=jnp.int32) - offsets[dest_s]
        cap = max(8, -(-math.ceil(t * k / ep * e_cfg.capacity_factor)
                       // 8) * 8)
        big = ep * cap + 7                   # OOB -> dropped (mode="drop")
        slot_s = jnp.where(valid_s & (pos_in_rank < cap),
                           dest_s * cap + pos_in_rank, big)

        tok_idx_s = (order // k).astype(jnp.int32)
        vals_s = jnp.take(x_t, tok_idx_s, axis=0)
        leid_s = (flat_p % s_loc)[order]
        send = jnp.zeros((ep * cap, d), x_t.dtype).at[slot_s].set(
            vals_s, mode="drop")
        eid_send = jnp.full((ep * cap,), s_loc, jnp.int32).at[slot_s].set(
            leid_s, mode="drop")
        slot_flat = jnp.full((t * k,), big, jnp.int32).at[order].set(
            slot_s.astype(jnp.int32))

        recv = comm.a2a(send.reshape(ep, cap, d)).reshape(ep * cap, d)
        eid_recv = comm.a2a(eid_send.reshape(ep, cap)).reshape(ep * cap)

    if not train and wq is None:   # ReaLB-seq: serialise T after dispatch
        with jax.named_scope("quantize_fp4"):
            token = (recv.sum() * 0.0).astype(F32)
            wq = _quantize_experts(w, use_fp4_me, rcfg, token)

    # ④ balanced local expert compute ---------------------------------------
    with jax.named_scope("expert_gemm"):
        order2 = jnp.argsort(eid_recv, stable=True)
        xs = jnp.take(recv, order2, axis=0)
        gs = jnp.bincount(eid_recv, length=s_loc + 1).astype(jnp.int32)
        pad_row = lambda a: jnp.concatenate([a, a[:1]], axis=0)
        w_pad = {n: pad_row(v) for n, v in w.items()}
        if train:
            ys = _grouped_ffn(xs, gs, w_pad["w_gate"], w_pad["w_up"],
                              w_pad["w_down"], act)
        else:
            wq_pad = {n: quant.QTensor(pad_row(v.packed), pad_row(v.scales),
                                       v.global_scale)
                      for n, v in wq.items()}
            ys = jax.lax.cond(
                use_fp4_me,
                lambda o: _grouped_ffn_fp4(o[0], gs, o[2], rcfg, act),
                lambda o: _grouped_ffn(o[0], gs, o[1]["w_gate"],
                                       o[1]["w_up"], o[1]["w_down"], act),
                (xs, w_pad, wq_pad))
        y_buf = jnp.zeros_like(ys).at[order2].set(ys)

    with jax.named_scope("combine"):
        ret = comm.a2a(y_buf.reshape(ep, cap, d)).reshape(ep * cap, d)
        y_flat = jnp.take(ret, slot_flat, axis=0, mode="fill", fill_value=0)
        y_flat = jnp.where((slot_flat < big)[:, None], y_flat, 0)
        out = jnp.sum(y_flat.reshape(t, k, d)
                      * gates[..., None].astype(y_flat.dtype), axis=1)

    # diagnostics ------------------------------------------------------------
    total = jnp.sum(load_d)
    dropped = comm.psum_model(
        jnp.sum((slot_flat >= big).astype(F32) * w_val))
    aux = _aux_losses(probs, counts_global, total / max(k, 1), e_cfg,
                      comm.psum_model)
    aux.update(drop_frac=dropped / jnp.maximum(total, 1.0),
               ib_global=dec.ib_global,
               fp4_ranks=jnp.sum(dec.use_fp4.astype(F32)),
               load_d=load_d, vis_d=vis_d,
               expert_load=counts_global, expert_vis=vis_global,
               slot_load=slot_load, slot_vis=slot_vis,
               split_frac=split / jnp.maximum(total, 1.0),
               gate_open=dec.gate_open.astype(F32))
    return out.astype(x_t.dtype), dec.m_new, aux


# --------------------------------------------------------------------------
# broadcast path (decode)
# --------------------------------------------------------------------------
def _moe_broadcast(x_t, mod_t, val_t, p, m_vec, cfg, rcfg, comm, act, rep,
                   pol_ep):
    """Decode-regime MoE: tokens replicated over the EP axis."""
    e_cfg = cfg.moe
    ep, e = comm.ep, e_cfg.num_experts
    n_slots = rep.slot_owner.shape[0]
    s_loc = n_slots // ep
    s_pol = n_slots // pol_ep
    t = x_t.shape[0]
    k = e_cfg.top_k

    with jax.named_scope("route"):
        gates, eidx, probs = _route(p["router"], x_t, e_cfg)
        flat_e = eidx.reshape(t * k)
        # every rank sees the full (replicated) token set, so the
        # round-robin counter is identical on all ranks: each assignment
        # has exactly one computing replica and the psum combine never
        # double-counts
        flat_p, secondary = _split_assignments(
            rep, flat_e, jnp.repeat(val_t.astype(bool), k))
        # valid-weighted: dummy decode rows (inactive slots) don't count
        w_val = jnp.repeat(val_t.astype(F32), k)
        w_vis = jnp.repeat((mod_t & val_t).astype(F32), k)
        counts = jnp.bincount(flat_e, weights=w_val, length=e)  # row totals
        vis = jnp.bincount(flat_e, weights=w_vis, length=e)
        slot_load = jnp.bincount(flat_p, weights=w_val, length=n_slots)
        slot_vis = jnp.bincount(flat_p, weights=w_vis, length=n_slots)
        load_d = slot_load.reshape(pol_ep, s_pol).sum(-1)
        vis_d = slot_vis.reshape(pol_ep, s_pol).sum(-1)
        split = jnp.sum(secondary.astype(F32) * w_val)
        dec = realb_policy(load_d, vis_d, m_vec, rcfg)
        if ep == pol_ep:
            use_fp4_me = dec.use_fp4[comm.my_rank]
        else:
            use_fp4_me = jnp.any(dec.use_fp4)

    with jax.named_scope("weight_gather"):
        w = _gather_weights(p, comm)

    with jax.named_scope("quantize_fp4"):
        wq = _quantize_experts(w, use_fp4_me, rcfg, None)

    with jax.named_scope("expert_gemm"):
        pidx = flat_p.reshape(t, k)                            # [t,K] placed
        sel = (pidx // s_loc) == comm.my_rank                  # [t,K]
        local_gate = jnp.where(sel, gates, 0.0)
        leid = pidx % s_loc

        def per_expert(x_all, wg, wu, wd):
            g = jnp.einsum("td,edf->etf", x_all, wg.astype(x_all.dtype))
            u = jnp.einsum("td,edf->etf", x_all, wu.astype(x_all.dtype))
            h = act(g.astype(F32)).astype(x_all.dtype) * u
            return jnp.einsum("etf,efd->etd", h, wd.astype(x_all.dtype))

        def bf16_branch(o):
            x_, w_, _ = o
            return per_expert(x_, w_["w_gate"], w_["w_up"], w_["w_down"])

        def fp4_branch(o):
            # same dynamic per-group a4 recipe as the grouped kernel, so
            # decode and prefill FP4 numerics agree across backends
            x_, _, wq_ = o
            xq = nvfp4.fake_quant_a4(x_, rcfg.group_size).astype(x_.dtype)
            wd = {n: quant.dequantize_fp4(q, x_.dtype)
                  for n, q in wq_.items()}
            g = jnp.einsum("td,edf->etf", xq, wd["w_gate"],
                           preferred_element_type=F32)
            u = jnp.einsum("td,edf->etf", xq, wd["w_up"],
                           preferred_element_type=F32)
            hq = nvfp4.fake_quant_a4(act(g) * u,
                                     rcfg.group_size).astype(x_.dtype)
            return jnp.einsum("etf,efd->etd", hq, wd["w_down"])

        y_e = jax.lax.cond(use_fp4_me, fp4_branch, bf16_branch,
                           (x_t, w, wq))

    with jax.named_scope("combine"):
        onehot = jax.nn.one_hot(leid, s_loc, dtype=y_e.dtype)  # [t,K,s_loc]
        weight_e = jnp.einsum("tk,tke->te", local_gate.astype(y_e.dtype),
                              onehot)
        y_partial = jnp.einsum("te,etd->td", weight_e, y_e)
        out = comm.psum_model(y_partial)

    total = jnp.sum(load_d)
    aux = _aux_losses(probs, counts, total / max(k, 1), e_cfg, lambda v: v)
    aux.update(drop_frac=jnp.zeros(()), ib_global=dec.ib_global,
               fp4_ranks=jnp.sum(dec.use_fp4.astype(F32)),
               load_d=load_d, vis_d=vis_d,
               expert_load=counts, expert_vis=vis,
               slot_load=slot_load, slot_vis=slot_vis,
               split_frac=split / jnp.maximum(total, 1.0),
               gate_open=dec.gate_open.astype(F32))
    return out.astype(x_t.dtype), dec.m_new, aux


# --------------------------------------------------------------------------
# public entry: shard_map wrapper
# --------------------------------------------------------------------------
AUX_SCALARS = ("lb_loss", "z_loss", "drop_frac", "ib_global", "fp4_ranks",
               "gate_open", "split_frac")


def _manual_fn(x, mod, val, m_state, router, w_gate, w_up, w_down,
               *tables, cfg, rcfg, ep, mode, fsdp, train):
    comm = _dist_comm(ep, fsdp)
    b, s, d = x.shape
    x_t = x.reshape(b * s, d)
    mod_t = mod.reshape(b * s)
    val_t = val.reshape(b * s)
    # every device holds its own scalar M_d; gather the EP-group vector via
    # psum-of-onehot (provably replicated over 'model' for the VMA checker)
    m_vec = comm.psum_model(
        jax.nn.one_hot(comm.my_rank, ep, dtype=F32) * m_state.reshape(()))
    p = {"router": router, "w_gate": w_gate, "w_up": w_up, "w_down": w_down}
    act = jax.nn.silu if cfg.activation == "swiglu" else jax.nn.gelu
    rep = _rep_from_entries(tables)
    if mode == "broadcast":
        y, m_new, aux = _moe_broadcast(x_t, mod_t, val_t, p, m_vec, cfg,
                                       rcfg, comm, act, rep, ep)
    else:
        y, m_new, aux = _moe_dispatch(x_t, mod_t, val_t, p, m_vec, cfg,
                                      rcfg, comm, act, rep, ep, train)
    y = y.reshape(b, s, d)
    m_out = m_new[comm.my_rank].reshape(m_state.shape)
    aux_s = jnp.stack([aux[n] for n in AUX_SCALARS]).reshape(1, -1)
    stats = jnp.stack([aux["load_d"], aux["vis_d"]]).reshape(1, 2, ep)
    estats = jnp.stack([aux["expert_load"], aux["expert_vis"]]
                       ).reshape(1, 2, -1)
    sstats = jnp.stack([aux["slot_load"], aux["slot_vis"]]
                       ).reshape(1, 2, -1)
    return y, m_out, aux_s, stats, estats, sstats


def ep_moe_forward(p: Params, x: jax.Array, cfg: ModelConfig,
                   rcfg: ReaLBConfig, m_state: jax.Array,
                   modality: Optional[jax.Array] = None,
                   mode: str = "dispatch", train: bool = False,
                   fsdp: bool = False,
                   valid: Optional[jax.Array] = None,
                   placement: Optional[Placement] = None):
    """MoE layer with ReaLB.  x [B,S,D]; m_state [groups, ep] (see
    :func:`moe_state_shape`); valid [B,S] marks real tokens (None = all) —
    padding still computes but is excluded from the routing stats the
    policy consumes.  ``placement`` maps logical experts onto EP ranks:
    None = the contiguous identity mapping (bitwise-identical to the
    pre-placement layer), a :class:`Placement`/2-tuple = a bijective
    permutation, a :class:`Replication`/3-tuple = redundant experts with
    round-robin token splitting.  The expert weight arrays in ``p`` must
    be stored in the matching *placed* physical-slot order (``[S, ...]``
    with ``S >= num_experts`` under replication).
    Returns (y, new_m_state, aux_dict)."""
    mesh = current_mesh()
    if modality is None:
        modality = jnp.zeros(x.shape[:2], jnp.bool_)
    if valid is None:
        valid = jnp.ones(x.shape[:2], jnp.bool_)

    local = (mesh is None or "model" not in mesh.axis_names or
             dict(zip(mesh.axis_names, mesh.devices.shape))["model"] == 1)
    if local:
        # the policy/statistics topology is the trailing m_state dim: [1]
        # physically, but a serving engine may provision a *virtual* EP
        # group (m_state [1, vep]) so IB_d / FP4 duty are non-trivial on
        # one device.
        pol_ep = int(m_state.shape[-1]) if m_state.ndim else 1
        assert cfg.moe.num_experts % pol_ep == 0, \
            (cfg.moe.num_experts, pol_ep)
        rep = _as_replication(placement, cfg.moe.num_experts, pol_ep)
        assert rep.slot_owner.shape[0] % pol_ep == 0, \
            (rep.slot_owner.shape[0], pol_ep)
        comm = _local_comm()
        b, s, d = x.shape
        act = jax.nn.silu if cfg.activation == "swiglu" else jax.nn.gelu
        fn = _moe_broadcast if mode == "broadcast" \
            else partial(_moe_dispatch, train=train)
        y, m_new, aux = fn(x.reshape(b * s, d), modality.reshape(b * s),
                           valid.reshape(b * s), p, m_state.reshape(-1),
                           cfg, rcfg, comm, act, rep, pol_ep)
        return (y.reshape(b, s, d), m_new.reshape(m_state.shape), aux)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    ep = sizes["model"]
    row_axes = tuple(a for a in mesh.axis_names if a != "model")
    row_entry = row_axes if len(row_axes) > 1 else row_axes[0]
    single_group = m_state.shape[0] == 1
    rep = _as_replication(placement, cfg.moe.num_experts, ep)
    assert rep.slot_owner.shape[0] % ep == 0, \
        (rep.slot_owner.shape[0], ep)

    x_axes = ("batch", "seq", None) if mode == "dispatch" \
        else ("batch", None, None)
    x_spec = resolve_spec(x.shape, x_axes, mesh)
    mod_spec = PartitionSpec(*x_spec[:2])
    m_spec = PartitionSpec(None if single_group else row_entry, "model")
    r_spec = PartitionSpec(None, None)
    t_spec = PartitionSpec(None)        # replicated [E]/[S] tables
    t2_spec = PartitionSpec(None, None)  # replicated [E, R] replica matrix
    wg_spec = resolve_spec(p["w_gate"].shape,
                           ("expert", "embed" if fsdp else None, None), mesh)
    wd_spec = resolve_spec(p["w_down"].shape,
                           ("expert", None, "embed" if fsdp else None), mesh)
    aux_spec = PartitionSpec(None if single_group else row_entry, None)
    stats_spec = PartitionSpec(None if single_group else row_entry,
                               None, None)

    fn = partial(_manual_fn, cfg=cfg, rcfg=rcfg, ep=ep, mode=mode,
                 fsdp=fsdp, train=train)
    table_args = (rep.rep_pos, rep.n_rep, rep.slot_owner)
    table_specs = (t2_spec, t_spec, t_spec)
    sched = getattr(rep, "split_sched", None)
    if sched is not None:                # replicated [E, Q] split schedule
        table_args += (sched,)
        table_specs += (t2_spec,)
    # check_vma=False: pallas_call (the FP4 quantize / grouped-FFN
    # kernels) has no replication rule; the out_specs above already state
    # the sharding we require, so only the static replication lint is lost
    y, m_new, aux_s, stats, estats, sstats = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(x_spec, mod_spec, mod_spec, m_spec, r_spec, wg_spec,
                  wg_spec, wd_spec) + table_specs,
        out_specs=(x_spec, m_spec, aux_spec, stats_spec, stats_spec,
                   stats_spec), check_vma=False,
    )(x, modality, valid, m_state, p["router"], p["w_gate"], p["w_up"],
      p["w_down"], *table_args)

    aux_mean = aux_s.mean(0)
    aux = {n: aux_mean[i] for i, n in enumerate(AUX_SCALARS)}
    aux["load_d"] = stats[:, 0, :]
    aux["vis_d"] = stats[:, 1, :]
    aux["expert_load"] = estats[:, 0, :].sum(0)
    aux["expert_vis"] = estats[:, 1, :].sum(0)
    aux["slot_load"] = sstats[:, 0, :].sum(0)
    aux["slot_vis"] = sstats[:, 1, :].sum(0)
    return y, m_new, aux


def moe_state_shape(mesh, global_batch: int,
                    virtual_ep: Optional[int] = None) -> Tuple[int, int]:
    """AIMD M-state shape [n_groups, ep] for a given mesh & batch.

    ``virtual_ep`` provisions the policy statistics over a virtual EP
    topology when there is no mesh (single-device serving simulations)."""
    if mesh is None:
        return (1, int(virtual_ep) if virtual_ep else 1)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    ep = sizes.get("model", 1)
    rows = 1
    for a in mesh.axis_names:
        if a != "model":
            rows *= sizes[a]
    if global_batch % max(rows, 1) != 0:
        rows = 1  # batch not shardable over rows -> single replicated group
    return (rows, ep)
