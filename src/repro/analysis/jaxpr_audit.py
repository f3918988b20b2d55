"""Trace-time audit of the serving hot loop's jaxpr.

:func:`audit_jaxpr` walks a closed jaxpr (recursing through scan /
cond / while / pjit / shard_map sub-jaxprs) and enforces the invariants
ReaLB's "zero scheduling overhead" claim rests on:

* **no host callbacks** — ``pure_callback`` / ``io_callback`` /
  ``debug_callback`` on the hot path would serialize every iteration on
  a device→host round trip;
* **no f64** — a stray Python float promoted to float64 doubles the
  bandwidth of whatever it touches and kicks the MXU off the fast path;
* **widening discipline** — every ``convert_element_type`` that widens
  a float (bf16→f32, anything→f64) inside the FP4 dispatch/expert
  phases must match an explicit allowlist (softmax, accumulators,
  norms, sub-byte dequant): an unlisted widening is usually a silently
  reintroduced BF16 round-trip the fused kernel PR removed.

:func:`collective_census_jaxpr` counts collective primitives
(``psum`` / ``all_to_all`` / ``ppermute`` / ``all_gather`` /
``reduce_scatter``) with per-participant payload bytes, multiplying
through ``scan`` trip counts.  The same census runs post-XLA over the
compiled HLO (:func:`repro.launch.hlo_analysis.collective_census`) and
both reconcile against the
:meth:`repro.obs.ledger.FlopByteLedger.predict_graph_census`
prediction — three independent derivations of the hot loop's ICI
traffic that must agree.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.extend import core as jcore

#: primitive-name fragments that mean a host round trip
_CALLBACK_RE = re.compile(r"callback")

#: collective primitive names (jaxpr level)
COLLECTIVE_PRIMS = ("psum", "all_to_all", "ppermute", "all_gather",
                    "reduce_scatter", "pmax", "pmin", "axis_index")
_CENSUS_PRIMS = ("psum", "all_to_all", "ppermute", "all_gather",
                 "reduce_scatter")

#: default name-stack allowlist for widening converts: phases where a
#: float widening is the algorithm (f32 softmax/logits in `route`, f32
#: gate accumulation in `combine`, f32 norm statistics, attention
#: softmax, aux losses, the f32 SiLU gate of the expert FFN, the f32
#: scales of the FP4 weight and activation quantizers).  Matched against
#: the eqn's full name stack.
DEFAULT_WIDEN_ALLOWLIST: Tuple[str, ...] = (
    "route", "combine", "norm", "attention", "aux", "softmax", "rope",
    "embed", "logits", "swiglu", "quantize_fp4", "quantize_a4",
)


@dataclasses.dataclass
class AuditViolation:
    kind: str            # callback | f64 | widening
    primitive: str
    where: str           # name-stack / context
    detail: str

    def format(self) -> str:
        return f"[{self.kind}] {self.primitive} @ {self.where}: {self.detail}"


@dataclasses.dataclass
class AuditReport:
    violations: List[AuditViolation]
    n_eqns: int
    widenings: List[Dict[str, Any]]     # every float widening seen
    census: Dict[str, Dict[str, int]]   # collective census (count/bytes)

    @property
    def ok(self) -> bool:
        return not self.violations

    def to_json(self) -> Dict[str, Any]:
        return {
            "ok": self.ok,
            "n_eqns": self.n_eqns,
            "violations": [dataclasses.asdict(v) for v in self.violations],
            "widenings": self.widenings,
            "census": self.census,
        }


def _aval_bytes(aval) -> int:
    shape = getattr(aval, "shape", ())
    dtype = getattr(aval, "dtype", None)
    if dtype is None:
        return 0
    n = 1
    for d in shape:
        n *= int(d)
    return n * np.dtype(dtype).itemsize


def _is_f64(aval) -> bool:
    dtype = getattr(aval, "dtype", None)
    return dtype is not None and np.dtype(dtype) in (np.float64,
                                                     np.complex128)


def _float_bits(dtype) -> Optional[int]:
    dt = np.dtype(dtype)
    # jax extended float types (bfloat16, f8/f4 variants) are ml_dtypes
    # customs with kind 'V': np.finfo rejects them, jnp.finfo does not
    if not jax.numpy.issubdtype(dt, jax.numpy.floating):
        return None
    try:
        return int(jax.numpy.finfo(dt).bits)
    except Exception:
        return dt.itemsize * 8


def _name_stack(eqn) -> str:
    try:
        return str(eqn.source_info.name_stack)
    except Exception:
        return ""


def _sub_jaxprs(eqn) -> List[Tuple[jcore.Jaxpr, int]]:
    """(sub_jaxpr, multiplier) pairs below one eqn."""
    out: List[Tuple[jcore.Jaxpr, int]] = []
    params = eqn.params
    mult = 1
    if eqn.primitive.name == "scan":
        mult = int(params.get("length", 1))
    for key in ("jaxpr", "call_jaxpr", "body_jaxpr", "cond_jaxpr",
                "fun_jaxpr"):
        sub = params.get(key)
        if sub is None:
            continue
        j = sub.jaxpr if isinstance(sub, jcore.ClosedJaxpr) else sub
        out.append((j, mult))
    for branch in params.get("branches", ()):  # lax.cond / switch
        j = branch.jaxpr if isinstance(branch, jcore.ClosedJaxpr) \
            else branch
        out.append((j, 1))
    return out


def _walk(jaxpr: jcore.Jaxpr, visit: Callable[[Any, int], None],
          mult: int = 1) -> None:
    """Depth-first over eqns; ``visit(eqn, mult)`` sees the product of
    enclosing scan trip counts."""
    for eqn in jaxpr.eqns:
        visit(eqn, mult)
        for sub, m in _sub_jaxprs(eqn):
            _walk(sub, visit, mult * m)


def audit_jaxpr(closed: jcore.ClosedJaxpr,
                widen_allowlist: Sequence[str] = DEFAULT_WIDEN_ALLOWLIST,
                widen_scopes: Sequence[str] = ("dispatch", "expert_gemm",
                                               "quantize_fp4"),
                allow_f64: bool = False) -> AuditReport:
    """Audit one traced step.

    ``widen_scopes``: name-stack fragments marking the FP4
    dispatch/expert path — float widenings there must match
    ``widen_allowlist`` (sub-byte → wider dequants are always legal:
    that *is* the FP4 mechanism).  Widenings to f64 are never legal.
    """
    violations: List[AuditViolation] = []
    widenings: List[Dict[str, Any]] = []
    census: Dict[str, Dict[str, int]] = {}
    n_eqns = 0

    def visit(eqn, mult: int):
        nonlocal n_eqns
        n_eqns += 1
        name = eqn.primitive.name
        stack = _name_stack(eqn)
        if _CALLBACK_RE.search(name):
            violations.append(AuditViolation(
                "callback", name, stack,
                "host callback on the hot path serializes every "
                "iteration on a device-host round trip"))
        if not allow_f64:
            for v in list(eqn.outvars) + list(eqn.invars):
                aval = getattr(v, "aval", None)
                if aval is not None and _is_f64(aval):
                    violations.append(AuditViolation(
                        "f64", name, stack,
                        f"float64 value of shape "
                        f"{getattr(aval, 'shape', ())}"))
                    break
        if name == "convert_element_type":
            self_bits = _convert_bits(eqn)
            if self_bits is not None:
                src_bits, dst_bits, src_dt, dst_dt = self_bits
                if dst_bits > src_bits:
                    entry = {"src": str(src_dt), "dst": str(dst_dt),
                             "where": stack}
                    widenings.append(entry)
                    on_fp4_path = any(s in stack for s in widen_scopes)
                    allowed = (
                        src_bits <= 8       # sub-byte/f8 dequant widen
                        or any(a in stack for a in widen_allowlist))
                    if on_fp4_path and not allowed:
                        violations.append(AuditViolation(
                            "widening", name, stack,
                            f"{src_dt} -> {dst_dt} widening on the FP4 "
                            "dispatch/expert path is not on the "
                            "allowlist"))
        if name in _CENSUS_PRIMS or any(
                name.startswith(p + "_") for p in _CENSUS_PRIMS):
            kind = next((p for p in _CENSUS_PRIMS
                         if name == p or name.startswith(p + "_")), name)
            out_b = sum(_aval_bytes(v.aval) for v in eqn.outvars)
            in_b = sum(_aval_bytes(v.aval) for v in eqn.invars
                       if hasattr(v, "aval"))
            b = max(out_b, in_b)
            ent = census.setdefault(kind, {"count": 0, "bytes": 0})
            ent["count"] += mult
            ent["bytes"] += b * mult

    _walk(closed.jaxpr, visit)
    return AuditReport(violations=violations, n_eqns=n_eqns,
                       widenings=widenings, census=census)


def _convert_bits(eqn):
    """(src_bits, dst_bits, src_dtype, dst_dtype) of a float->float
    convert_element_type, else None."""
    if not eqn.invars:
        return None
    src_aval = getattr(eqn.invars[0], "aval", None)
    if src_aval is None:
        return None
    src_dt = getattr(src_aval, "dtype", None)
    dst_dt = eqn.params.get("new_dtype")
    if src_dt is None or dst_dt is None:
        return None
    sb, db = _float_bits(src_dt), _float_bits(dst_dt)
    if sb is None or db is None:
        return None
    return sb, db, src_dt, dst_dt


def collective_census_jaxpr(closed: jcore.ClosedJaxpr
                            ) -> Dict[str, Dict[str, int]]:
    """Collective census alone: {prim: {count, bytes}} with per-
    participant payload bytes, scan trip counts multiplied through."""
    return audit_jaxpr(closed, allow_f64=True).census
