"""Cost manifest — the per-program record the PT-COST gate baselines.

``compute_manifest`` folds the walker stream (flops.py) into one JSON-able
:class:`CostManifest`: FLOPs per op family, byte traffic + arithmetic
intensity, a full dtype census, host-sync / scatter / gather / upcast
counts, the donation audit (read from the traced ``jit`` equation's
``donated_invars`` — the actual donation the jitted callable declares, not
a hand-maintained list), and, once :func:`scaling_verdict` has seen the
same program at two slot widths, the slot-scaling law record.

Counts come in two flavors, deliberately:

- ``num_eqns`` / ``scatter_ops`` / ``gather_ops`` / ``upcast_converts`` /
  ``host_sync_eqns`` are STATIC equation counts (scan bodies count once) —
  they measure *program text growth*, the thing that explodes when a
  python loop accidentally unrolls per slot.
- ``flops`` / ``bytes_total`` apply the execution multipliers (a scan body
  of length L counts L times) — they measure *work*.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from .flops import FAMILIES, HOST_SYNC_PRIMS, closed_jaxpr_of, iter_eqn_costs

__all__ = ["CostManifest", "HotPathSpec", "compute_manifest",
           "scaling_verdict"]

#: upcasts the dtype census calls out: a half-precision value widened to a
#: full-precision one (the bf16->f32 weak-type accident class)
_NARROW = ("bfloat16", "float16")
_WIDE = ("float32", "float64")


@dataclass
class HotPathSpec:
    """Reviewed registration of one hot-path program (tools/
    audit_program_cost.py): which argument subtrees are step-to-step
    carries (and therefore must be donated), where they sit in the traced
    callable's flat input order, and the program's slot width for the
    scaling law."""

    name: str
    #: carry name -> (lo, hi) flat-invar index range of the traced call
    carries: Dict[str, Tuple[int, int]] = field(default_factory=dict)
    slots: Optional[int] = None
    notes: str = ""


@dataclass
class CostManifest:
    program: str
    slots: Optional[int] = None
    num_eqns: int = 0                     # static, containers recursed
    flops: Dict[str, float] = field(default_factory=dict)   # per family
    bytes_total: float = 0.0
    arithmetic_intensity: float = 0.0
    dtypes: Dict[str, Dict[str, float]] = field(default_factory=dict)
    upcast_converts: int = 0
    host_sync_eqns: int = 0
    host_sync_prims: List[str] = field(default_factory=list)
    scatter_ops: int = 0
    gather_ops: int = 0
    while_loops: int = 0                  # unknown-trip containers: the
    #                                       flop/byte totals UNDERCOUNT these
    donation: Dict[str, List[str]] = field(default_factory=dict)
    scaling: Optional[Dict] = None

    @property
    def flops_total(self) -> float:
        return self.flops.get("total", 0.0)

    def to_dict(self) -> Dict:
        return {
            "program": self.program, "slots": self.slots,
            "num_eqns": self.num_eqns, "flops": dict(self.flops),
            "bytes_total": self.bytes_total,
            "arithmetic_intensity": self.arithmetic_intensity,
            "dtypes": {k: dict(v) for k, v in self.dtypes.items()},
            "upcast_converts": self.upcast_converts,
            "host_sync_eqns": self.host_sync_eqns,
            "host_sync_prims": list(self.host_sync_prims),
            "scatter_ops": self.scatter_ops, "gather_ops": self.gather_ops,
            "while_loops": self.while_loops,
            "donation": {k: list(v) for k, v in self.donation.items()},
            "scaling": self.scaling,
        }

    @classmethod
    def from_dict(cls, d: Dict) -> "CostManifest":
        m = cls(program=d.get("program", "?"))
        for k, v in d.items():
            if hasattr(m, k):
                setattr(m, k, v)
        return m


def _donation_audit(closed, carries: Dict[str, Tuple[int, int]]):
    """Read the ACTUAL donation off the outermost ``jit`` equation of a
    traced jitted callable. A carry is donated iff every flat invar in its
    range is marked in ``donated_invars``. Programs traced from a bare
    function (no jit wrapper) have no jit equation — nothing is donated."""
    donated_invars = None
    if closed is not None:
        jaxpr = getattr(closed, "jaxpr", closed)
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "jit":
                donated_invars = eqn.params.get("donated_invars")
                break
    names, donated, missing = [], [], []
    for name, (lo, hi) in carries.items():
        names.append(name)
        ok = (donated_invars is not None and hi <= len(donated_invars)
              and all(donated_invars[lo:hi]))
        (donated if ok else missing).append(name)
    return {"carries": names, "donated": donated, "missing": missing}


def compute_manifest(program_or_jaxpr, name: str = "program",
                     spec: Optional[HotPathSpec] = None) -> CostManifest:
    """Fold the cost walk into one manifest. Pure tracing arithmetic — no
    XLA compile, no device dispatch. When the argument is a traced Program
    import, the manifest is also attached as ``program._cost_manifest``."""
    m = CostManifest(program=name,
                     slots=spec.slots if spec is not None else None)
    flops = {f: 0.0 for f in FAMILIES}
    total_f = total_b = 0.0
    for e in iter_eqn_costs(program_or_jaxpr):
        m.num_eqns += 1
        flops[e.family] = flops.get(e.family, 0.0) + e.total_flops
        total_f += e.total_flops
        total_b += e.total_bytes
        if e.prim in HOST_SYNC_PRIMS:
            m.host_sync_eqns += 1
            m.host_sync_prims.append(e.prim)
        if e.family == "scatter":
            m.scatter_ops += 1
        elif e.family == "gather":
            m.gather_ops += 1
        if e.prim == "while":
            m.while_loops += 1
        if (e.prim == "convert_element_type" and e.in_dtypes
                and e.out_dtypes and e.in_dtypes[0] in _NARROW
                and e.out_dtypes[0] in _WIDE):
            m.upcast_converts += 1
        if e.out_dtypes:
            # census: the eqn and its traffic ride the first output's dtype
            slot = m.dtypes.setdefault(e.out_dtypes[0],
                                       {"eqns": 0, "bytes": 0.0})
            slot["eqns"] += 1
            slot["bytes"] += e.total_bytes
    m.flops = {k: v for k, v in flops.items() if v} or {}
    m.flops["total"] = total_f
    m.bytes_total = total_b
    m.arithmetic_intensity = (total_f / total_b) if total_b else 0.0
    closed = closed_jaxpr_of(program_or_jaxpr)
    if spec is not None and spec.carries:
        m.donation = _donation_audit(closed, spec.carries)
    if hasattr(program_or_jaxpr, "global_block"):
        program_or_jaxpr._cost_manifest = m
    return m


def scaling_verdict(manifests: Sequence[CostManifest],
                    tol: float = 0.25) -> Dict:
    """The slot-scaling law (PT-COST-005): given the SAME program traced at
    ascending slot widths, program text (``num_eqns``) and work
    (``flops_total``) must scale at most linearly in slots — an accidental
    O(slots^2) term (a per-slot python loop unrolling, a dense slot x slot
    interaction in the scatter machinery) fails the law. The verdict is
    recorded onto every participating manifest."""
    ms = sorted(manifests, key=lambda m: (m.slots or 0))
    slots = [m.slots for m in ms]
    if len(ms) < 2 or any(s is None or s <= 0 for s in slots):
        raise ValueError("scaling law needs >=2 manifests with slot widths")
    verdict, worst = "<=linear", 0.0
    for a, b in zip(ms, ms[1:]):
        grow = b.slots / a.slots
        for attr in ("num_eqns", "flops_total"):
            va, vb = float(getattr(a, attr)), float(getattr(b, attr))
            if va <= 0:
                continue
            ratio = (vb / va) / grow        # 1.0 == exactly linear
            worst = max(worst, ratio)
            if ratio > 1.0 + tol:
                verdict = "superlinear"
    rec = {"slots": slots, "num_eqns": [m.num_eqns for m in ms],
           "flops_total": [m.flops_total for m in ms],
           "verdict": verdict, "worst_linear_ratio": round(worst, 4),
           "tol": tol}
    for m in ms:
        m.scaling = rec
    return rec
