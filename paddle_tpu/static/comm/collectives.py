"""Collective census walker — the arithmetic under the PT-COMM manifest.

Walks a traced program's jaxpr (``trace_to_program`` retains the
ClosedJaxpr as ``_closed_jaxpr``) and yields one :class:`CollectiveInfo`
per collective equation, recursing containers: ``shard_map`` bodies bind
their mesh axis sizes (read off the equation's ``mesh`` param — an
AbstractMesh at audit time), ``scan`` bodies multiply by trip count,
``while`` bodies count once (unknown trip; the manifest undercounts
these, same convention as PT-COST), ``cond`` counts every branch.

Per-dispatch wire bytes use the ring-algorithm volumes every production
collective library converges on (per participating device, ``n`` = the
product of the named axis sizes, ``b`` = the operand's per-shard bytes):

==================  ==============================  =====================
primitive           wire bytes                      note
==================  ==============================  =====================
psum / pmin / pmax  ``2 (n-1)/n * b``               reduce-scatter+gather
all_gather          ``(n-1) * b``                   b = the local shard
reduce_scatter      ``(n-1)/n * b``                 b = the full input
all_to_all          ``(n-1)/n * b``                 keeps 1/n locally
ppermute            ``b``                           one neighbour send
==================  ==============================  =====================

``psum_invariant`` / ``all_gather_invariant`` (what ``check_vma=True``
traces psum / a replicated-output all_gather as) are normalized to
``psum`` / ``all_gather`` so contracts do not depend on the
``check_vma`` flag; ``pvary`` is a varying-ness *marker* the check
inserts — zero wire bytes, not censused.

Loop-invariance (PT-COMM-002's input) is a taint walk: inside a scan
body the carries and the per-step slices are "varying", the scan consts
are not; an equation's outputs inherit taint from its inputs; a
collective all of whose inputs are untainted re-communicates the same
bytes every iteration and is marked ``loop_invariant``.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Iterator, Optional, Tuple

from ..cost.flops import _aval_of, _inner_jaxprs, _nbytes, closed_jaxpr_of
from .mesh import mesh_axis_sizes

__all__ = ["CollectiveInfo", "COLLECTIVE_PRIMS", "iter_collectives",
           "wire_bytes"]

#: jaxpr primitive names that move bytes between mesh participants
COLLECTIVE_PRIMS = frozenset({
    "psum", "psum_invariant", "pmin", "pmax", "all_gather",
    "all_gather_invariant", "reduce_scatter", "all_to_all", "ppermute",
})

#: normalization: check_vma=True traces the *_invariant spellings
_NORMALIZE = {"psum_invariant": "psum", "all_gather_invariant": "all_gather"}


@dataclass
class CollectiveInfo:
    """One collective equation (possibly nested), censused."""

    prim: str                     # normalized (psum_invariant -> psum)
    raw_prim: str
    axes: Tuple[str, ...]         # mesh axes the collective spans
    group_size: int               # product of the named axes' sizes
    payload_bytes: float          # first operand's (per-shard) bytes
    bytes_wire: float             # per-device per-dispatch wire bytes
    mult: int                     # static execution multiplier (scan len)
    scope: str                    # "/shard_map/scan" nesting path
    loop_invariant: bool = False  # inside a scan/while, inputs all consts
    axis_sizes: Dict[str, int] = field(default_factory=dict)
    eqn: object = None

    @property
    def total_wire_bytes(self) -> float:
        return self.bytes_wire * self.mult


def wire_bytes(prim: str, payload_bytes: float, group_size: int) -> float:
    """Ring-algorithm per-device wire bytes for one dispatch (table in
    the module docstring). ``group_size <= 1`` moves nothing."""
    n = max(int(group_size), 1)
    if n <= 1:
        return 0.0
    b = float(payload_bytes)
    p = _NORMALIZE.get(prim, prim)
    if p in ("psum", "pmin", "pmax"):
        return 2.0 * (n - 1) / n * b
    if p == "all_gather":
        return (n - 1.0) * b
    if p in ("reduce_scatter", "all_to_all"):
        return (n - 1.0) / n * b
    if p == "ppermute":
        return b
    return 0.0


def _axes_of(params) -> Tuple[str, ...]:
    """Axis names off a collective's params: psum-family uses ``axes``,
    the rest ``axis_name`` (str or tuple)."""
    ax = params.get("axes", None)
    if ax is None:
        ax = params.get("axis_name", ())
    if isinstance(ax, (str, int)):
        ax = (ax,)
    return tuple(str(a) for a in ax)


def _is_literal(v) -> bool:
    return hasattr(v, "val") and not hasattr(v, "count")


def _tainted(invars, taint) -> bool:
    return any(taint.get(v, False) for v in invars if not _is_literal(v))


def _mark(outvars, taint, value: bool) -> None:
    if taint is None:
        return
    for v in outvars:
        taint[v] = value


def _walk(jaxpr, mult: int, scope: str, sizes: Dict[str, int],
          taint: Optional[dict]) -> Iterator[CollectiveInfo]:
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in inner.eqns:
        prim = eqn.primitive.name
        t_in = _tainted(eqn.invars, taint) if taint is not None else False

        if prim == "shard_map":
            sub_sizes = dict(sizes)
            sub_sizes.update(mesh_axis_sizes(eqn.params.get("mesh")))
            sub = eqn.params.get("jaxpr")
            sub_taint = None
            if taint is not None:
                sj = getattr(sub, "jaxpr", sub)
                sub_taint = {v: taint.get(cv, False)
                             for v, cv in zip(sj.invars, eqn.invars)
                             if not _is_literal(cv)}
            yield from _walk(sub, mult, scope + "/shard_map", sub_sizes,
                             sub_taint)
            _mark(eqn.outvars, taint, t_in)
            continue

        if prim == "scan":
            length = int(eqn.params.get("length", 1) or 1)
            n_consts = int(eqn.params.get("num_consts", 0))
            sub = eqn.params["jaxpr"]
            sj = getattr(sub, "jaxpr", sub)
            # taint starts fresh at every scan: consts are invariant FOR
            # THIS loop whatever they were outside; carries/xs vary
            sub_taint = {v: i >= n_consts for i, v in enumerate(sj.invars)}
            yield from _walk(sub, mult * length, scope + "/scan", sizes,
                             sub_taint)
            _mark(eqn.outvars, taint, True)
            continue

        if prim == "while":
            cn = int(eqn.params.get("cond_nconsts", 0))
            bn = int(eqn.params.get("body_nconsts", 0))
            for key, nconsts, sfx in (("cond_jaxpr", cn, ".cond"),
                                      ("body_jaxpr", bn, ".body")):
                sub = eqn.params.get(key)
                if sub is None:
                    continue
                sj = getattr(sub, "jaxpr", sub)
                sub_taint = {v: i >= nconsts
                             for i, v in enumerate(sj.invars)}
                yield from _walk(sub, mult, scope + "/while" + sfx, sizes,
                                 sub_taint)
            _mark(eqn.outvars, taint, True)
            continue

        subs = _inner_jaxprs(eqn)
        if subs:
            call_in = eqn.invars[1:] if prim == "cond" else eqn.invars
            for sub, factor, sfx in subs:
                sub_taint = None
                if taint is not None:
                    sj = getattr(sub, "jaxpr", sub)
                    if len(sj.invars) == len(call_in):
                        sub_taint = {v: (taint.get(cv, False)
                                         if not _is_literal(cv) else False)
                                     for v, cv in zip(sj.invars, call_in)}
                    else:       # unknown calling convention: no false
                        sub_taint = {v: True for v in sj.invars}  # positives
                yield from _walk(sub, mult * factor,
                                 scope + "/" + prim + sfx, sizes, sub_taint)
            _mark(eqn.outvars, taint, t_in)
            continue

        if prim in COLLECTIVE_PRIMS:
            axes = _axes_of(eqn.params)
            n = 1
            axis_sizes = {}
            for a in axes:
                s = int(sizes.get(a, 1))
                axis_sizes[a] = s
                n *= s
            shape, dtype = _aval_of(eqn.invars[0]) if eqn.invars else ((),
                                                                       None)
            payload = _nbytes(shape, dtype)
            yield CollectiveInfo(
                prim=_NORMALIZE.get(prim, prim), raw_prim=prim, axes=axes,
                group_size=n, payload_bytes=payload,
                bytes_wire=wire_bytes(prim, payload, n), mult=mult,
                scope=scope,
                loop_invariant=(taint is not None and not t_in),
                axis_sizes=axis_sizes, eqn=eqn)
        _mark(eqn.outvars, taint, t_in)


def iter_collectives(program_or_jaxpr,
                     mesh: Optional[Dict[str, int]] = None
                     ) -> Iterator[CollectiveInfo]:
    """Yield every collective in a traced Program / (Closed)Jaxpr.
    ``mesh`` seeds axis sizes for collectives OUTSIDE any shard_map
    (pmap-style programs); shard_map equations bind their own mesh."""
    closed = closed_jaxpr_of(program_or_jaxpr)
    if closed is None:
        return
    yield from _walk(closed, 1, "", dict(mesh or {}), None)
