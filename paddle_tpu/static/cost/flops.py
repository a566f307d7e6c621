"""Recursive jaxpr cost walker — the arithmetic under the PT-COST manifest.

A traced hot-path program (``trace_to_program`` keeps the ClosedJaxpr on the
imported Program as ``_closed_jaxpr``) is walked equation by equation,
RECURSING into container primitives — ``scan`` bodies multiply by their
trip count, ``jit``/``remat``/``custom_*_call`` inline at 1x, ``while``
bodies count ONCE (trip count is data-dependent; the manifest records how
many unknown-trip loops the estimate leaves out), ``cond`` counts every
branch (a deliberate upper bound). Each equation yields an :class:`EqnInfo`
with a roofline-style FLOP estimate and an HBM byte-traffic estimate
(operand + result bytes — reuse inside XLA fusions is invisible at jaxpr
level, so treat both as *estimates for comparison across revisions of the
same program*, not absolute hardware counters; that is exactly what the
baseline gate needs).

FLOP conventions (documented in docs/STATIC_ANALYSIS.md): dot_general =
2*B*M*N*K from its dimension numbers; conv = 2 * out_elems * (C_in/groups *
prod(kernel_spatial)); reductions = input elems; sort/top_k = n*ceil(log2
(extent)); every other elementwise op = 1 FLOP per output element
(transcendentals deliberately NOT weighted — the census is a drift
detector, not a cycle model); pure data movement (reshape/transpose/
gather/scatter/convert/...) = 0 FLOPs, bytes only.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Iterator, List, Optional, Tuple

__all__ = ["EqnInfo", "iter_eqn_costs", "closed_jaxpr_of", "FAMILIES"]

#: manifest flop/byte breakdown buckets
FAMILIES = ("dot", "conv", "elementwise", "reduce", "sort", "rng",
            "gather", "scatter", "shape", "callback", "container", "other")

#: container primitives — cost lives in their inner jaxprs
_CONTAINER_KEYS = {
    "scan": ("jaxpr",),
    "shard_map": ("jaxpr",),
    "while": ("cond_jaxpr", "body_jaxpr"),
    "cond": ("branches",),
    "jit": ("jaxpr",),
    "xla_call": ("call_jaxpr",),
    "closed_call": ("call_jaxpr",),
    "core_call": ("call_jaxpr",),
    "remat2": ("jaxpr",),
    "remat": ("jaxpr",),
    "checkpoint": ("jaxpr",),
    "custom_jvp_call": ("call_jaxpr",),
    "custom_vjp_call": ("call_jaxpr",),
    "custom_vjp_call_jaxpr": ("fun_jaxpr",),
}

#: host-sync / host-transfer primitives inside a supposedly device-resident
#: program (PT-COST-002; the source-level sibling is PT-TRACE-004)
HOST_SYNC_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "outfeed", "infeed", "device_put",
})

_ZERO_FLOP = frozenset({
    "reshape", "transpose", "broadcast_in_dim", "convert_element_type",
    "slice", "squeeze", "concatenate", "pad", "rev", "copy", "iota",
    "stop_gradient", "gather", "dynamic_slice", "dynamic_update_slice",
    "bitcast_convert_type", "expand_dims", "real", "imag",
})

_REDUCE = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod", "reduce_and",
    "reduce_or", "reduce_xor", "argmax", "argmin", "cumsum", "cumprod",
    "cummax", "cummin", "cumlogsumexp", "reduce_precision",
})

_RNG = frozenset({
    "random_bits", "random_seed", "random_fold_in", "random_wrap",
    "random_unwrap", "threefry2x32", "random_gamma",
})


@dataclass
class EqnInfo:
    """One walked equation (possibly nested): classification + cost."""

    prim: str
    family: str
    flops: float                  # per single execution of this eqn
    bytes: float                  # operand + result bytes, one execution
    mult: int                     # static execution multiplier (scan lengths)
    scope: str                    # "/scan" nesting path, "" at top level
    out_dtypes: Tuple[str, ...] = ()
    in_dtypes: Tuple[str, ...] = ()
    params: Optional[dict] = None
    eqn: object = None            # the jax eqn (dataflow checks); None for
    #                               op-level fallback walks
    is_container: bool = False

    @property
    def total_flops(self) -> float:
        return self.flops * self.mult

    @property
    def total_bytes(self) -> float:
        return self.bytes * self.mult


def _aval_of(x):
    """(shape, dtype) of a jaxpr var / Literal / Program arg (Variable or
    captured Tensor expose ``_data``; python scalars are 0-d)."""
    aval = getattr(x, "aval", None)
    if aval is not None:
        return tuple(getattr(aval, "shape", ())), getattr(aval, "dtype", None)
    data = getattr(x, "_data", None)
    if data is not None:
        return tuple(getattr(data, "shape", ())), getattr(data, "dtype", None)
    shape = getattr(x, "shape", None)
    if shape is not None:
        return tuple(shape), getattr(x, "dtype", None)
    return (), None


def _nbytes(shape, dtype) -> float:
    n = 1
    for s in shape:
        n *= max(int(s), 0)
    try:
        item = dtype.itemsize if dtype is not None else 4
    except Exception:   # jax extended dtypes (PRNG keys) — treat as 4 B
        item = 4
    return float(n * item)


def _nelems(shape) -> float:
    n = 1
    for s in shape:
        n *= max(int(s), 0)
    return float(n)


def _dot_flops(params, in_avals) -> float:
    (lc, rc), (lb, rb) = params["dimension_numbers"]
    lshape, rshape = in_avals[0][0], in_avals[1][0]
    batch = 1
    for d in lb:
        batch *= lshape[d]
    k = 1
    for d in lc:
        k *= lshape[d]
    m = 1
    for i, s in enumerate(lshape):
        if i not in lb and i not in lc:
            m *= s
    n = 1
    for i, s in enumerate(rshape):
        if i not in rb and i not in rc:
            n *= s
    return 2.0 * batch * m * n * k


def _conv_flops(params, in_avals, out_avals) -> float:
    dn = params["dimension_numbers"]
    rshape = in_avals[1][0]
    rhs_spec = getattr(dn, "rhs_spec", None)
    if rhs_spec is None:        # defensive: count as a dense product
        return 2.0 * _nelems(out_avals[0][0]) * _nelems(rshape)
    in_feat = rshape[rhs_spec[1]]
    kernel = 1
    for d in rhs_spec[2:]:
        kernel *= rshape[d]
    groups = int(params.get("feature_group_count", 1)) or 1
    return 2.0 * _nelems(out_avals[0][0]) * (in_feat / groups) * kernel


def _classify(prim: str) -> str:
    if prim in ("dot_general",):
        return "dot"
    if prim == "conv_general_dilated":
        return "conv"
    if prim in HOST_SYNC_PRIMS:
        return "callback"
    if prim in _REDUCE:
        return "reduce"
    if prim in ("sort", "top_k"):
        return "sort"
    if prim in _RNG:
        return "rng"
    if prim == "gather" or prim == "dynamic_slice":
        return "gather"
    if prim.startswith("scatter") or prim == "dynamic_update_slice":
        return "scatter"
    if prim in _ZERO_FLOP:
        return "shape"
    if prim in _CONTAINER_KEYS:
        return "container"
    return "elementwise"


def _eqn_flops(prim: str, family: str, params, in_avals, out_avals) -> float:
    if family in ("shape", "gather", "scatter", "callback", "rng",
                  "container"):
        if family == "rng" and out_avals:
            return _nelems(out_avals[0][0])
        return 0.0
    if family == "dot":
        return _dot_flops(params, in_avals)
    if family == "conv":
        return _conv_flops(params, in_avals, out_avals)
    if family == "reduce":
        return _nelems(in_avals[0][0]) if in_avals else 0.0
    if family == "sort":
        shape = in_avals[0][0] if in_avals else ()
        if not shape:
            return 0.0
        dim = params.get("dimension", len(shape) - 1) \
            if params else len(shape) - 1
        try:
            extent = shape[dim]
        except Exception:
            extent = shape[-1]
        return _nelems(shape) * max(1.0, math.log2(max(int(extent), 2)))
    # elementwise / other: one flop per output element
    return _nelems(out_avals[0][0]) if out_avals else 0.0


def _inner_jaxprs(eqn) -> List[Tuple[object, int, str]]:
    """(inner jaxpr, multiplier, scope suffix) triples for a container."""
    name = eqn.primitive.name
    keys = _CONTAINER_KEYS.get(name)
    if not keys:
        return []
    out = []
    if name == "scan":
        length = int(eqn.params.get("length", 1) or 1)
        out.append((eqn.params["jaxpr"], length, ""))
    elif name == "cond":
        for i, br in enumerate(eqn.params.get("branches", ()) or ()):
            out.append((br, 1, f".branch{i}"))
    else:
        for k in keys:
            sub = eqn.params.get(k)
            if sub is not None:
                sfx = "" if len(keys) == 1 else "." + k.split("_")[0]
                out.append((sub, 1, sfx))
    return out


def _walk_jaxpr(jaxpr, mult: int, scope: str) -> Iterator[EqnInfo]:
    inner = getattr(jaxpr, "jaxpr", jaxpr)   # ClosedJaxpr or Jaxpr
    for eqn in inner.eqns:
        prim = eqn.primitive.name
        in_avals = [_aval_of(v) for v in eqn.invars]
        out_avals = [_aval_of(v) for v in eqn.outvars]
        family = _classify(prim)
        subs = _inner_jaxprs(eqn)
        if subs:
            yield EqnInfo(
                prim=prim, family="container", flops=0.0, bytes=0.0,
                mult=mult, scope=scope, params=eqn.params, eqn=eqn,
                is_container=True,
                out_dtypes=tuple(str(d) for _, d in out_avals),
                in_dtypes=tuple(str(d) for _, d in in_avals))
            for sub, factor, sfx in subs:
                yield from _walk_jaxpr(sub, mult * factor,
                                       scope + "/" + prim + sfx)
            continue
        flops = _eqn_flops(prim, family, eqn.params, in_avals, out_avals)
        byt = sum(_nbytes(s, d) for s, d in in_avals) \
            + sum(_nbytes(s, d) for s, d in out_avals)
        yield EqnInfo(
            prim=prim, family=family, flops=flops, bytes=byt, mult=mult,
            scope=scope, params=eqn.params, eqn=eqn,
            out_dtypes=tuple(str(d) for _, d in out_avals),
            in_dtypes=tuple(str(d) for _, d in in_avals))


def _walk_program_ops(program) -> Iterator[EqnInfo]:
    """Fallback for hand-recorded Programs (no retained jaxpr): per-op
    costs via the ``trace_to_program`` kernel back-links where present;
    ops recorded through arbitrary python callables classify ``other``
    with IO bytes only (the walker cannot see inside them)."""
    for op in program.global_block().ops:
        prim = getattr(op.fn, "_primitive", None)
        params = getattr(op.fn, "_prim_params", None) or {}
        name = prim.name if prim is not None else op.type
        in_avals = [_aval_of(a) for a in list(op.inputs) + list(op.captured)]
        out_avals = [_aval_of(v) for v in op.outputs]
        family = _classify(name) if prim is not None else "other"
        flops = _eqn_flops(name, family, params, in_avals, out_avals) \
            if prim is not None else 0.0
        byt = sum(_nbytes(s, d) for s, d in in_avals) \
            + sum(_nbytes(s, d) for s, d in out_avals)
        yield EqnInfo(
            prim=name, family=family, flops=flops, bytes=byt, mult=1,
            scope="", params=params,
            out_dtypes=tuple(str(d) for _, d in out_avals),
            in_dtypes=tuple(str(d) for _, d in in_avals))


def closed_jaxpr_of(program_or_jaxpr):
    """The retained ClosedJaxpr of a traced import, or the argument itself
    when it already is one (``None`` for hand-recorded Programs)."""
    if hasattr(program_or_jaxpr, "jaxpr") or hasattr(program_or_jaxpr,
                                                     "eqns"):
        return program_or_jaxpr
    return getattr(program_or_jaxpr, "_closed_jaxpr", None)


def iter_eqn_costs(program_or_jaxpr) -> Iterator[EqnInfo]:
    """Walk a traced Program (``trace_to_program`` import) or a raw
    (Closed)Jaxpr, yielding one :class:`EqnInfo` per equation, containers
    recursed."""
    closed = closed_jaxpr_of(program_or_jaxpr)
    if closed is not None:
        yield from _walk_jaxpr(closed, 1, "")
    else:
        yield from _walk_program_ops(program_or_jaxpr)
