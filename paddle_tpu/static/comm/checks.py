"""PT-COMM checks — diagnostics over a traced program's collective census.

Five code classes (docs/STATIC_ANALYSIS.md, PT-COMM section), enforced by
tools/audit_collectives.py against tools/collective_baseline.json:

- PT-COMM-001  accidental full replication: a LARGE operand entering a
               shard_map with no sharded dim while the same equation
               shards its siblings — every device holds (and the
               enclosing dispatch moves) the whole buffer.
- PT-COMM-002  loop-invariant collective inside a scan/while body: all
               of its inputs are loop constants, so the same bytes are
               re-gathered every iteration — hoist it out of the loop.
- PT-COMM-003  superlinear comm-byte scaling with mesh size across a
               traced width pair (the mesh-scaling law, manifest.py).
- PT-COMM-004  an ``all_gather`` whose output is summed over the
               gathered dimension — a reduce_scatter/psum_scatter
               contract moves ``(n-1)/n`` of the bytes instead of
               ``(n-1)``; matmul-reduction variants differ the same way.
- PT-COMM-005  baseline contract drift / unbaselined sharded program /
               a program breaking its explicit ``unsharded`` contract.

Every diagnostic carries a line-number-free ``finding_id``
(``CODE:program:detail``) so baseline waivers survive refactors — the
PT-RACE/PT-COST baseline discipline.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

from ..analysis.diagnostics import Diagnostic, Severity
from ..cost.flops import _aval_of, _inner_jaxprs, _nbytes, closed_jaxpr_of
from .collectives import iter_collectives
from .manifest import CommManifest, mesh_scaling_verdict
from .mesh import mesh_axis_sizes

__all__ = ["check_replication", "check_loop_invariant_collectives",
           "check_mesh_scaling", "check_gather_reduce",
           "check_comm_contract"]

_ANALYZER = "CollectiveCommAuditor"

#: PT-COMM-001 only fires on operands at least this large — small
#: replicated scalars/tables are the normal case, not a defect
_REPLICATION_MIN_BYTES = 1 << 20


def _diag(code, severity, message, program, detail, prim=None):
    d = Diagnostic(code=code, severity=Severity(severity), message=message,
                   op_type=prim, analyzer=_ANALYZER)
    d.finding_id = f"{code}:{program}:{detail}"
    return d


def _shard_map_eqns(closed):
    """Every shard_map equation, recursing containers (scope-labelled)."""
    out = []

    def scan_scope(jaxpr, scope):
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            if prim == "shard_map":
                out.append((eqn, scope))
            for sub, _, sfx in _inner_jaxprs(eqn):
                scan_scope(getattr(sub, "jaxpr", sub),
                           scope + "/" + prim + sfx)
    if closed is not None:
        scan_scope(getattr(closed, "jaxpr", closed), "")
    return out


def check_replication(program_or_jaxpr, name: str = "program",
                      min_bytes: int = _REPLICATION_MIN_BYTES
                      ) -> List[Diagnostic]:
    """PT-COMM-001: for each shard_map over a >1-device mesh whose
    ``in_specs`` shard at least one operand, flag every operand of
    ``min_bytes`` or more entering with NO sharded dim (an empty spec,
    or only size-1 axes) — full replication that is almost always an
    annotation accident on a mesh that shards its consumers."""
    findings: List[Diagnostic] = []
    for eqn, scope in _shard_map_eqns(closed_jaxpr_of(program_or_jaxpr)):
        sizes = mesh_axis_sizes(eqn.params.get("mesh"))
        world = 1
        for v in sizes.values():
            world *= max(int(v), 1)
        if world <= 1:
            continue
        in_specs = eqn.params["in_specs"]

        def effective(spec):
            # a PartitionSpec entry is None, an axis name or a tuple of them
            return any(sizes.get(str(a), 1) > 1 for entry in spec
                       if entry is not None
                       for a in (entry if isinstance(entry, tuple)
                                 else (entry,)))
        if not any(effective(sp) for sp in in_specs):
            continue
        for i, sp in enumerate(in_specs):
            if effective(sp):
                continue
            shape, dtype = _aval_of(eqn.invars[i])
            nb = _nbytes(shape, dtype)
            if nb < min_bytes:
                continue
            findings.append(_diag(
                "PT-COMM-001", Severity.ERROR,
                f"operand {i} of shard_map{scope or ''} "
                f"({'x'.join(map(str, shape))} {dtype}, {nb:.3g} B) enters "
                f"fully REPLICATED while the mesh {sizes} shards its "
                f"siblings — every device holds the whole buffer; shard it "
                f"(or waive with a justification if replication is the "
                f"contract)", name,
                f"replicated:in{i}:{'x'.join(map(str, shape))}",
                prim="shard_map"))
    return findings


def check_loop_invariant_collectives(program_or_jaxpr,
                                     name: str = "program"
                                     ) -> List[Diagnostic]:
    """PT-COMM-002: collectives inside a scan/while body whose inputs are
    all loop constants — the same bytes cross the wire every iteration.
    Hoist the collective above the loop (gather once, close over the
    result)."""
    findings: List[Diagnostic] = []
    for c in iter_collectives(program_or_jaxpr):
        if not c.loop_invariant:
            continue
        if "/scan" not in c.scope and "/while" not in c.scope:
            continue
        times = f"{c.mult}x" if c.mult > 1 else "every iteration"
        findings.append(_diag(
            "PT-COMM-002", Severity.ERROR,
            f"loop-invariant '{c.prim}' over {c.axes}{c.scope}: all inputs "
            f"are loop constants, so {c.bytes_wire:.3g} wire B are "
            f"re-communicated {times} — hoist the collective out of the "
            f"loop body", name, f"{c.prim}{c.scope}", prim=c.raw_prim))
    return findings


def check_gather_reduce(program_or_jaxpr,
                        name: str = "program") -> List[Diagnostic]:
    """PT-COMM-004: ``all_gather`` feeding a ``reduce_sum`` over the
    gathered dimension (directly or through a dtype convert) — the
    gather moves ``(n-1) * b`` where a reduce_scatter (+ small gather if
    the full result is truly needed) moves ``(n-1)/n * b``. The classic
    Megatron-style contract miss."""
    findings: List[Diagnostic] = []
    closed = closed_jaxpr_of(program_or_jaxpr)
    if closed is None:
        return findings

    def scan_scope(jaxpr, scope):
        gathers = {}   # id(var) -> (gathered dim, raw eqn)
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            if prim == "all_gather":
                gathers[id(eqn.outvars[0])] = (
                    int(eqn.params.get("all_gather_dimension", 0)), eqn)
            elif prim == "convert_element_type" and eqn.invars:
                hit = gathers.get(id(eqn.invars[0]))
                if hit is not None:
                    gathers[id(eqn.outvars[0])] = hit
            elif prim == "reduce_sum":
                axes = tuple(int(a) for a in eqn.params.get("axes", ()))
                for v in eqn.invars:
                    hit = gathers.get(id(v))
                    if hit is not None and hit[0] in axes:
                        g_axes = hit[1].params.get("axis_name", ())
                        findings.append(_diag(
                            "PT-COMM-004", Severity.ERROR,
                            f"all_gather over {g_axes}{scope or ''} is "
                            f"summed over its gathered dim {hit[0]} — a "
                            f"reduce_scatter contract moves (n-1)/n of the "
                            f"bytes instead of (n-1); use psum_scatter (or "
                            f"psum if the full result must be replicated)",
                            name, f"all_gather+reduce_sum{scope}",
                            prim="all_gather"))
            for sub, _, sfx in _inner_jaxprs(eqn):
                scan_scope(getattr(sub, "jaxpr", sub),
                           scope + "/" + prim + sfx)
    scan_scope(getattr(closed, "jaxpr", closed), "")
    return findings


def check_mesh_scaling(manifests: Sequence[CommManifest],
                       tol: float = 0.25) -> List[Diagnostic]:
    """PT-COMM-003: apply :func:`mesh_scaling_verdict` over a width pair
    and flag a superlinear verdict."""
    rec = mesh_scaling_verdict(manifests, tol=tol)
    if rec["verdict"] == "superlinear":
        name = manifests[0].program.split("@")[0]
        return [_diag(
            "PT-COMM-003", Severity.ERROR,
            f"program family '{name}' scales SUPERLINEARLY in mesh size "
            f"(worst ring-envelope ratio {rec['worst_ring_ratio']}x over "
            f"widths {rec['widths']}; wire bytes {rec['comm_bytes']}, "
            f"collective eqns {rec['collective_eqns']}) — an O(mesh^2) "
            f"term in the collective plan", name, "superlinear")]
    return []


def check_comm_contract(manifest: CommManifest,
                        baseline: Optional[Dict]) -> List[Diagnostic]:
    """PT-COMM-005: the baseline contract. A program declaring
    ``unsharded: true`` must trace zero collectives; a program whose
    baseline records a mesh census must NOT silently revert to unsharded
    (or lose a recorded collective primitive) — sharding regressions gate
    exactly like sharding drift; an unbaselined program is itself a
    finding; per-primitive counts and total wire bytes may only change
    through a reviewed refresh."""
    name = manifest.program
    findings: List[Diagnostic] = []
    unsharded = manifest.unsharded or bool((baseline or {}).get("unsharded"))
    if unsharded and manifest.collective_eqns > 0:
        findings.append(_diag(
            "PT-COMM-005", Severity.ERROR,
            f"program '{name}' declares the unsharded contract but traces "
            f"{manifest.collective_eqns} collective(s) "
            f"({dict(manifest.collectives)}) — flip the contract (spec + "
            f"baseline) together with the sharding change",
            name, "unsharded-contract"))
    if not baseline:
        findings.append(_diag(
            "PT-COMM-005", Severity.ERROR,
            f"program '{name}' has no entry in the collective baseline — "
            f"record it (tools/audit_collectives.py --write-baseline) and "
            f"review the manifest", name, "unbaselined"))
        return findings
    base_counts = baseline.get("collectives", {}) or {}
    base_mesh = baseline.get("mesh") or {}
    if base_mesh and manifest.unsharded:
        findings.append(_diag(
            "PT-COMM-005", Severity.ERROR,
            f"program '{name}' reverted to the unsharded contract but its "
            f"baseline records a mesh census "
            f"({'x'.join(f'{k}{v}' for k, v in sorted(base_mesh.items()))},"
            f" {dict(base_counts)}) — the program silently LOST its "
            f"sharding; restore it or refresh the baseline with a "
            f"justification", name, "lost-sharding"))
    # elastic degrade exemption (docs/RESILIENCE.md "Elastic serving
    # mesh"): a baseline may record `degrade_widths` — the narrower tp
    # widths its PT-SRV-008 reshard path legitimately serves at. A
    # STILL-SHARDED manifest at a recorded degrade width is a planned
    # partial shrink: its per-primitive counts and wire bytes scale with
    # the width, so the count/drift/bytes gates below would misfire.
    # Losing sharding ENTIRELY is never exempt — that already gated as
    # lost-sharding above.
    if not manifest.unsharded:
        degrade_widths = {int(w) for w in
                          (baseline.get("degrade_widths") or ())}
        width = int(manifest.width
                    or (manifest.mesh or {}).get("tp") or 0)
        base_width = int(baseline.get("width")
                         or (base_mesh or {}).get("tp") or 0)
        if (degrade_widths and width and base_width
                and width != base_width and width in degrade_widths):
            return findings
    for prim, want in sorted(base_counts.items()):
        if int(want) and not manifest.collectives.get(prim, 0):
            findings.append(_diag(
                "PT-COMM-005", Severity.ERROR,
                f"'{name}' traces zero '{prim}' collective(s) but its "
                f"recorded contract expects {int(want)} — the collective "
                f"plan silently dropped a primitive; review and refresh "
                f"the baseline", name, f"lost-collective:{prim}",
                prim=prim))
    for prim, have in sorted(manifest.collectives.items()):
        want = base_counts.get(prim)
        if want is None:
            findings.append(_diag(
                "PT-COMM-005", Severity.ERROR,
                f"'{name}' now traces {have} '{prim}' collective(s) — a "
                f"primitive absent from its recorded contract; review and "
                f"refresh the baseline", name, f"new-collective:{prim}",
                prim=prim))
        elif have > int(want):
            findings.append(_diag(
                "PT-COMM-005", Severity.ERROR,
                f"'{prim}' count grew {int(want)} -> {have} vs the "
                f"recorded contract for '{name}' — review the new "
                f"collective(s) or refresh the baseline with a "
                f"justification", name, f"{prim}-drift", prim=prim))
    base_bytes = float(baseline.get("comm_bytes") or 0.0)
    if base_bytes and manifest.comm_bytes > 1.5 * base_bytes:
        findings.append(_diag(
            "PT-COMM-005", Severity.ERROR,
            f"wire bytes grew {base_bytes:.3g} -> {manifest.comm_bytes:.3g}"
            f" (>1.5x) vs the recorded contract for '{name}' — the "
            f"collective plan blew up; review and refresh the baseline",
            name, "comm-bytes-blowup"))
    return findings
