"""Collective-communication gate (PT-COMM — docs/STATIC_ANALYSIS.md):
trace every registered mesh-sharded program under a symbolic
``AbstractMesh`` (NO XLA compile, no devices — pure ``make_jaxpr``
through ``static.analysis.trace_to_program``; a compile counter enforces
this and the gate fails if anything compiled) and audit its collective
census against the reviewed baseline (tools/collective_baseline.json).

What PT-COST is for device-program cost, this is for the WIRE: the
registry covers the train-step collective contract at each recorded
MULTICHIP_r01–r05 mesh shape, the ring-attention and MoE dispatch/
combine spmd-rule programs traced at two mesh widths (the mesh-scaling
law), and the single-device serving programs (mega-step, prefill chunk,
spec verify — reusing audit_program_cost's recorders) under an explicit
``unsharded: true`` contract that ROADMAP item 1's sharding PR must
flip together with its sharding change. The audit catches, before any
multi-chip run:

- PT-COMM-001  a large operand entering shard_map fully replicated
               while the mesh shards its siblings
- PT-COMM-002  a loop-invariant collective inside a scan/while body
               (the same bytes re-gathered every step)
- PT-COMM-003  comm bytes growing superlinearly with mesh size across
               a traced width pair
- PT-COMM-004  all_gather feeding a reduce over the gathered dim where
               a reduce_scatter contract moves (n-1)/n of the bytes
- PT-COMM-005  contract drift / unbaselined program / broken unsharded
               contract

Exit 0 iff every error-severity finding is fixed or covered by a
reviewed waiver WITH a justification (the PT-RACE baseline discipline).

Usage:
    JAX_PLATFORMS=cpu python tools/audit_collectives.py     # full gate
    python tools/audit_collectives.py --program mesh_train_step@r01
    python tools/audit_collectives.py --write-baseline      # refresh
    python tools/audit_collectives.py --inject loop_regather
    python tools/audit_collectives.py --selftest            # all 5 classes
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

import _selftest

ROOT = _selftest.bootstrap()

BASELINE_PATH = os.path.join(ROOT, "tools", "collective_baseline.json")

import jax  # noqa: E402
import numpy as np  # noqa: E402

DEFECTS = ("replicated_param", "loop_regather", "superlinear_comm",
           "gather_reduce", "contract_drift", "serving_unsharded")

EXPECTED_CODE = {
    "replicated_param": "PT-COMM-001",
    "loop_regather": "PT-COMM-002",
    "superlinear_comm": "PT-COMM-003",
    "gather_reduce": "PT-COMM-004",
    "contract_drift": "PT-COMM-005",
    "serving_unsharded": "PT-COMM-005",
}

#: the recorded MULTICHIP_r01–r05 dryrun mesh shapes (size-1 axes kept
#: for the record; the contract program drops them)
MULTICHIP_MESHES = {
    "r01": {"dp": 1, "fsdp": 1, "sep": 2, "tp": 2, "pp": 2},   # primary
    "r02": {"dp": 2, "fsdp": 2, "sep": 1, "tp": 1, "pp": 2},   # hybrid
    "r03": {"dp": 4, "fsdp": 1, "sep": 1, "tp": 1, "pp": 2},   # zero-bubble
    "r04": {"ep": 4, "fsdp": 2},                               # MoE
    "r05": {"dp": 2, "tp": 4},                                 # tp4
}

#: mesh widths each scaling family is traced at (PT-COMM-003 law)
SCALING_WIDTHS = (2, 4)

#: per-process count of XLA compiles — must stay 0 for the whole gate
_COMPILES = []


def install_compile_guard():
    """Count backend compiles so 'zero XLA compiles' is enforced, not
    asserted in a docstring. jax-internal hook — if the symbol moves on
    a future jax, the guard degrades to 'untracked' rather than lying."""
    try:
        from jax._src import compiler as _jc
    except Exception:
        return False
    orig = _jc.backend_compile

    def counting(*a, **kw):
        _COMPILES.append(1)
        return orig(*a, **kw)
    _jc.backend_compile = counting
    return True


def _spec(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), np.dtype(dtype))


# ---------------------------------------------------------------------------
# registry — each recorder returns (Program, CommPathSpec)
# ---------------------------------------------------------------------------

def record_mesh_train_step(key: str):
    """The train-step collective contract at one recorded MULTICHIP mesh
    shape (distributed.auto_parallel.comm_programs.train_step_comm)."""
    from paddle_tpu.distributed.auto_parallel import train_step_comm
    from paddle_tpu.static.analysis import trace_to_program
    from paddle_tpu.static.comm import CommPathSpec

    fn, structs, names, axes = train_step_comm(MULTICHIP_MESHES[key])
    prog = trace_to_program(fn, *structs, input_names=names)
    spec = CommPathSpec(
        f"mesh_train_step@{key}", mesh=axes,
        notes=f"MULTICHIP_{key} dryrun shape {MULTICHIP_MESHES[key]} — "
              "Megatron/FSDP/Ulysses/MoE/pp contract step")
    return prog, spec


def record_tp_train(width: int):
    """The tensor-parallel train step at a tp width (the r05 family) —
    one leg of the mesh-scaling law."""
    from paddle_tpu.distributed.auto_parallel import train_step_comm
    from paddle_tpu.static.analysis import trace_to_program
    from paddle_tpu.static.comm import CommPathSpec

    fn, structs, names, axes = train_step_comm({"dp": 2, "tp": width})
    prog = trace_to_program(fn, *structs, input_names=names)
    spec = CommPathSpec(f"tp_train@{width}", mesh=axes, width=2 * width,
                        notes="dp2 x tp-width Megatron step (r05 family)")
    return prog, spec


def record_flash_ring(width: int):
    """Ring (flash) attention under a sep-axis mesh — the SURVEY
    flash-attention spmd-rule program (ops/ring_attention.py, zigzag
    layout: 2(n-1) ppermutes of the local KV chunk)."""
    from paddle_tpu.ops.ring_attention import ring_attention
    from paddle_tpu.static.analysis import trace_to_program
    from paddle_tpu.static.comm import CommPathSpec, abstract_mesh

    mesh = abstract_mesh({"sep": width})
    sh = _spec((2, 32, 2, 8), "bfloat16")      # [B, S, H, D], S % 2n == 0
    prog = trace_to_program(
        lambda q, k, v: ring_attention(q, k, v, mesh, axis_name="sep"),
        sh, sh, sh, input_names=["q", "k", "v"])
    spec = CommPathSpec(f"flash_ring@{width}", mesh={"sep": width},
                        width=width,
                        notes="zigzag ring attention, causal, bf16")
    return prog, spec


def record_moe_combine(width: int):
    """MoE token dispatch/combine under an ep-axis mesh — the SURVEY
    moe_combine spmd-rule program (two all_to_alls through
    distributed.utils.moe_utils)."""
    from paddle_tpu.distributed.auto_parallel import moe_combine_comm
    from paddle_tpu.static.analysis import trace_to_program
    from paddle_tpu.static.comm import CommPathSpec

    fn, structs, names, axes = moe_combine_comm(width)
    prog = trace_to_program(fn, *structs, input_names=names)
    spec = CommPathSpec(f"moe_combine@{width}", mesh=axes, width=width,
                        notes="global_scatter -> expert FFN -> "
                              "global_gather")
    return prog, spec


@contextlib.contextmanager
def _compile_free_setup():
    """Build the serving recorders' concrete state (weights, KV pools,
    tables) on numpy stand-ins: the auditor only ever reads shapes and
    dtypes off those buffers — their values are dead — and eager jax
    array creation would cost one tiny XLA compile per init op, which
    the zero-compile guard (rightly) fails. Every stub delegates to the
    real function the moment a tracer is involved, so the tracing the
    recorders do under this context is untouched; numpy results inside
    a trace are ordinary constants. Dtypes are canonicalized to jax's
    x32 defaults so the traced programs are bit-identical."""
    import jax.numpy as jnp

    def canon(a):
        fix = {np.dtype(np.int64): np.int32,
               np.dtype(np.float64): np.float32,
               np.dtype(np.uint64): np.uint32}.get(a.dtype)
        return a.astype(fix) if fix else a

    def traced(*vals):
        return any(isinstance(v, jax.core.Tracer) for v in vals)

    targets = {
        (jax.random, "key"), (jax.random, "PRNGKey"),
        (jax.random, "split"), (jax.random, "normal"),
        (jax.random, "uniform"), (jnp, "zeros"), (jnp, "ones"),
        (jnp, "full"), (jnp, "arange"),
    }
    saved = {(mod, name): getattr(mod, name) for mod, name in targets}

    def stub(mod, name, fake):
        orig = saved[(mod, name)]

        def f(*args, **kw):
            if traced(*args, *kw.values()):
                return orig(*args, **kw)
            return fake(*args, **kw)
        setattr(mod, name, f)

    stub(jax.random, "key", lambda seed: np.zeros(2, np.uint32))
    stub(jax.random, "PRNGKey", lambda seed: np.zeros(2, np.uint32))
    stub(jax.random, "split",
         lambda key, num=2: np.zeros((num, 2), np.uint32))
    stub(jax.random, "normal",
         lambda key, shape=(), dtype=np.float32: np.zeros(shape, dtype))
    stub(jax.random, "uniform",
         lambda key, shape=(), dtype=np.float32, minval=0.0, maxval=1.0:
         np.zeros(shape, dtype))
    stub(jnp, "zeros",
         lambda shape, dtype=np.float32, **kw: np.zeros(shape, dtype))
    stub(jnp, "ones",
         lambda shape, dtype=np.float32, **kw: np.ones(shape, dtype))
    stub(jnp, "full",
         lambda shape, v, dtype=None, **kw: canon(np.full(shape, v, dtype)))
    stub(jnp, "arange", lambda *a, **kw: canon(np.arange(*a, **kw)))
    try:
        yield
    finally:
        for (mod, name), orig in saved.items():
            setattr(mod, name, orig)


def record_unsharded(which: str):
    """The single-device serving programs under the EXPLICIT unsharded
    contract. Since the sharding PR flipped the registry to
    :func:`record_sharded`, this recorder exists for the
    ``serving_unsharded`` defect arm: it is exactly what a serving
    program looks like after silently LOSING its sharding, and auditing
    it against the sharded baseline must flip the gate (PT-COMM-005
    ``lost-sharding``)."""
    import audit_program_cost as apc
    from paddle_tpu.static.comm import CommPathSpec

    rec = {"mega_step@8": lambda: apc.record_mega_step(8),
           "spec_verify@8": lambda: apc.record_spec_verify(8),
           "prefill_chunk": apc.record_prefill_chunk}[which]
    with _compile_free_setup():
        prog, cost_spec = rec()
    spec = CommPathSpec(which, unsharded=True,
                        notes="single-device serving program "
                              f"({cost_spec.notes}) — unsharded contract")
    return prog, spec


def record_sharded(which: str, tp: int = 2):
    """The mesh-sharded serving programs, re-recorded from
    audit_program_cost's registry over an ABSTRACT tp mesh (no devices,
    no compiles — docs/SERVING.md "Sharded serving"). Column-parallel
    identity contract: every collective is an all_gather of disjoint
    output shards, so the census must stay psum-free."""
    import audit_program_cost as apc
    from paddle_tpu.static.comm import CommPathSpec

    rec = {"mega_step@8": lambda: apc.record_mega_step(8, mesh=tp),
           "spec_verify@8": lambda: apc.record_spec_verify(8, mesh=tp),
           "prefill_chunk": lambda: apc.record_prefill_chunk(mesh=tp)}[which]
    with _compile_free_setup():
        prog, cost_spec = rec()
    spec = CommPathSpec(which, mesh={"tp": tp}, width=tp,
                        notes=f"tp{tp}-sharded serving program "
                              f"({cost_spec.notes}) — column-parallel, "
                              "all_gather-only by construction")
    return prog, spec


def record_all(only=None):
    out = {}
    for key in MULTICHIP_MESHES:
        out[f"mesh_train_step@{key}"] = lambda k=key: record_mesh_train_step(k)
    for w in SCALING_WIDTHS:
        out[f"tp_train@{w}"] = lambda s=w: record_tp_train(s)
        out[f"flash_ring@{w}"] = lambda s=w: record_flash_ring(s)
        out[f"moe_combine@{w}"] = lambda s=w: record_moe_combine(s)
    for name in ("mega_step@8", "spec_verify@8", "prefill_chunk"):
        out[name] = lambda n=name: record_sharded(n)
    if only:
        if only not in out:
            raise SystemExit(f"unknown program {only!r} "
                             f"(choose: {sorted(out)})")
        out = {only: out[only]}
    return {name: rec() for name, rec in out.items()}


# ---------------------------------------------------------------------------
# baseline
# ---------------------------------------------------------------------------

def load_baseline(path: str = BASELINE_PATH):
    """Returns (programs: {name: manifest dict}, waivers: {id: just}).
    Waiver entries without a justification are rejected — the file is a
    review record, not a mute button (PT-RACE discipline)."""
    if not os.path.exists(path):
        return {}, {}
    with open(path) as f:
        doc = json.load(f)
    waivers = {}
    for entry in doc.get("waivers", ()):
        fid = entry.get("id")
        just = (entry.get("justification") or "").strip()
        if not fid or not just:
            raise SystemExit(
                f"baseline waiver {entry!r} is missing an id or a "
                "justification — every suppression must say why")
        waivers[fid] = just
    return doc.get("programs", {}), waivers


def write_baseline(manifests, waivers, path: str = BASELINE_PATH):
    # `degrade_widths` is a REVIEWED annotation, not a traced fact —
    # CommManifest.to_dict() cannot produce it, so a refresh must carry
    # it over from the prior baseline or the elastic-degrade exemption
    # (docs/RESILIENCE.md "Elastic serving mesh") silently disappears
    prior, _ = load_baseline(path)
    programs = {}
    for k, m in sorted(manifests.items()):
        rec = m.to_dict()
        widths = (prior.get(k) or {}).get("degrade_widths")
        if widths:
            rec["degrade_widths"] = [int(w) for w in widths]
        programs[k] = rec
    doc = {
        "_comment": [
            "PT-COMM manifests + reviewed waivers",
            "(docs/STATIC_ANALYSIS.md, tools/audit_collectives.py).",
            "Counts and wire bytes are CONTRACTS: collectives may only",
            "change through a reviewed refresh. The serving programs",
            "record their tp-sharded collective census (column-parallel,",
            "all_gather-only); a program that silently reverts to",
            "unsharded gates as PT-COMM-005 lost-sharding. Every waiver",
            "needs a justification; stale waivers are reported.",
            "Serving entries may record `degrade_widths`: the narrower",
            "tp widths the elastic PT-SRV-008 reshard path legitimately",
            "serves at — a still-sharded manifest at a recorded degrade",
            "width passes the count/drift/bytes gates (its census scales",
            "with the width); losing sharding entirely still gates as",
            "lost-sharding. Preserved across --write-baseline refreshes.",
        ],
        "programs": programs,
        "waivers": [{"id": fid, "justification": waivers[fid]}
                    for fid in sorted(waivers)],
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    print(f"baseline written: {path} ({len(manifests)} program(s), "
          f"{len(waivers)} waiver(s))")


# ---------------------------------------------------------------------------
# audit driver (shared by the real gate and the selftest fixtures)
# ---------------------------------------------------------------------------

def audit(programs, base_programs, waivers, skip_contract=False,
          report_stale=True):
    """Audit ``programs`` ({name: (Program, CommPathSpec)}). Returns
    (exit_code, manifests, gate_findings)."""
    from paddle_tpu.static.comm import (check_comm_contract,
                                        check_gather_reduce,
                                        check_loop_invariant_collectives,
                                        check_mesh_scaling,
                                        check_replication,
                                        compute_comm_manifest)

    manifests, specs, findings = {}, {}, []
    for name, (prog, spec) in programs.items():
        man = compute_comm_manifest(prog, name=name, spec=spec)
        manifests[name], specs[name] = man, spec
        findings += check_replication(prog, name)
        findings += check_loop_invariant_collectives(prog, name)
        findings += check_gather_reduce(prog, name)
        if not skip_contract:
            findings += check_comm_contract(man, base_programs.get(name))
    # mesh-scaling law over every family traced at >=2 widths
    groups = {}
    for name, man in manifests.items():
        if man.width and "@" in name:
            groups.setdefault(name.split("@")[0], []).append(man)
    for fam, group in sorted(groups.items()):
        if len(group) >= 2:
            findings += check_mesh_scaling(group)
    gate, suppressed = [], []
    for d in findings:
        fid = getattr(d, "finding_id", None)
        (suppressed if fid in waivers else gate).append(d)
    for name, man in sorted(manifests.items()):
        scal = (man.scaling or {}).get("verdict", "-")
        counts = " ".join(f"{k}:{v}" for k, v in sorted(
            man.collectives.items())) or "none"
        contract = "unsharded" if man.unsharded else (
            "mesh " + "x".join(f"{k}{v}" for k, v in sorted(man.mesh.items()))
            if man.mesh else "unmeshed")
        print(f"[manifest] {name}: {contract}, "
              f"{man.collective_eqns} collective eqn(s) [{counts}], "
              f"{man.comm_bytes:.3g} wire B, "
              f"loop-inv {man.loop_invariant_eqns}, scaling {scal}")
    for d in gate:
        print(f"{d.format()}\n    id: {getattr(d, 'finding_id', '')}")
    for d in suppressed:
        fid = getattr(d, "finding_id", "")
        print(f"[waived] {fid}: {waivers[fid]}")
    if report_stale:
        all_ids = {getattr(d, "finding_id", None) for d in findings}
        for fid in sorted(set(waivers) - all_ids):
            print(f"[stale waiver — remove it] {fid}")
    status = "FINDINGS AT GATE SEVERITY" if gate else "CLEAN"
    print(f"COLLECTIVE COMM AUDIT {'FAIL' if gate else 'OK'}: "
          f"{len(manifests)} program(s), {len(findings)} finding(s), "
          f"{len(suppressed)} waived, {len(gate)} at gate severity — "
          f"{status}")
    return (1 if gate else 0), manifests, gate


# ---------------------------------------------------------------------------
# seeded-defect fixtures (synthetic, tiny — no model builds, no compiles)
# ---------------------------------------------------------------------------

def _fixture(width=2, replicated=False, loop_regather=False,
             quadratic=False, gather_reduce=False, extra_psum=False):
    """One tiny shard_map'd step over an ``x``-axis mesh: a sharded
    weight, a small replicated activation, one row-parallel psum — each
    defect class is one knob away."""
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.static.analysis import trace_to_program
    from paddle_tpu.static.comm import CommPathSpec, abstract_mesh

    r_shape = (512, 512) if replicated else (8, 8)   # 1 MiB vs 256 B
    perm = [(i, (i + 1) % width) for i in range(width)]

    def step(w, x, r):
        h = x @ w.T                          # [8, 8] partial over x
        h = lax.psum(h, "x")                 # the one contracted psum
        if extra_psum:
            h = lax.psum(h, "x")             # contract drift
        if gather_reduce:
            g = lax.all_gather(x, "x", axis=0, tiled=True)
            h = h + g.sum()                  # reduce over the gathered dim
        if loop_regather:
            def sbody(c, _):                 # w is a scan CONST: the same
                g = lax.all_gather(w, "x", axis=0, tiled=True)  # bytes
                return c + g.sum(), None     # re-gathered every step
            h2, _ = lax.scan(sbody, jnp.float32(0), jnp.arange(4))
            h = h + h2
        if quadratic:
            # an O(width^2) collective count on a width-scaled payload:
            # the "gather the world then ring it around" accident
            xt = jnp.tile(x, (width, 1))
            for _ in range(width * width):
                xt = lax.ppermute(xt, "x", perm)
            h = h + xt.sum()
        return h.sum() + r[0, 0] * jnp.float32(0)

    mesh = abstract_mesh({"x": width})
    fn = jax.shard_map(step, mesh=mesh,
                       in_specs=(P("x", None), P(None, None), P(None, None)),
                       out_specs=P(), check_vma=False)
    prog = trace_to_program(
        fn, _spec((8 * width, 16), np.float32), _spec((8, 16), np.float32),
        _spec(r_shape, np.float32), input_names=["w", "x", "r"])
    spec = CommPathSpec(f"fixture@{width}", mesh={"x": width}, width=width)
    return prog, spec


def _fixture_pair(**kw):
    return {f"fixture@{w}": _fixture(width=w, **kw) for w in (2, 4)}


def _fixture_baseline():
    from paddle_tpu.static.comm import compute_comm_manifest

    base = {}
    for name, (prog, spec) in _fixture_pair().items():
        base[name] = compute_comm_manifest(prog, name=name,
                                           spec=spec).to_dict()
    return base


def inject(defect, base_programs):
    """Programs for one seeded defect class, audited against the CLEAN
    fixture baseline."""
    if defect == "replicated_param":
        return _fixture_pair(replicated=True)
    if defect == "loop_regather":
        return _fixture_pair(loop_regather=True)
    if defect == "superlinear_comm":
        return _fixture_pair(quadratic=True)
    if defect == "gather_reduce":
        return _fixture_pair(gather_reduce=True)
    if defect == "contract_drift":
        return _fixture_pair(extra_psum=True)
    if defect == "serving_unsharded":
        # a serving program that silently LOST its sharding: the engine
        # dispatches the single-device program while the baseline records
        # the tp-sharded all_gather census (audit against _serving_base())
        return {"mega_step@8": record_unsharded("mega_step@8")}
    raise SystemExit(f"unknown defect {defect!r} (choose: {DEFECTS})")


def _serving_base():
    """The REAL sharded mega-step census, recorded as the baseline the
    ``serving_unsharded`` defect arm is audited against — the one defect
    class that needs a production program, not a synthetic fixture."""
    from paddle_tpu.static.comm import compute_comm_manifest

    prog, spec = record_sharded("mega_step@8")
    man = compute_comm_manifest(prog, name="mega_step@8", spec=spec)
    return {"mega_step@8": man.to_dict()}


def selftest():
    """The clean fixture must audit clean against its own baseline; every
    seeded defect class must flip the exit code with its expected code;
    an unbaselined program and the waiver discipline are pinned
    (harness: tools/_selftest.py — asserted in tests/test_ci_gates.py)."""
    h = _selftest.Harness("COMM")
    base = _fixture_baseline()
    rc, _, gate = audit(_fixture_pair(), base, waivers={})
    h.case("clean fixture", rc == 0, f"rc={rc}, {len(gate)} gate finding(s)")
    for defect in DEFECTS:
        want = EXPECTED_CODE[defect]
        b = dict(base, **_serving_base()) \
            if defect == "serving_unsharded" else base
        rc, _, gate = audit(inject(defect, b), b, waivers={})
        hit = [d for d in gate if d.code == want]
        if rc == 1 and hit:
            h.case(f"inject {defect}", True,
                   f"detected {want} — {hit[0].message[:70]}")
        else:
            h.case(f"inject {defect}", False,
                   f"rc={rc}, wanted {want}, gate codes: "
                   f"{sorted({d.code for d in gate})}")
    rc, _, gate = audit(_fixture_pair(), {}, waivers={})
    h.case("unbaselined program flips the gate",
           rc == 1 and any(d.code == "PT-COMM-005" for d in gate),
           f"rc={rc}")
    # waiver discipline end-to-end: a waiver with a justification
    # un-flips exactly its finding; nothing else
    progs = inject("replicated_param", base)
    rc_bad, _, gate = audit(progs, base, waivers={})
    fids = {getattr(d, "finding_id", "") for d in gate}
    rc_ok, _, _ = audit(progs, base,
                        waivers={fid: "selftest" for fid in fids})
    h.case("waiver un-flips the gate", rc_bad == 1 and rc_ok == 0,
           f"rc {rc_bad} -> {rc_ok} with {len(fids)} waiver(s)")
    return h.finish(
        f"COMM SELFTEST OK: {len(DEFECTS)} defect classes detected, "
        "clean fixture audits clean, waiver discipline pinned",
        "COMM SELFTEST FAIL: {failures} expectation(s) violated")


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--program", default=None,
                    help="audit one registered program (default: all)")
    ap.add_argument("--baseline", default=BASELINE_PATH)
    ap.add_argument("--no-baseline", action="store_true",
                    help="ignore the baseline (show everything; the "
                         "unbaselined-program finding still fires)")
    ap.add_argument("--inject", choices=DEFECTS, default=None,
                    help="audit the synthetic fixture seeded with one "
                         "defect class (must flip the exit code)")
    ap.add_argument("--selftest", action="store_true",
                    help="verify every defect class flips the gate")
    ap.add_argument("--write-baseline", action="store_true",
                    help="record the current manifests as the baseline "
                         "(review the diff!)")
    args = ap.parse_args(argv)

    t0 = time.monotonic()
    guarded = install_compile_guard()

    if args.selftest:
        rc = selftest()
    elif args.inject:
        base = _fixture_baseline()
        if args.inject == "serving_unsharded":
            base = dict(base, **_serving_base())
        rc, _, _ = audit(inject(args.inject, base), base, waivers={})
    else:
        base_programs, waivers = ({}, {}) if args.no_baseline \
            else load_baseline(args.baseline)
        programs = record_all(only=args.program)
        rc, manifests, gate = audit(programs, base_programs, waivers,
                                    skip_contract=args.write_baseline,
                                    report_stale=args.program is None)
        if args.write_baseline:
            if args.program:
                raise SystemExit("--write-baseline needs the full set")
            write_baseline(manifests, waivers, args.baseline)

    compiles = len(_COMPILES) if guarded else "untracked"
    print(f"xla_compiles={compiles} elapsed={time.monotonic() - t0:.1f}s")
    if guarded and _COMPILES:
        print("COLLECTIVE COMM AUDIT FAIL: the gate triggered an XLA "
              "compile — the auditor must stay pure tracing")
        return 1
    return rc


if __name__ == "__main__":
    sys.exit(main())
