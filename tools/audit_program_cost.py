"""Program-cost gate (PT-COST — docs/STATIC_ANALYSIS.md): trace every
registered hot-path program (NO XLA compile — pure ``make_jaxpr`` through
``static.analysis.trace_to_program``) and audit its cost manifest against
the reviewed baseline (tools/program_cost_baseline.json).

What PR 9's PT-RACE gate is for thread-safety, this is for DEVICE-PROGRAM
COST: a machine-independent CI invariant over the programs the serving and
training hot paths actually dispatch — the fused mega-step (traced at TWO
slot widths for the slot-scaling law), the packed prefill chunk, the hapi
train step, and the PR 12 KV-migration scatters. The audit catches, before
any hardware run:

- PT-COST-001  a bf16 path silently widened to f32 (weak-type accident /
               upcast-census drift)
- PT-COST-002  a host-sync primitive inside a jitted program (jaxpr-level
               sibling of the PT-TRACE-004 source scan)
- PT-COST-003  a step-to-step carry the jitted program stopped donating
               (read off the traced pjit's ``donated_invars``)
- PT-COST-004  scatter/gather equation counts past the recorded contract
- PT-COST-005  program text or FLOPs growing superlinearly in slot count

Exit 0 iff every error-severity finding is fixed or covered by a reviewed
waiver WITH a justification (the PT-RACE baseline discipline — an
unreviewed defect can only make the gate red, never silently pass).

Usage:
    JAX_PLATFORMS=cpu python tools/audit_program_cost.py      # full gate
    python tools/audit_program_cost.py --program mega_step@8
    python tools/audit_program_cost.py --write-baseline       # refresh
    python tools/audit_program_cost.py --inject lost_donation # seeded demo
    python tools/audit_program_cost.py --selftest             # all 5 classes
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import _selftest

ROOT = _selftest.bootstrap()

BASELINE_PATH = os.path.join(ROOT, "tools", "program_cost_baseline.json")

import jax  # noqa: E402
import numpy as np  # noqa: E402

DEFECTS = ("f32_upcast", "host_sync", "lost_donation", "scatter_drift",
           "superlinear_scaling")

EXPECTED_CODE = {
    "f32_upcast": "PT-COST-001",
    "host_sync": "PT-COST-002",
    "lost_donation": "PT-COST-003",
    "scatter_drift": "PT-COST-004",
    "superlinear_scaling": "PT-COST-005",
}

#: slot widths the mega-step is traced at for the PT-COST-005 scaling law
SCALING_WIDTHS = (8, 32)


def _spec(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), np.dtype(dtype))


# ---------------------------------------------------------------------------
# hot-path recorders — each returns (Program, HotPathSpec)
# ---------------------------------------------------------------------------

def record_mega_step(slots: int, mesh: int = 0):
    """The fused decode mega-step EXACTLY as the engine dispatches it:
    traced through ``_build_mega_jit()`` (donation included, so the audited
    ``donated_invars`` are the production program's), every buffer — params,
    kv pools, tables, device step state, sampling vectors — a named input.

    ``mesh=N`` traces the tp-sharded shard_map variant over an ABSTRACT
    tp mesh (no devices needed — docs/SERVING.md "Sharded serving"), so the
    manifest covers the column-parallel program the sharded engine really
    dispatches, all_gathers included."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import (ContinuousBatchingEngine,
                                              MeshConfig, PrefixCacheConfig)
    from paddle_tpu.jit.api import _collect_state
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.static.analysis import trace_to_program
    from paddle_tpu.static.cost import HotPathSpec

    paddle.seed(11)
    cfg = LlamaConfig.tiny(num_hidden_layers=1)
    m = LlamaForCausalLM(cfg)
    eng = ContinuousBatchingEngine(
        m, max_batch=slots, max_len=32, page_size=8, block_size=2,
        prefix_cache=PrefixCacheConfig(prefill_chunk=8),
        mesh=MeshConfig(tp=mesh, abstract=True) if mesh else None)
    jf = eng._build_mega_jit()
    names, tensors = _collect_state(m)
    param_structs = [_spec(t._data.shape, t._data.dtype) for t in tensors]
    n_p = len(param_structs)
    kv = eng.caches["kv"]
    L = len(kv)
    B, maxp = eng.max_batch, eng._maxp

    def flat(*args):
        params, i = list(args[:n_p]), n_p
        toks = args[i]
        i += 1
        kvl = [(args[i + 2 * l], args[i + 2 * l + 1]) for l in range(L)]
        i += 2 * L
        tables, pos, act, seeds, temps, tops, topks = args[i:i + 7]
        return jf(params, toks, kvl, tables, pos, act, seeds, temps, tops,
                  topks, n_steps=2, do_sample=True)

    kv_specs = [_spec(a.shape, a.dtype) for pair in kv for a in pair]
    kv_names = [f"kv{l}_{t}" for l in range(L) for t in ("k", "v")]
    ins = ([_spec((B,), np.int32)] + kv_specs +
           [_spec((B, maxp), np.int32), _spec((B,), np.int32),
            _spec((B,), np.bool_), _spec((B,), np.int32),
            _spec((B,), np.float32), _spec((B,), np.float32),
            _spec((B,), np.int32)])
    in_names = (["toks"] + kv_names +
                ["tables", "pos", "act", "seeds", "temps", "tops", "topks"])
    prog = trace_to_program(flat, *ins, input_names=in_names,
                            param_structs=param_structs, param_names=names,
                            param_tensors=tensors)
    kv_lo = n_p + 1
    kv_hi = kv_lo + 2 * L
    fam = f"mega_step_tp{mesh}" if mesh else "mega_step"
    spec = HotPathSpec(
        f"{fam}@{slots}", slots=slots,
        carries={"kv": (kv_lo, kv_hi), "pos": (kv_hi + 1, kv_hi + 2)},
        notes="fused decode mega-step (serving.py), n_steps=2, sampled" +
              (f", column-parallel tp={mesh} shard_map" if mesh else ""))
    return prog, spec


def record_spec_verify(slots: int, mesh: int = 0):
    """The speculative verify mega-step (docs/SERVING.md "Speculative
    decode") EXACTLY as the engine dispatches it: traced through
    ``_build_spec_jit()`` so the audited ``donated_invars`` cover the real
    carry set — kv pools, positions AND the drafter's history ring/length.
    Traced at both SCALING_WIDTHS for the <=linear slot law; the in-graph
    draft -> K-wide verify -> accept/rollback scatters are census-pinned
    by the baseline contract (PT-COST-004)."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import (ContinuousBatchingEngine,
                                              MeshConfig, PrefixCacheConfig,
                                              SpecConfig)
    from paddle_tpu.jit.api import _collect_state
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.static.analysis import trace_to_program
    from paddle_tpu.static.cost import HotPathSpec

    paddle.seed(11)
    cfg = LlamaConfig.tiny(num_hidden_layers=1)
    m = LlamaForCausalLM(cfg)
    eng = ContinuousBatchingEngine(
        m, max_batch=slots, max_len=32, page_size=8, block_size=2,
        speculative=SpecConfig(k=3, ngram=2, history=16),
        prefix_cache=PrefixCacheConfig(prefill_chunk=8),
        mesh=MeshConfig(tp=mesh, abstract=True) if mesh else None)
    jf = eng._build_spec_jit()
    names, tensors = _collect_state(m)
    param_structs = [_spec(t._data.shape, t._data.dtype) for t in tensors]
    n_p = len(param_structs)
    kv = eng.caches["kv"]
    L = len(kv)
    B, maxp, H = eng.max_batch, eng._maxp, eng._spec.history

    def flat(*args):
        params, i = list(args[:n_p]), n_p
        toks = args[i]
        i += 1
        kvl = [(args[i + 2 * l], args[i + 2 * l + 1]) for l in range(L)]
        i += 2 * L
        tables, pos, act, hist, hlen, caps = args[i:i + 6]
        return jf(params, toks, kvl, tables, pos, act, hist, hlen, caps)

    kv_specs = [_spec(a.shape, a.dtype) for pair in kv for a in pair]
    kv_names = [f"kv{l}_{t}" for l in range(L) for t in ("k", "v")]
    ins = ([_spec((B,), np.int32)] + kv_specs +
           [_spec((B, maxp), np.int32), _spec((B,), np.int32),
            _spec((B,), np.bool_), _spec((B, H), np.int32),
            _spec((B,), np.int32), _spec((B,), np.int32)])
    in_names = (["toks"] + kv_names +
                ["tables", "pos", "act", "hist", "hlen", "caps"])
    prog = trace_to_program(flat, *ins, input_names=in_names,
                            param_structs=param_structs, param_names=names,
                            param_tensors=tensors)
    kv_lo = n_p + 1
    kv_hi = kv_lo + 2 * L
    fam = f"spec_verify_tp{mesh}" if mesh else "spec_verify"
    spec = HotPathSpec(
        f"{fam}@{slots}", slots=slots,
        carries={"kv": (kv_lo, kv_hi), "pos": (kv_hi + 1, kv_hi + 2),
                 "hist": (kv_hi + 3, kv_hi + 4),
                 "hlen": (kv_hi + 4, kv_hi + 5)},
        notes="speculative verify mega-step (serving.py), k=3 draft + "
              "bonus, n-gram drafter in-graph" +
              (f", column-parallel tp={mesh} shard_map" if mesh else ""))
    return prog, spec


def record_prefill_chunk(mesh: int = 0):
    """The packed prefill-chunk program (``_chunk_fn`` — shared by the
    legacy chunked path and the fused ``_run_pack``), at a 4-row bucket."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import (ContinuousBatchingEngine,
                                              MeshConfig, PrefixCacheConfig)
    from paddle_tpu.jit.api import _collect_state
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.static.analysis import trace_to_program
    from paddle_tpu.static.cost import HotPathSpec

    paddle.seed(11)
    cfg = LlamaConfig.tiny(num_hidden_layers=1)
    m = LlamaForCausalLM(cfg)
    eng = ContinuousBatchingEngine(
        m, max_batch=8, max_len=32, page_size=8, block_size=2,
        prefix_cache=PrefixCacheConfig(prefill_chunk=8),
        mesh=MeshConfig(tp=mesh, abstract=True) if mesh else None)
    g, C = 4, eng._chunk_tokens
    jf = eng._chunk_fn(g)
    names, tensors = _collect_state(m)
    param_structs = [_spec(t._data.shape, t._data.dtype) for t in tensors]
    n_p = len(param_structs)
    kv = eng.caches["kv"]
    L = len(kv)

    def flat(*args):
        params, i = list(args[:n_p]), n_p
        ids = args[i]
        i += 1
        kvl = [(args[i + 2 * l], args[i + 2 * l + 1]) for l in range(L)]
        i += 2 * L
        rows, starts = args[i], args[i + 1]
        return jf(params, ids, kvl, rows, starts)

    kv_specs = [_spec(a.shape, a.dtype) for pair in kv for a in pair]
    kv_names = [f"kv{l}_{t}" for l in range(L) for t in ("k", "v")]
    ins = ([_spec((g, C), np.int32)] + kv_specs +
           [_spec((g, eng._maxp), np.int32), _spec((g,), np.int32)])
    prog = trace_to_program(
        flat, *ins, input_names=["ids"] + kv_names + ["rows", "starts"],
        param_structs=param_structs, param_names=names,
        param_tensors=tensors)
    kv_lo = n_p + 1
    spec = HotPathSpec(
        f"prefill_chunk_tp{mesh}" if mesh else "prefill_chunk",
        carries={"kv": (kv_lo, kv_lo + 2 * L)},
        notes="packed prefill chunk (_chunk_fn g=4), chunk=8 tokens" +
              (f", column-parallel tp={mesh} shard_map" if mesh else ""))
    return prog, spec


def record_train_step():
    """The hapi jitted train step — forward + loss + backward + Adam update
    in one program; params/opt-state are the carries (hapi donates both via
    ``donate_argnums=(0, 1)`` — losing that shows up here)."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.framework.random import next_key
    from paddle_tpu.hapi.model import Model
    from paddle_tpu.static.analysis import trace_to_program
    from paddle_tpu.static.cost import HotPathSpec

    paddle.seed(7)
    net = paddle.nn.Sequential(paddle.nn.Linear(16, 32), paddle.nn.ReLU(),
                               paddle.nn.Linear(32, 8))
    mdl = Model(net)
    opt = paddle.optimizer.Adam(parameters=net.parameters())
    mdl.prepare(opt, paddle.nn.CrossEntropyLoss())
    mdl._build_train_step()          # builds mdl._jitted (donated)
    jf = mdl._jitted
    tensors = mdl._state_tensors
    state_structs = [_spec(t._data.shape, t._data.dtype) for t in tensors]
    n_s = len(state_structs)
    key = next_key()

    def flat(*args):
        state = list(args[:n_s])
        x, y = args[n_s], args[n_s + 1]
        # opt_state={} is the real first-call signature; key/lr/step ride
        # as trace constants (they are not cost-relevant inputs)
        return jf(state, {}, [x], [y], key, jnp.float32(1e-3),
                  jnp.int32(1))

    prog = trace_to_program(
        flat, _spec((8, 16), np.float32), _spec((8,), np.int64),
        input_names=["x", "labels"],
        param_structs=state_structs,
        param_names=[f"state_{i}" for i in range(n_s)],
        param_tensors=list(tensors))
    spec = HotPathSpec("train_step", carries={"state": (0, n_s)},
                       notes="hapi Model train step (MLP + CE + Adam)")
    return prog, spec


def record_migration():
    """The PR 12 KV-migration device programs (inference/disagg.py via
    ops/paged_attention.py): the per-layer page gather that exports a
    chain and ``scatter_chain_pages`` that imports it. These dispatch
    EAGERLY on the control plane (once per request, never on the decode
    hot path) — so no pjit wrapper exists and the kv carry is undonated by
    design: the source pool keeps serving concurrently-decoding slots
    while the bytes are in flight. That PT-COST-003 finding is WAIVED in
    the baseline with this justification. tools/lint_graph.py's
    ``migration`` family reuses THIS recorder, so graph-lint and cost
    coverage stay one program."""
    from paddle_tpu.ops.paged_attention import scatter_chain_pages
    from paddle_tpu.static.analysis import trace_to_program
    from paddle_tpu.static.cost import HotPathSpec

    P, H, PG, D, n = 8, 2, 8, 4, 3

    def roundtrip(k0, v0, k1, v1, src, dst):
        kv = [(k0, v0), (k1, v1)]
        pages = [(k[src], v[src]) for k, v in kv]   # device half of the
        #                                             gather_chain_pages export
        out = scatter_chain_pages(kv, dst, pages)
        return tuple(x for pair in out for x in pair)

    pool = _spec((P, H, PG, D), np.float32)
    prog = trace_to_program(
        roundtrip, pool, pool, pool, pool, _spec((n,), np.int32),
        _spec((n,), np.int32),
        input_names=["k0", "v0", "k1", "v1", "src_blocks", "dst_blocks"])
    spec = HotPathSpec("migration", carries={"kv": (0, 4)},
                       notes="KV-chain migration gather+scatter (eager "
                             "control-plane dispatch)")
    return prog, spec


def record_all(only=None):
    out = {}
    for slots in SCALING_WIDTHS:
        out[f"mega_step@{slots}"] = lambda s=slots: record_mega_step(s)
        out[f"spec_verify@{slots}"] = lambda s=slots: record_spec_verify(s)
    out["prefill_chunk"] = record_prefill_chunk
    # mesh-sharded serving variants (abstract tp=2 mesh; one width — the
    # slot-scaling law is carried by the unsharded family above)
    out["mega_step_tp2@8"] = lambda: record_mega_step(8, mesh=2)
    out["spec_verify_tp2@8"] = lambda: record_spec_verify(8, mesh=2)
    out["prefill_chunk_tp2"] = lambda: record_prefill_chunk(mesh=2)
    out["train_step"] = record_train_step
    out["migration"] = record_migration
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
    doc = {
        "_comment": [
            "PT-COST manifests + reviewed waivers",
            "(docs/STATIC_ANALYSIS.md, tools/audit_program_cost.py).",
            "Counts are CONTRACTS: scatter/gather/host-sync/upcast may",
            "only grow through a reviewed refresh. Every waiver needs a",
            "justification; stale waivers are reported by the gate —",
            "remove them when the code is fixed.",
        ],
        "programs": {k: m.to_dict() for k, m in sorted(manifests.items())},
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
          report_stale=True, verbose=False):
    """Audit ``programs`` ({name: (Program, HotPathSpec)}). Returns
    (exit_code, manifests, gate_findings). ``report_stale=False`` for
    subset runs (``--program``): a waiver for an unaudited program is not
    stale, and telling the operator to delete it would lose the review."""
    from paddle_tpu.static.cost import (check_contract, check_donation,
                                        check_dtype_promotion,
                                        check_host_sync, check_slot_scaling,
                                        compute_manifest)

    manifests, findings = {}, []
    for name, (prog, spec) in programs.items():
        man = compute_manifest(prog, name=name, spec=spec)
        manifests[name] = man
        findings += check_dtype_promotion(prog, name)
        findings += check_host_sync(prog, name)
        findings += check_donation(man)
        if not skip_contract:
            findings += check_contract(man, base_programs.get(name))
    # slot-scaling law over every name traced at >=2 widths
    groups = {}
    for name, man in manifests.items():
        if man.slots and "@" in name:
            groups.setdefault(name.split("@")[0], []).append(man)
    for fam, group in sorted(groups.items()):
        if len(group) >= 2:
            findings += check_slot_scaling(group)
    gate, suppressed = [], []
    for d in findings:
        fid = getattr(d, "finding_id", None)
        (suppressed if fid in waivers else gate).append(d)
    for name, man in sorted(manifests.items()):
        scal = (man.scaling or {}).get("verdict", "-")
        print(f"[manifest] {name}: {man.num_eqns} eqns, "
              f"{man.flops_total:.3g} flops, {man.bytes_total:.3g} B, "
              f"AI {man.arithmetic_intensity:.2f}, "
              f"scatter/gather {man.scatter_ops}/{man.gather_ops}, "
              f"host-sync {man.host_sync_eqns}, "
              f"upcasts {man.upcast_converts}, "
              f"donated {sorted(man.donation.get('donated', []))} "
              f"missing {sorted(man.donation.get('missing', []))}, "
              f"scaling {scal}")
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
    print(f"PROGRAM COST AUDIT {'FAIL' if gate else 'OK'}: "
          f"{len(manifests)} program(s), {len(findings)} finding(s), "
          f"{len(suppressed)} waived, {len(gate)} at gate severity — "
          f"{status}")
    return (1 if gate else 0), manifests, gate


# ---------------------------------------------------------------------------
# seeded-defect fixtures (synthetic, tiny — no model builds, no compiles)
# ---------------------------------------------------------------------------

def _fixture(width=8, donate=True, extra_scatter=False, upcast=False,
             sync=False, quadratic=False):
    """One tiny jitted step over (kv[16,8] f32, x[width,8] bf16) with a
    donated kv carry, one scatter, and a weak-typed scalar — each defect
    class is one knob away."""
    import jax.numpy as jnp

    from paddle_tpu.static.analysis import trace_to_program
    from paddle_tpu.static.cost import HotPathSpec

    def step(kv, x):
        kv = kv.at[0].add(x.sum(0).astype(kv.dtype))       # the one scatter
        if extra_scatter:
            kv = kv.at[1].add(x.sum(0).astype(kv.dtype))   # contract drift
        y = jnp.tanh(x) * 2.0            # weak-typed python scalar: stays bf16
        if upcast:
            y = y * np.float32(2.0)      # f32 SCALAR constant: promotes
        if quadratic:
            # an O(width^2) term: the accidental slot x slot interaction
            y = y + (x[:, :1] @ x[:, :1].T) @ x
        if sync:
            y = y + jax.pure_callback(
                lambda a: np.asarray(a),
                jax.ShapeDtypeStruct(y.shape, y.dtype), y)
        return kv, y.sum()

    jf = jax.jit(step, donate_argnums=(0,) if donate else ())
    prog = trace_to_program(
        lambda kv, x: jf(kv, x), _spec((16, 8), np.float32),
        _spec((width, 8), "bfloat16"), input_names=["kv", "x"])
    spec = HotPathSpec(f"fixture@{width}", slots=width,
                       carries={"kv": (0, 1)})
    return prog, spec


def _fixture_pair(**kw):
    return {f"fixture@{w}": _fixture(width=w, **kw) for w in (8, 32)}


def _fixture_baseline():
    from paddle_tpu.static.cost import compute_manifest

    base = {}
    for name, (prog, spec) in _fixture_pair().items():
        base[name] = compute_manifest(prog, name=name, spec=spec).to_dict()
    return base


def inject(defect, base_programs):
    """Programs for one seeded defect class, audited against the CLEAN
    fixture baseline."""
    if defect == "f32_upcast":
        return _fixture_pair(upcast=True)
    if defect == "host_sync":
        return _fixture_pair(sync=True)
    if defect == "lost_donation":
        return _fixture_pair(donate=False)
    if defect == "scatter_drift":
        return _fixture_pair(extra_scatter=True)
    if defect == "superlinear_scaling":
        return _fixture_pair(quadratic=True)
    raise SystemExit(f"unknown defect {defect!r} (choose: {DEFECTS})")


def selftest():
    """The clean fixture must audit clean against its own baseline; every
    seeded defect class must flip the exit code with its expected code
    (harness: tools/_selftest.py — pinned in tests/test_ci_gates.py)."""
    h = _selftest.Harness("COST")
    base = _fixture_baseline()
    rc, _, gate = audit(_fixture_pair(), base, waivers={})
    h.case("clean fixture", rc == 0, f"rc={rc}, {len(gate)} gate finding(s)")
    for defect in DEFECTS:
        want = EXPECTED_CODE[defect]
        rc, _, gate = audit(inject(defect, base), base, waivers={})
        hit = [d for d in gate if d.code == want]
        if rc == 1 and hit:
            h.case(f"inject {defect}", True,
                   f"detected {want} — {hit[0].message[:70]}")
        else:
            h.case(f"inject {defect}", False,
                   f"rc={rc}, wanted {want}, gate codes: "
                   f"{sorted({d.code for d in gate})}")
    # waiver discipline end-to-end: a waiver with a justification un-flips
    # exactly its finding; nothing else
    progs = inject("lost_donation", base)
    rc_bad, _, gate = audit(progs, base, waivers={})
    fids = {getattr(d, "finding_id", "") for d in gate}
    rc_ok, _, _ = audit(progs, base,
                        waivers={fid: "selftest" for fid in fids})
    h.case("waiver un-flips the gate", rc_bad == 1 and rc_ok == 0,
           f"rc {rc_bad} -> {rc_ok} with {len(fids)} waiver(s)")
    return h.finish(
        f"COST SELFTEST OK: {len(DEFECTS)} defect classes detected, "
        "clean fixture audits clean, waiver discipline pinned",
        "COST SELFTEST FAIL: {failures} expectation(s) violated")


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
    ap.add_argument("-v", "--verbose", action="store_true")
    args = ap.parse_args(argv)

    if args.selftest:
        return selftest()
    if args.inject:
        base = _fixture_baseline()
        rc, _, _ = audit(inject(args.inject, base), base, waivers={})
        return rc

    base_programs, waivers = ({}, {}) if args.no_baseline \
        else load_baseline(args.baseline)
    programs = record_all(only=args.program)
    rc, manifests, gate = audit(programs, base_programs, waivers,
                                skip_contract=args.write_baseline,
                                report_stale=args.program is None,
                                verbose=args.verbose)
    if args.write_baseline:
        if args.program:
            raise SystemExit("--write-baseline needs the full program set")
        write_baseline(manifests, waivers, args.baseline)
    return rc


if __name__ == "__main__":
    sys.exit(main())
