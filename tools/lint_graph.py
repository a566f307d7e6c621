"""Graph-lint gate: record every in-repo model-family program and run the
static analyzer suite (paddle_tpu/static/analysis) over each.

Exit code 0 iff every program lints clean at error severity. Each finding
prints as ``<program>: PT-XXXX-NNN [severity] op#i type @file:line: message``.

Usage:
    JAX_PLATFORMS=cpu python tools/lint_graph.py              # full zoo gate
    python tools/lint_graph.py --family bert                  # one family
    python tools/lint_graph.py --fail-on warning              # stricter gate
    python tools/lint_graph.py --inject shape_mismatch        # seeded defect
    python tools/lint_graph.py --selftest                     # all injections

``--inject`` plants exactly one defect of a known class into one recorded
program (or a tiny synthetic run for cache-hazard classes) and must flip the
exit code — tests/test_ci_gates.py pins this behavior. ``--selftest`` loops
every defect class in-process and exits 0 iff each one was detected with its
expected diagnostic code.
"""

from __future__ import annotations

import argparse
import sys

import _selftest

ROOT = _selftest.bootstrap()

import jax  # noqa: E402
import numpy as np  # noqa: E402

DEFECTS = ("shape_mismatch", "fp64_leak", "recompile_key",
           "unseeded_stochastic", "bad_mesh_axis", "uneven_shard",
           "unused_param", "async_borrow", "host_sync")

EXPECTED_CODE = {
    "shape_mismatch": "PT-SHAPE-001",
    "fp64_leak": "PT-DTYPE-001",
    "recompile_key": "PT-TRACE-001",
    "unseeded_stochastic": "PT-TRACE-003",
    "bad_mesh_axis": "PT-SPMD-001",
    "uneven_shard": "PT-SPMD-002",
    "unused_param": "PT-GRAPH-003",
    "async_borrow": "PT-TRACE-005",
    # warning-severity class: the selftest lints it at --fail-on warning
    "host_sync": "PT-TRACE-004",
}


# ---------------------------------------------------------------------------
# model-family recording
# ---------------------------------------------------------------------------

def _spec(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), np.dtype(dtype))


def record_bert():
    import paddle_tpu  # noqa: F401
    from paddle_tpu.models import BertConfig, BertForMaskedLM
    from paddle_tpu.static.analysis import layer_to_program

    m = BertForMaskedLM(BertConfig.tiny())
    prog = layer_to_program(m, _spec((2, 16), np.int32), _spec((2, 16), np.int32),
                            input_names=["input_ids", "token_type_ids"])
    return prog, m


def record_gpt():
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.static.analysis import layer_to_program

    cfg = GPTConfig.tiny() if hasattr(GPTConfig, "tiny") else GPTConfig()
    m = GPTForCausalLM(cfg)
    prog = layer_to_program(m, _spec((2, 16), np.int32),
                            input_names=["input_ids"])
    return prog, m


def record_llama():
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.static.analysis import layer_to_program

    cfg = LlamaConfig.tiny() if hasattr(LlamaConfig, "tiny") else LlamaConfig()
    m = LlamaForCausalLM(cfg)
    prog = layer_to_program(m, _spec((2, 16), np.int32),
                            input_names=["input_ids"])
    return prog, m


def record_vit():
    from paddle_tpu.vision.models import ViTConfig, VisionTransformer
    from paddle_tpu.static.analysis import layer_to_program

    m = VisionTransformer(ViTConfig.tiny())
    prog = layer_to_program(m, _spec((2, 3, 32, 32), np.float32),
                            input_names=["images"])
    return prog, m


def record_unet():
    from paddle_tpu.models import UNet2DConditionModel, UNetConfig
    from paddle_tpu.static.analysis import layer_to_program

    cfg = UNetConfig.tiny()
    m = UNet2DConditionModel(cfg)
    prog = layer_to_program(
        m, _spec((2, 4, 16, 16), np.float32), _spec((2,), np.int32),
        _spec((2, 6, cfg.cross_attention_dim), np.float32),
        input_names=["sample", "timesteps", "context"])
    return prog, m


def record_serving():
    """The fused mega-step serving program (inference/serving.py, ISSUE
    10): the ONE device program a 128-256-slot engine dispatches per
    decode block — decode + in-graph sampling + position advance over
    every row, inactive rows masked. Recorded through the engine's own
    ``_mega_step_fn`` so the linted program IS the production program
    (params as named inputs; caches/tables/sampling state as baked
    constants of the trace). The raw step fn also rides along as a
    ``static_fns`` context entry, so the PT-TRACE-004 host-sync scan
    covers the mega-step source — a ``.numpy()``/``.item()`` creeping
    into the fused step path is exactly the per-slot host sync the
    big-batch refactor removed."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import (ContinuousBatchingEngine,
                                              PrefixCacheConfig)
    from paddle_tpu.jit.api import _collect_state
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.static.analysis import trace_to_program

    paddle.seed(11)
    cfg = LlamaConfig.tiny(num_hidden_layers=1)
    m = LlamaForCausalLM(cfg)
    eng = ContinuousBatchingEngine(
        m, max_batch=8, max_len=32, page_size=8, block_size=2,
        prefix_cache=PrefixCacheConfig(prefill_chunk=8))
    run = eng._mega_step_fn()
    names, tensors = _collect_state(m)
    param_structs = [_spec(t._data.shape, t._data.dtype) for t in tensors]
    n_p = len(param_structs)
    kv, tables = eng.caches["kv"], eng.caches["tables"]
    seeds, temps, tops, topks = eng._dev_samp

    def flat(*args):
        params, (toks, pos, act) = list(args[:n_p]), args[n_p:]
        return run(params, toks, kv, tables, pos, act, seeds, temps, tops,
                   topks, n_steps=2, do_sample=True)

    B = eng.max_batch
    prog = trace_to_program(
        flat, _spec((B,), np.int32), _spec((B,), np.int32),
        _spec((B,), np.bool_), input_names=["toks", "pos", "act"],
        param_structs=param_structs, param_names=names,
        param_tensors=tensors)
    prog._static_fns = [run]        # host-sync scan target (lint_family)
    return prog, m


def record_migration():
    """The PR 12 KV-block migration programs (inference/disagg.py
    KVChainCodec via ops/paged_attention.py): the per-layer page gather
    that exports a chain plus ``scatter_chain_pages`` that imports it,
    traced as one roundtrip so the disagg path has the same graph-lint
    coverage as the mega-step. The linted program IS the cost auditor's
    ``migration`` program (ONE recorder, tools/audit_program_cost.py —
    lint coverage and cost coverage cannot silently diverge).
    ``gather_chain_pages`` itself is DELIBERATELY host-side (its
    np.asarray readback is the fence that orders the export behind
    in-flight decode writes — docs/SERVING.md), so what is traced is its
    device gather expression."""
    import types

    import audit_program_cost

    prog, _ = audit_program_cost.record_migration()
    # no Layer behind this family: the lint context needs a parameters()
    model = types.SimpleNamespace(parameters=lambda: [])
    return prog, model


FAMILIES = {
    "bert": record_bert,
    "gpt": record_gpt,
    "llama": record_llama,
    "vit": record_vit,
    "unet": record_unet,
    "serving": record_serving,
    "migration": record_migration,
}


# ---------------------------------------------------------------------------
# seeded-defect injection
# ---------------------------------------------------------------------------

def inject(defect, prog, model, context):
    """Plant one defect into ``prog`` / the analysis context. Returns the
    context dict handed to run_analysis."""
    import paddle_tpu as paddle
    from paddle_tpu.core.static_graph import Operation
    from paddle_tpu.framework import random as frandom

    blk = prog.global_block()
    first = next(op for op in blk.ops if op.outputs)

    if defect == "shape_mismatch":
        v = first.outputs[0]
        v._data = jax.ShapeDtypeStruct(tuple(v._data.shape) + (1,),
                                       v._data.dtype)
    elif defect == "fp64_leak":
        v = first.outputs[0]
        v._data = jax.ShapeDtypeStruct(tuple(v._data.shape), np.float64)
    elif defect == "recompile_key":
        # per-step feed-signature churn: one tiny program, three batch shapes
        from paddle_tpu import static
        from paddle_tpu.static import Executor, program_guard

        paddle.enable_static()
        try:
            main = static.Program()
            with program_guard(main):
                x = static.data("x", [None, 4], "float32")
                y = x * 2.0
            exe = Executor()
            for b in (1, 2, 3):
                exe.run(main, feed={"x": np.ones((b, 4), np.float32)},
                        fetch_list=[y])
        finally:
            paddle.disable_static()
        context["executors"] = [exe]
    elif defect == "unseeded_stochastic":
        frandom._global["seeded"] = False
        prog.random_seed = 0

        def draw(shape=(4,)):
            return jax.random.uniform(jax.random.key(0), shape)

        op = Operation(len(blk.ops), "uniform_random_injected", draw, [], {},
                       src="tools/lint_graph.py:inject")
        blk.ops.append(op)
        op.outputs.append(blk.create_var((4,), np.float32,
                                         name="injected_uniform", op=op))
    elif defect in ("bad_mesh_axis", "uneven_shard"):
        from paddle_tpu.distributed.auto_parallel import (ProcessMesh,
                                                          Replicate, Shard)

        target = None
        for op in blk.ops:
            for t in list(op.inputs) + list(op.captured):
                if getattr(t, "_data", None) is not None and \
                        len(getattr(t._data, "shape", ())) >= 1:
                    target = t
                    break
            if target is not None:
                break
        assert target is not None, "no shardable tensor in program"
        dim0 = int(target._data.shape[0])
        if defect == "bad_mesh_axis":
            mesh = ProcessMesh(shape=[2, 2], dim_names=["dp", "mp"])
            target.process_mesh = mesh
            target.placements = [Shard(99), Replicate()]
        else:
            mesh = ProcessMesh(shape=[dim0 + 1], dim_names=["mp"])
            target.process_mesh = mesh
            target.placements = [Shard(0)]  # dim0 % (dim0+1) != 0
    elif defect == "unused_param":
        ghost = paddle.Tensor(np.zeros((3, 3), np.float32))
        ghost.is_parameter = True
        ghost.name = "ghost_weight"
        params = list(context.get("parameters") or [])
        params.append(ghost)
        context["parameters"] = params
    elif defect == "async_borrow":
        # the PR-4 serving bug class, reduced: upload a host buffer with
        # jnp.asarray, then mutate it — the async transfer may read the
        # post-mutation bytes (PT-TRACE-005; a .copy() upload lints clean)
        def dispatch_tables(tables_host):
            import jax.numpy as jnp

            dev = jnp.asarray(tables_host)
            tables_host[0] = -1          # parks the row AFTER the borrow
            return dev

        context["borrow_fns"] = [dispatch_tables]
    elif defect == "host_sync":
        # the per-slot host sync the fused mega-step removed, reduced: a
        # token-value read (.item()) inside the traced step fn — exactly
        # what would drag a 256-row device program back to one host round
        # trip per slot (PT-TRACE-004; the real mega-step source is clean)
        def mega_step_with_sync(toks, pos):
            n_live = int(pos.item())     # host sync inside the traced step
            return toks[:n_live]

        context["static_fns"] = (list(context.get("static_fns") or [])
                                 + [mega_step_with_sync])
    else:
        raise SystemExit(f"unknown defect {defect!r} (choose: {DEFECTS})")
    return context


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def lint_family(name, defect=None, fail_on="error"):
    """Record one family, (optionally) inject, analyze. Returns (report,
    n_gate_findings)."""
    import paddle_tpu as paddle
    from paddle_tpu.static.analysis import Severity, run_analysis

    paddle.seed(2024)  # explicit seed: stochastic recordings are reproducible
    prog, model = FAMILIES[name]()
    context = {
        "targets": getattr(prog, "_outputs", None),
        "parameters": list(model.parameters()),
        # recording may attach traced callables (the serving mega-step fn)
        # for the PT-TRACE-002/004 source scans
        "static_fns": list(getattr(prog, "_static_fns", ())),
    }
    if defect is not None:
        context = inject(defect, prog, model, context)
    report = run_analysis(
        prog,
        targets=context.get("targets"),
        parameters=context.get("parameters"),
        executors=context.get("executors", ()),
        static_fns=context.get("static_fns", ()),
        borrow_fns=context.get("borrow_fns", ()),
    )
    floor = Severity.ERROR if fail_on == "error" else Severity.WARNING
    return prog, report, report.at_least(floor)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--family", choices=sorted(FAMILIES), default=None,
                    help="lint one family (default: all)")
    ap.add_argument("--inject", choices=DEFECTS, default=None,
                    help="plant one seeded defect (lints --family or bert)")
    ap.add_argument("--fail-on", choices=("error", "warning"),
                    default="error")
    ap.add_argument("--selftest", action="store_true",
                    help="verify every injection class flips the gate")
    ap.add_argument("-v", "--verbose", action="store_true",
                    help="also print warning/info findings")
    args = ap.parse_args(argv)

    if args.selftest:
        return selftest(args.family or "bert")

    families = [args.family] if args.family else sorted(FAMILIES)
    if args.inject:
        families = [args.family or "bert"]

    rc, linted = 0, 0
    for name in families:
        prog, report, gate = lint_family(name, defect=args.inject,
                                         fail_on=args.fail_on)
        linted += 1
        shown = gate if not args.verbose else list(report)
        for d in shown:
            print(f"{name}: {d.format()}")
        status = "FAIL" if gate else "ok"
        print(f"[{status}] {name}: {prog.num_ops} ops, "
              f"{len(report.errors())} error(s), "
              f"{len(report.warnings())} warning(s)")
        if gate:
            rc = 1
    print(f"LINTED {linted} program(s): "
          f"{'CLEAN' if rc == 0 else 'FINDINGS AT GATE SEVERITY'}")
    return rc


def selftest(family):
    """Every defect class must flip the gate with its expected code; the
    clean program must not (harness: tools/_selftest.py)."""
    h = _selftest.Harness("LINT")
    _, clean_report, clean_gate = lint_family(family)
    if clean_gate:
        print(f"SELFTEST FAIL: clean '{family}' has gate findings:")
        for d in clean_gate:
            print("  " + d.format())
        return 1
    print(f"clean {family}: ok ({len(clean_report)} sub-gate finding(s))")
    for defect in DEFECTS:
        # lint_family seeds (paddle.seed) before recording; the
        # unseeded_stochastic inject() un-seeds again afterwards itself.
        # host_sync is a WARNING-severity class (PT-TRACE-004): it must
        # flip the gate at --fail-on warning, the stricter operator mode
        _, report, gate = lint_family(
            family, defect=defect,
            fail_on="warning" if defect == "host_sync" else "error")
        code = EXPECTED_CODE[defect]
        hit = [d for d in gate if d.code == code]
        if hit:
            h.case(f"inject {defect}", True,
                   f"detected {code} — {hit[0].message[:80]}")
        else:
            h.case(f"inject {defect}", False,
                   f"wanted {code}, gate codes: "
                   f"{sorted({d.code for d in gate})}")
    return h.finish(
        f"SELFTEST OK: {len(DEFECTS)} defect classes detected, "
        "clean program lints clean",
        "SELFTEST FAIL: {failures} defect class(es) undetected")


if __name__ == "__main__":
    sys.exit(main())
