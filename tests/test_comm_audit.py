"""PT-COMM — the static collective-communication auditor
(paddle_tpu/static/comm, docs/STATIC_ANALYSIS.md "Collective
communication" section).

Everything here is PURE TRACING — shard_map under a symbolic
``AbstractMesh`` through ``trace_to_program``, no XLA compile, no
devices — so the whole module runs in seconds. The end-to-end pins (the
real MULTICHIP sweep, the seeded-defect selftest, the zero-compile
counter) run as subprocess gates in tests/test_ci_gates.py via
tools/audit_collectives.py.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from paddle_tpu.static.analysis import run_analysis, trace_to_program
from paddle_tpu.static.comm import (CollectiveCommPass, CommManifest,
                                    CommPathSpec, abstract_mesh,
                                    check_comm_contract, check_gather_reduce,
                                    check_loop_invariant_collectives,
                                    check_mesh_scaling, check_replication,
                                    compute_comm_manifest, iter_collectives,
                                    mesh_scaling_verdict, mesh_spec,
                                    wire_bytes)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _spec(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), np.dtype(dtype))


def _trace(fn, *structs, names=None):
    return trace_to_program(fn, *structs,
                            input_names=names or [f"in{i}" for i
                                                  in range(len(structs))])


def _sharded(body, width=4, in_specs=None, out_specs=P(), axes=None):
    mesh = abstract_mesh(axes or {"x": width})
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# ---------------------------------------------------------------------------
# ring wire-byte rules
# ---------------------------------------------------------------------------

def test_wire_bytes_ring_formulas():
    """Per device per dispatch, n-member ring, b payload bytes:
    psum 2(n-1)/n*b, all_gather (n-1)*b, reduce_scatter and all_to_all
    (n-1)/n*b, ppermute b."""
    b, n = 1024.0, 4
    assert wire_bytes("psum", b, n) == pytest.approx(2 * 3 / 4 * b)
    assert wire_bytes("pmax", b, n) == pytest.approx(2 * 3 / 4 * b)
    assert wire_bytes("all_gather", b, n) == pytest.approx(3 * b)
    assert wire_bytes("reduce_scatter", b, n) == pytest.approx(3 / 4 * b)
    assert wire_bytes("all_to_all", b, n) == pytest.approx(3 / 4 * b)
    assert wire_bytes("ppermute", b, n) == pytest.approx(b)


def test_wire_bytes_degenerate_group_is_free():
    """A group of one moves nothing — the same rule that makes the
    eager single-controller collective wrappers semantically free."""
    for prim in ("psum", "all_gather", "reduce_scatter", "all_to_all",
                 "ppermute", "pmin", "pmax"):
        assert wire_bytes(prim, 4096.0, 1) == 0.0


# ---------------------------------------------------------------------------
# the collective walker
# ---------------------------------------------------------------------------

def _census_prog(width=4):
    """shard_map body with one psum, one direct all_gather, and one
    loop-INVARIANT all_gather inside a scan of length 3."""

    def body(w, x):
        h = lax.psum(x @ w, "x")                      # [8, 16]
        g = lax.all_gather(x, "x", axis=0, tiled=True)

        def sbody(c, _):
            gw = lax.all_gather(w, "x", axis=0, tiled=True)  # w: scan const
            return c + gw.sum(), None

        s, _ = lax.scan(sbody, jnp.float32(0), jnp.arange(3))
        return h.sum() + g.sum() + s

    fn = _sharded(body, width=width,
                  in_specs=(P("x", None), P(None, None)))
    return _trace(fn, _spec((4 * width, 16), np.float32),
                  _spec((8, 4), np.float32), names=["w", "x"])


def test_iter_collectives_census():
    cs = list(iter_collectives(_census_prog()))
    by_prim = {}
    for c in cs:
        by_prim.setdefault(c.prim, []).append(c)
    assert sorted(by_prim) == ["all_gather", "psum"]
    assert len(by_prim["psum"]) == 1 and len(by_prim["all_gather"]) == 2
    for c in cs:
        assert c.axes == ("x",)
        assert c.group_size == 4        # resolved from the shard_map mesh
        assert c.axis_sizes.get("x") == 4


def test_scan_multiplies_dispatches_and_marks_invariance():
    cs = list(iter_collectives(_census_prog()))
    in_scan = [c for c in cs if "/scan" in c.scope]
    assert len(in_scan) == 1
    c = in_scan[0]
    assert c.mult == 3                  # scan length multiplies dispatches
    assert c.loop_invariant             # gathers a scan const every step
    assert all(o.mult == 1 and not o.loop_invariant
               for o in cs if o is not c)


def test_scan_carry_dependent_collective_not_invariant():
    def body(x):
        def sbody(c, _):
            return lax.psum(c * 2.0, "x"), None   # depends on the carry

        s, _ = lax.scan(sbody, x.sum(), jnp.arange(5))
        return s

    fn = _sharded(body, in_specs=(P(None, None),))
    prog = _trace(fn, _spec((4, 4), np.float32))
    (c,) = iter_collectives(prog)
    assert c.mult == 5 and "/scan" in c.scope
    assert not c.loop_invariant


def test_wire_bytes_use_per_shard_payload():
    """Byte volumes come from the avals the collective actually sees
    INSIDE shard_map (per-shard), not the global operand shapes."""
    cs = {c.prim: c for c in iter_collectives(_census_prog())}
    # x is [8, 16] per shard in f32 -> 512 B payload
    assert cs["psum"].payload_bytes == 8 * 16 * 4
    assert cs["psum"].bytes_wire == pytest.approx(2 * 3 / 4 * 512)


# ---------------------------------------------------------------------------
# manifest + mesh-scaling law
# ---------------------------------------------------------------------------

def test_comm_manifest_census_and_roundtrip():
    prog = _census_prog()
    spec = CommPathSpec("census@4", mesh={"x": 4}, width=4)
    m = compute_comm_manifest(prog, name="census@4", spec=spec)
    assert m.collective_eqns == 3
    assert m.collectives == {"psum": 1, "all_gather": 2}
    assert m.dispatches == 1 + 1 + 3            # scan body counts 3x
    assert m.loop_invariant_eqns == 1
    assert m.per_axis["x"]["eqns"] == 3
    assert m.comm_bytes == pytest.approx(m.per_axis["x"]["bytes"])
    assert prog._comm_manifest is m             # attached for reuse
    m2 = CommManifest.from_dict(json.loads(json.dumps(m.to_dict())))
    assert m2.collectives == m.collectives
    assert m2.comm_bytes == pytest.approx(m.comm_bytes)
    assert m2.width == 4 and not m2.unsharded


def _man(width, comm_bytes, eqns=2):
    return CommManifest(program=f"fam@{width}", width=width,
                        comm_bytes=comm_bytes, collective_eqns=eqns)


def test_mesh_scaling_law_ring_envelope():
    """(n-1)-shaped growth is the legal envelope: 2 -> 4 devices may
    TRIPLE ring bytes (ratio 1.0); an O(n^2) family fails."""
    rec = mesh_scaling_verdict([_man(2, 1000.0), _man(4, 3000.0)])
    assert rec["verdict"] == "<=ring"
    assert rec["worst_ring_ratio"] == pytest.approx(1.0)
    rec = mesh_scaling_verdict([_man(2, 1000.0), _man(4, 4000.0)])
    assert rec["verdict"] == "superlinear"
    # comm appearing from nothing with width is superlinear by definition
    rec = mesh_scaling_verdict([_man(2, 0.0, eqns=0), _man(4, 64.0)])
    assert rec["verdict"] == "superlinear"
    assert rec["worst_ring_ratio"] == "inf"


def test_mesh_scaling_needs_width_pair():
    with pytest.raises(ValueError, match="widths"):
        mesh_scaling_verdict([_man(2, 10.0)])
    with pytest.raises(ValueError, match="widths"):
        mesh_scaling_verdict([_man(2, 10.0), CommManifest(program="p")])


def test_check_mesh_scaling_finding_is_stable():
    ms = [_man(2, 1000.0), _man(4, 8000.0)]
    (d,) = check_mesh_scaling(ms)
    assert d.code == "PT-COMM-003"
    assert d.finding_id == "PT-COMM-003:fam:superlinear"
    assert ms[0].scaling["verdict"] == "superlinear"
    assert check_mesh_scaling([_man(2, 1000.0), _man(4, 3000.0)]) == []


# ---------------------------------------------------------------------------
# program-local checks
# ---------------------------------------------------------------------------

def test_check_replication_flags_large_replicated_operand():
    def body(w, r):
        return (w.sum() + r.sum())[None]

    fn = _sharded(body, in_specs=(P("x", None), P(None, None)),
                  out_specs=P("x"))
    big = _trace(fn, _spec((8, 8), np.float32),
                 _spec((512, 512), np.float32), names=["w", "r"])
    (d,) = check_replication(big, "prog")
    assert d.code == "PT-COMM-001"
    assert d.finding_id == "PT-COMM-001:prog:replicated:in1:512x512"
    # small replicated operands are fine (scalars/biases ride along)
    small = _trace(fn, _spec((8, 8), np.float32),
                   _spec((8, 8), np.float32), names=["w", "r"])
    assert check_replication(small, "prog") == []


def test_check_replication_ignores_fully_replicated_programs():
    """No sharded sibling -> replication IS the contract; and the ids
    carry no trace positions, so retracing keeps them identical."""
    def body(r):
        return r.sum()[None]

    fn = _sharded(body, in_specs=(P(None, None),), out_specs=P("x"))
    prog = _trace(fn, _spec((512, 512), np.float32))
    assert check_replication(prog, "prog") == []


def test_check_loop_invariant_collective():
    (d,) = [x for x in check_loop_invariant_collectives(
        _census_prog(), "prog") if x.code == "PT-COMM-002"]
    assert d.finding_id == "PT-COMM-002:prog:all_gather/shard_map/scan"
    assert "hoist" in d.message or "every step" in d.message


def test_check_gather_reduce_fires_only_on_gathered_dim():
    def bad(x):
        g = lax.all_gather(x, "x", axis=0, tiled=True)
        return g.sum()                       # reduce eats the gathered dim

    def ok(x):
        g = lax.all_gather(x, "x", axis=0, tiled=True)
        return g.sum(axis=1).max()           # reduce over a local dim only

    pb = _trace(_sharded(bad, in_specs=(P("x", None),)),
                _spec((16, 8), np.float32))
    hits = [d for d in check_gather_reduce(pb, "p")
            if d.code == "PT-COMM-004"]
    assert hits and hits[0].finding_id.startswith(
        "PT-COMM-004:p:all_gather+reduce_sum")
    po = _trace(_sharded(ok, in_specs=(P("x", None),)),
                _spec((16, 8), np.float32))
    assert [d for d in check_gather_reduce(po, "p")
            if d.code == "PT-COMM-004"] == []


def test_check_comm_contract_drift_and_unbaselined():
    spec = CommPathSpec("census@4", mesh={"x": 4}, width=4)
    m = compute_comm_manifest(_census_prog(), name="census@4", spec=spec)
    base = m.to_dict()
    assert check_comm_contract(m, base) == []
    (d,) = check_comm_contract(m, None)
    assert d.code == "PT-COMM-005"
    assert d.finding_id == "PT-COMM-005:census@4:unbaselined"
    shrunk = dict(base, collectives={"psum": 1, "all_gather": 1},
                  comm_bytes=base["comm_bytes"] / 4)
    codes = {d.finding_id for d in check_comm_contract(m, shrunk)}
    assert "PT-COMM-005:census@4:all_gather-drift" in codes
    assert "PT-COMM-005:census@4:comm-bytes-blowup" in codes


def test_check_comm_contract_unsharded():
    spec = CommPathSpec("serve", unsharded=True)
    m = compute_comm_manifest(_census_prog(), name="serve", spec=spec)
    codes = {d.finding_id for d in check_comm_contract(m, m.to_dict())}
    assert "PT-COMM-005:serve:unsharded-contract" in codes


# ---------------------------------------------------------------------------
# pass composition
# ---------------------------------------------------------------------------

def test_comm_pass_composes_with_run_analysis():
    prog = _census_prog()
    p = CollectiveCommPass(spec=CommPathSpec("census@4", mesh={"x": 4},
                                             width=4))
    rep = run_analysis(prog, passes=[p])
    # the fixture's two gather+sum sites also (correctly) trip PT-COMM-004
    assert sorted(d.code for d in rep) == ["PT-COMM-002", "PT-COMM-004",
                                           "PT-COMM-004"]
    assert p.manifest is not None and p.manifest.collective_eqns == 3
    assert prog._comm_manifest is p.manifest
    rep2 = run_analysis(prog, passes=[CollectiveCommPass(
        spec=CommPathSpec("census@4"),
        suppress=("PT-COMM-002", "PT-COMM-004"))])
    assert len(rep2) == 0


# ---------------------------------------------------------------------------
# mesh helpers
# ---------------------------------------------------------------------------

def test_abstract_mesh_and_spec_helpers():
    mesh = abstract_mesh({"dp": 2, "tp": 4})
    assert dict(mesh.shape) == {"dp": 2, "tp": 4}
    with pytest.raises(ValueError):
        abstract_mesh({})
    axes = {"dp": 2, "tp": 4}
    assert mesh_spec(axes, "dp", "tp") == P("dp", "tp")
    # absent axes are masked to None so one spec serves every mesh shape
    assert mesh_spec(axes, "fsdp", "tp") == P(None, "tp")
    assert mesh_spec(axes, ("dp", "fsdp"), None) == P("dp", None)
    assert mesh_spec(axes) == P()


# ---------------------------------------------------------------------------
# contract-program hookpoints (distributed.auto_parallel.comm_programs)
# ---------------------------------------------------------------------------

def test_train_step_comm_dp_only_census():
    from paddle_tpu.distributed.auto_parallel import train_step_comm

    fn, structs, names, axes = train_step_comm({"dp": 2, "pp": 1})
    assert axes == {"dp": 2}            # size-1 axes are dropped
    m = compute_comm_manifest(_trace(fn, *structs, names=names),
                              name="dp", spec=CommPathSpec("dp", mesh=axes))
    assert set(m.collectives) == {"psum"}       # grads + loss only
    assert m.collectives["psum"] == 3
    assert m.per_axis["dp"]["eqns"] == 3


def test_moe_combine_comm_census():
    from paddle_tpu.distributed.auto_parallel import moe_combine_comm

    fn, structs, names, axes = moe_combine_comm(4)
    m = compute_comm_manifest(_trace(fn, *structs, names=names),
                              name="moe", spec=CommPathSpec("moe", mesh=axes))
    assert m.collectives == {"all_to_all": 2}   # dispatch + combine
    assert m.per_axis["ep"]["eqns"] == 2


# ---------------------------------------------------------------------------
# eager collective wrappers under a world of 1 (satellite: the byte rules
# agree with the degenerate-group semantics)
# ---------------------------------------------------------------------------

class TestFunctionalWorldOfOne:
    """distributed.communication.functional over a group of ONE rank
    (the test harness forces 8 host devices, so the world group is not
    usable for this): every wrapper must degenerate to the
    zero-communication identity the ring rule predicts
    (wire_bytes(prim, b, 1) == 0) — the eager single-controller regime
    the module docstring promises."""

    def _g1(self):
        from paddle_tpu.distributed.communication.group import Group

        # unbound axis name -> the eager branch; one rank -> n == 1
        return Group([0], 97, axis_name="pt_comm_test_unbound")

    def test_all_reduce_identity(self):
        import paddle_tpu as paddle
        from paddle_tpu.distributed.communication.functional import \
            all_reduce

        t = paddle.to_tensor(np.arange(6, dtype=np.float32).reshape(2, 3))
        nbytes = t.numpy().nbytes
        all_reduce(t, group=self._g1())   # SUM over a group of one
        np.testing.assert_allclose(t.numpy(),
                                   np.arange(6, dtype=np.float32)
                                   .reshape(2, 3))
        assert wire_bytes("psum", nbytes, 1) == 0.0

    def test_all_gather_single_copy(self):
        import paddle_tpu as paddle
        from paddle_tpu.distributed.communication.functional import (
            all_gather, all_gather_into_tensor)

        x = np.ones((3, 2), np.float32)
        parts = all_gather(None, paddle.to_tensor(x), group=self._g1())
        assert len(parts) == 1
        np.testing.assert_allclose(parts[0].numpy(), x)
        out = all_gather_into_tensor(None, paddle.to_tensor(x),
                                     group=self._g1())
        np.testing.assert_allclose(out.numpy(), x)   # concat of one shard
        assert wire_bytes("all_gather", x.nbytes, 1) == 0.0

    def test_reduce_scatter_keeps_own_shard(self):
        import paddle_tpu as paddle
        from paddle_tpu.distributed.communication.functional import \
            reduce_scatter

        x = np.arange(8, dtype=np.float32).reshape(4, 2)
        out = paddle.zeros([4, 2])
        reduce_scatter(out, paddle.to_tensor(x), group=self._g1())
        np.testing.assert_allclose(out.numpy(), x)   # n=1: shard == input
        assert wire_bytes("reduce_scatter", x.nbytes, 1) == 0.0

    def test_alltoall_identity(self):
        import paddle_tpu as paddle
        from paddle_tpu.distributed.communication.functional import (
            alltoall, alltoall_single)

        x = np.arange(4, dtype=np.float32).reshape(1, 4)
        parts = alltoall(None, [paddle.to_tensor(x[0])], group=self._g1())
        assert len(parts) == 1
        np.testing.assert_allclose(parts[0].numpy(), x[0])
        out = alltoall_single(None, paddle.to_tensor(x), group=self._g1())
        np.testing.assert_allclose(out.numpy(), x)
        assert wire_bytes("all_to_all", x.nbytes, 1) == 0.0

    def test_broadcast_identity(self):
        import paddle_tpu as paddle
        from paddle_tpu.distributed.communication.functional import \
            broadcast

        x = np.full((2, 2), 7.0, np.float32)
        t = paddle.to_tensor(x)
        broadcast(t, src=0, group=self._g1())
        np.testing.assert_allclose(t.numpy(), x)
        assert wire_bytes("ppermute", x.nbytes, 1) == 0.0


# ---------------------------------------------------------------------------
# gate plumbing (in-process — the subprocess pins live in test_ci_gates)
# ---------------------------------------------------------------------------

def test_comm_baseline_waiver_requires_justification(tmp_path):
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import audit_collectives as gate
    finally:
        sys.path.pop(0)
    p = tmp_path / "b.json"
    p.write_text(json.dumps({"programs": {},
                             "waivers": [{"id": "PT-COMM-001:x:rep"}]}))
    with pytest.raises(SystemExit, match="justification"):
        gate.load_baseline(str(p))


def test_committed_comm_baseline_loads_and_covers_registry():
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import audit_collectives as gate
    finally:
        sys.path.pop(0)
    programs, waivers = gate.load_baseline()
    # every recorded MULTICHIP shape has its per-mesh manifest committed
    for key in gate.MULTICHIP_MESHES:
        name = f"mesh_train_step@{key}"
        assert name in programs, name
        assert programs[name]["collective_eqns"] > 0, name
    # serving programs carry the per-mesh tp contract (column-parallel:
    # all_gather-only — a psum appearing here would break byte-identity)
    for name in ("mega_step@8", "spec_verify@8", "prefill_chunk"):
        assert programs[name]["unsharded"] is False
        assert programs[name]["mesh"] == {"tp": 2}
        assert programs[name]["collective_eqns"] > 0
        assert set(programs[name]["collectives"]) == {"all_gather"}
    for fam in ("flash_ring", "moe_combine", "tp_train"):
        for w in gate.SCALING_WIDTHS:
            assert programs[f"{fam}@{w}"]["scaling"]["verdict"] == "<=ring"
