"""``metrics/_program.py`` and the seven readers built on the program's
names: on a trace written by hand, whose answers are known, and on the
trace recorded on the chip by ``scratch/record_scoped_trace.py``."""

import os
import shutil
import types

import pytest

from chipbench.harness import loader
from chipbench.metrics import _program

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 10 ** 9        # picoseconds


def _reader(name):
    return loader._module("metrics", name, name)


# ---- a trace written by hand -------------------------------------------------

def _event(meta, offset_ps, dur_ps, stats=""):
    return (f"events {{ metadata_id: {meta} offset_ps: {offset_ps} "
            f"duration_ps: {dur_ps} {stats} }}")


def _plane(pid, name, lines, metas, stat_names):
    esc = lambda n: n.replace('"', '\\"')               # noqa: E731
    md = "\n".join(
        f'event_metadata {{ key: {k} value {{ id: {k} name: "{esc(n)}" '
        + (f'stats {{ metadata_id: 1 str_value: "{op}" }} ' if op else "")
        + "} }" for k, (n, op) in metas.items())
    sm = "\n".join(
        f'stat_metadata {{ key: {k} value {{ id: {k} name: "{n}" }} }}'
        for k, n in stat_names.items())
    ls = "\n".join(
        f'lines {{ id: {i} name: "{ln}" timestamp_ns: 1000\n'
        f'{chr(10).join(ev)}\n}}' for i, (ln, ev) in enumerate(lines.items()))
    return f'planes {{ id: {pid} name: "{name}"\n{md}\n{sm}\n{ls}\n}}'


def _known_text(named: bool) -> str:
    """Device 0 runs two decode blocks and one train step.

    Block A 0-10 ms: a copy 0-2 (fixed part), the token loop ``while`` 2-10
    holding attention 2-5, the sampler's sort 5-6 and gather 6-8, the paged
    kernel 8-10. Idle 10-14. Block B 14-20: copy 14-15, ``while`` 15-20
    holding attention 15-18 and the sampler 18-20. Idle 20-30. Train step
    30-40: fused CE forward 30-32 and backward 36-39, the rest matmuls.

    Host: step 1 0-13 ms (dispatch 0-1, wait 1-11, emit 11-13), then
    nothing until step 2 13.5-21 (dispatch 13.5-14.2, wait 14.2-20.5, emit
    20.5-21), and the train step 29-41.

    ``named=False`` is the same trace from a program that names nothing.
    """
    blk = "jit(pt_decode_block)/while/body/" if named else "jit(run)/"
    trn = "jit(pt_train_step)/" if named else "jit(train_step)/"
    sc = (lambda s: s) if named else (lambda s: "")
    metas = {
        1: ("%copy.1 = bf16[8,16]", ""),
        2: ("%while.2 = (s32[]) while(...)", blk.split("/")[0] + "/while"),
        3: ("%fusion.3 = bf16[8]", blk + sc("pt.attn/") + "dot_general:"),
        4: ("%sort.4 = f32[8,100]",
            blk + sc("pt.sampler/") + "jit(argsort)/sort:"),
        5: ("%fusion.5 = f32[800]", blk + sc("pt.sampler/") + "gather:"),
        6: (("%pt_paged_decode.6" if named else "%closed_call.6")
            + ' = bf16[8] custom-call(), custom_call_target="tpu_custom_call"',
            blk + sc("pt.attn/pt_paged_decode/") + "pallas_call:"),
        7: ("%fusion.7 = f32[2,16]",
            trn + ("jvp(pt.fused_ce)/" if named else "jvp()/") + "dot_general:"),
        8: ("%fusion.8 = f32[2,16]", trn + (
            "transpose(jvp(pt.fused_ce))/" if named else "transpose(jvp())/")
            + "dot_general:"),
        9: ("%fusion.9 = bf16[2,16]",
            trn + ("jvp(pt.mlp)/" if named else "jvp()/") + "dot_general:"),
        10: (("jit_pt_decode_block" if named else "jit_run") + "(123)", ""),
        11: (("jit_pt_train_step" if named else "jit_train_step") + "(45)",
             ""),
    }
    ops = [_event(1, 0, 2 * MS), _event(2, 2 * MS, 8 * MS),
           _event(3, 2 * MS, 3 * MS), _event(4, 5 * MS, 1 * MS),
           _event(5, 6 * MS, 2 * MS), _event(6, 8 * MS, 2 * MS),
           _event(1, 14 * MS, 1 * MS), _event(2, 15 * MS, 5 * MS),
           _event(3, 15 * MS, 3 * MS), _event(5, 18 * MS, 2 * MS),
           _event(7, 30 * MS, 2 * MS), _event(9, 32 * MS, 4 * MS),
           _event(8, 36 * MS, 3 * MS), _event(9, 39 * MS, 1 * MS)]
    dev = _plane(1, "/device:TPU:0", {
        "XLA Modules": [_event(10, 0, 10 * MS), _event(10, 14 * MS, 6 * MS),
                        _event(11, 30 * MS, 10 * MS)],
        "XLA Ops": ops}, metas, {1: "tf_op"})
    pre = "pt." if named else "engine."
    names = ["serve.step", "serve.decode.dispatch", "serve.wait",
             "serve.emit", "train.step"]
    hmeta = {i + 1: (pre + n, "") for i, n in enumerate(names)}
    hmeta[9] = ("bench.engine.step", "")
    arg = 'stats { metadata_id: 2 int64_value: 4 }'
    host = _plane(2, "/host:CPU", {"python3": [
        _event(9, 0, 13 * MS),
        _event(1, 0, 13 * MS), _event(2, 0, 1 * MS, arg),
        _event(3, 1 * MS, 10 * MS), _event(4, 11 * MS, 2 * MS),
        _event(1, 13 * MS + MS // 2, 7 * MS + MS // 2),
        _event(2, 13 * MS + MS // 2, 7 * MS // 10, arg),
        _event(3, 14 * MS + MS // 5, 6 * MS + 3 * MS // 10),
        _event(4, 20 * MS + MS // 2, MS // 2),
        _event(5, 29 * MS, 12 * MS)]}, hmeta, {2: "n_steps"})
    return dev + "\n" + host


def _write(root, cell, text):
    from jax.profiler import ProfileData

    d = os.path.join(root, ".chipbench_trace", cell, "plugins", "profile",
                     "run1")
    os.makedirs(d)
    path = os.path.join(d, "host.xplane.pb")
    with open(path, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(text))
    return path


def _run(cell, stats0=None, stats1=None):
    return types.SimpleNamespace(
        cell=types.SimpleNamespace(name=cell), trace=object(),
        window={"stats0": stats0 or {}, "stats1": stats1 or {}})


@pytest.fixture()
def root(tmp_path, monkeypatch):
    monkeypatch.setattr(_program, "ROOT", str(tmp_path))
    _program._CACHE.clear()
    _write(str(tmp_path), "named", _known_text(True))
    _write(str(tmp_path), "unnamed", _known_text(False))
    return str(tmp_path)


def test_program_reads_names_stacks_and_spans(root):
    prog = _program.of(_run("named"))
    assert [m[0] for m in prog.modules] == [
        "jit_pt_decode_block", "jit_pt_decode_block", "jit_pt_train_step"]
    assert _program.executions(prog, "jit_pt_train_step") == [
        pytest.approx((1e-6 + 30e-3, 1e-6 + 40e-3))]
    kernel, = [o for o in prog.ops if "pt_paged_decode" in o.name]
    assert kernel.stack.endswith("pt.attn/pt_paged_decode/pallas_call:")
    assert _program.scope_of(kernel) == "pt_paged_decode"
    leaves = _program.leaf_ops(prog.ops)
    assert len(leaves) == len(prog.ops) - 2          # the two whiles hold ops
    by = dict(_program.by_scope(prog))
    assert by["pt.sampler"] == pytest.approx(5e-3)
    assert by["pt.attn"] == pytest.approx(6e-3)
    assert by["pt_paged_decode"] == pytest.approx(2e-3)
    assert by["pt.fused_ce"] == pytest.approx(5e-3)
    assert by["pt.mlp"] == pytest.approx(5e-3)
    assert by[_program.NO_SCOPE] == pytest.approx(3e-3)   # the copies
    # the module's own name is no scope
    assert not any(k.startswith("pt_decode") or k.startswith("pt_train")
                   for k in by)
    steps = [s for s in prog.spans if s.name == "pt.serve.step"]
    assert len(steps) == 2 and all(s.parent is None for s in steps)
    kids = [s for s in prog.spans if s.parent in steps]
    assert [s.name for s in kids] == [
        "pt.serve.decode.dispatch", "pt.serve.wait", "pt.serve.emit"] * 2
    assert kids[0].args == {"n_steps": 4}
    assert all(not s.name.startswith("bench.") for s in prog.spans)
    gaps = dict((k, t) for k, t, _ in _program.gaps_by_span(prog, 1e-3))
    # 10-14 ms: its middle lies in step 1's emit; 20-30: in step 2's emit
    # until 21, so by its middle (25 ms) in no span at all
    assert gaps == {"pt.serve.emit": pytest.approx(4e-3),
                    "(none)": pytest.approx(10e-3)}
    text = _program.describe(prog, "jit_pt_decode_block")
    assert "pt.sampler" in text and "(no pt name)" in text
    assert "pt.serve.step x2" in text


def test_trace_readers_known_answers(root):
    run = _run("named")
    # sampler 1 + 2 + 2 ms of 10 + 6 ms of leaf time inside the blocks
    assert _reader("sampler_share").read(run) == pytest.approx(
        100 * 5 / 16)
    # block A: 10 ms less its 8 ms loop; block B: 6 ms less 5
    assert _reader("decode_block_fixed_ms").read(run) == pytest.approx(1.5)
    assert _reader("fused_ce_share").read(run) == pytest.approx(50.0)
    # the 4 ms gap falls in pt.serve.emit; the 10 ms one in no span; two
    # steps overlap the device's span
    assert _reader("host_gap_ms").read(run) == pytest.approx(2.0)


@pytest.mark.parametrize("metric", ["sampler_share", "decode_block_fixed_ms",
                                    "fused_ce_share", "host_gap_ms"])
def test_trace_readers_find_nothing_without_names(root, metric):
    """The parent's trace (jit_run, closed_call, no pt.* span), a run
    without a trace, and a cell whose trace is missing: nothing to read,
    and nothing raised."""
    assert _reader(metric).read(_run("unnamed")) is None
    no_trace = _run("named")
    no_trace.trace = None
    assert _reader(metric).read(no_trace) is None
    assert _reader(metric).read(_run("no-such-cell")) is None


def test_counter_readers_known_answers_and_nothing_without_counters():
    s0 = {"steps": 10, "step_wall_s": 4.0, "device_wait_s": 3.9,
          "decode_blocks": 8, "decode_block_steps": 50, "programs_built": 24}
    s1 = {"steps": 110, "step_wall_s": 54.0, "device_wait_s": 53.5,
          "decode_blocks": 98, "decode_block_steps": 536,
          "programs_built": 24}
    run = _run("x", s0, s1)
    assert _reader("decode_block_len_mean").read(run) == pytest.approx(5.4)
    assert _reader("step_host_work_ms").read(run) == pytest.approx(4.0)
    assert _reader("programs_built").read(run) == 24.0
    old = _run("x", {"hit_tokens": 1}, {"hit_tokens": 2})   # the parent
    for m in ("decode_block_len_mean", "step_host_work_ms",
              "programs_built"):
        assert _reader(m).read(old) is None
    idle = _run("x", s0, dict(s0))                          # nothing ran
    assert _reader("decode_block_len_mean").read(idle) is None
    assert _reader("step_host_work_ms").read(idle) is None


# ---- the trace recorded on the chip ------------------------------------------

SCOPED = os.path.join(DATA, "scoped.xplane.pb")


@pytest.fixture()
def chip(tmp_path, monkeypatch):
    monkeypatch.setattr(_program, "ROOT", str(tmp_path))
    _program._CACHE.clear()
    d = tmp_path / ".chipbench_trace" / "chip" / "plugins" / "profile" / "r"
    d.mkdir(parents=True)
    shutil.copy(SCOPED, d / "vm.xplane.pb")
    return _program.of(_run("chip"))


def test_chip_trace_modules_kernels_and_scopes_by_name(chip):
    assert sorted({m[0] for m in chip.modules}) == [
        "jit_pt_decode_block", "jit_pt_train_step"]
    assert len(_program.executions(chip, "jit_pt_decode_block")) == 2
    assert len(_program.executions(chip, "jit_pt_train_step")) == 1
    # the kernel's name= is the instruction's name, the scope its stack
    kernels = [o for o in chip.ops
               if o.name.lstrip("%").startswith("pt_tiny_kernel")]
    assert len(kernels) == 8                         # 2 blocks x 4 steps
    assert all("tpu_custom_call" in o.name
               and "pt_tiny_kernel/pallas_call" in o.stack for o in kernels)
    by = dict(_program.by_scope(chip, "jit_pt_decode_block"))
    assert {"pt.attn", "pt.sampler", "pt_tiny_kernel",
            _program.NO_SCOPE} <= set(by)
    by = dict(_program.by_scope(chip, "jit_pt_train_step"))
    assert {"pt.fused_ce", "pt.mlp"} <= set(by)


def test_chip_trace_readers(chip):
    run = _run("chip")
    sampler = _reader("sampler_share").read(run)
    fused = _reader("fused_ce_share").read(run)
    fixed = _reader("decode_block_fixed_ms").read(run)
    gap = _reader("host_gap_ms").read(run)
    # the sort of 1024 x 1024 floats outweighs the matmul on a v5e
    assert CHIP["sampler_share"] == pytest.approx(sampler, rel=1e-6)
    assert CHIP["fused_ce_share"] == pytest.approx(fused, rel=1e-6)
    assert CHIP["decode_block_fixed_ms"] == pytest.approx(fixed, rel=1e-6)
    assert CHIP["host_gap_ms"] == pytest.approx(gap, rel=1e-6)
    assert 0 < fixed < 1.0 and 50 < sampler < 100 and 0 < fused < 100
    # each step's 20 ms sleep inside pt.serve.emit left the device idle
    assert 19.0 < gap < 25.0


def test_chip_trace_spans_tile_their_step(chip):
    steps = [s for s in chip.spans if s.name == "pt.serve.step"]
    assert [s.args["step"] for s in steps] == [1, 2]
    for st in steps:
        kids = [s for s in chip.spans if s.parent is st]
        assert [s.name for s in kids] == [
            "pt.serve.decode.dispatch", "pt.serve.wait", "pt.serve.emit"]
        covered = sum(s.t1 - s.t0 for s in kids)
        assert 0.95 < covered / (st.t1 - st.t0) <= 1.0
    assert any(s.name == "pt.train.step" for s in chip.spans)
    gaps = {k: t for k, t, _ in _program.gaps_by_span(chip, 1e-3)}
    assert gaps["pt.serve.emit"] > 0.035             # two sleeps of 20 ms


# what record_scoped_trace.py's run on the chip printed for the committed
# file (my chip run, PR 24)
CHIP = {"sampler_share": 92.99628083916495,
        "fused_ce_share": 50.26211589483424,
        "decode_block_fixed_ms": 0.012822499999998876,
        "host_gap_ms": 21.756848319999996}
