"""``chunk_attn_share`` (PR 42) on traces written by hand: a packed chunk
whose attention is the kernel ``pt_paged_chunk``, the same chunk as the
parent runs it (a gather and float32 scores under ``pt.attn``, no kernel),
and a program without the names; and where ``BENCHMARK.json`` lists it."""

import importlib.util
import json
import os
import types

import pytest

from chipbench.harness import loader
from chipbench.harness import trace as trace_lib
from chipbench.metrics import _program

HERE = os.path.dirname(os.path.abspath(__file__))
SERVING = ["internlm2-1.8b.chat-batch", "lfm2-24b-a2b.chat-batch-64",
           "nemotron-3-nano-30b-a3b.chat-short-batch-32",
           "trinity-mini.mixed-len-batch-16"]


def _helpers():
    spec = importlib.util.spec_from_file_location(
        "_chipbench_test_program", os.path.join(HERE, "test_program.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _text(h, form):
    """One packed chunk, 0-10 ms. ``kernel``: a layer's projections 1 ms,
    its page append 0.5 ms, the kernel 1.5 ms, the experts 6 ms, a norm
    1 ms. ``xla``: the projections, the append, then the float32 scores
    4 ms and ``p.V`` 2 ms under ``pt.attn``, the experts 6 ms, the norm.
    ``unnamed``: the kernel form's ops with no ``pt`` name on any."""
    MS = h.MS
    blk = "jit(pt_prefill_chunk)/"
    sc = (lambda s: "") if form == "unnamed" else (lambda s: s)
    attn = sc("pt.attn/pt.attn.full/")
    metas = {
        1: ("%fusion.1 = bf16[16,128,4096]", blk + attn + "dot_general:"),
        2: ("%fusion.2 = bf16[8256,4,16,128]",
            blk + attn + sc("pt.kv_write/") + "scatter:"),
        3: ('%pt_paged_chunk.3 = bf16[16,4,1024,128]{3,2,1,0} custom-call('
            '%p.1), custom_call_target="tpu_custom_call"',
            blk + attn + sc("pt_paged_chunk/") + "pallas_call"),
        4: ("%fusion.4 = f32[16,4,8,128]", blk + attn + "reduce:"),
        5: ("%fusion.5 = f32[16,4,8,128,128]", blk + attn + "dot_general:"),
        6: ("%gmm.6 = bf16[16384,1024]",
            blk + sc("pt.moe/pt.moe.experts/") + "gmm"),
        7: ("%fusion.7 = bf16[16,128,2048]", blk + "rsqrt:"),
        10: ("jit_pt_prefill_chunk(3)" if form != "unnamed" else "jit_run(3)",
             ""),
    }
    if form == "unnamed":
        metas[3] = ("%fusion.3 = bf16[16,4,1024,128]", blk + "dot_general:")
    body = {"xla": ((1, 1.0), (2, 0.5), (4, 4.0), (5, 2.0), (6, 6.0),
                    (7, 1.0))}.get(
        form, ((1, 1.0), (2, 0.5), (3, 1.5), (6, 6.0), (7, 1.0)))
    ops, t = [], 0.0
    for meta, dur in body:
        ops.append(h._event(meta, int(t), int(dur * MS)))
        t += dur * MS
    dev = h._plane(1, "/device:TPU:0", {
        "XLA Modules": [h._event(10, 0, int(t))], "XLA Ops": ops},
        metas, {1: "tf_op"})
    host = h._plane(2, "/host:CPU", {"python3": [h._event(1, 0, int(t))]},
                    {1: ("bench.engine.step", "")}, {})
    return dev + "\n" + host


@pytest.fixture()
def traced(tmp_path, monkeypatch):
    h = _helpers()
    monkeypatch.setattr(_program, "ROOT", str(tmp_path))
    _program._CACHE.clear()
    out = {}
    for form in ("kernel", "xla", "unnamed"):
        path = h._write(str(tmp_path), form, _text(h, form))
        cell = loader.load(SERVING[-1])
        out[form] = types.SimpleNamespace(
            cell=types.SimpleNamespace(name=form, config=cell.config,
                                       spec=cell.spec),
            trace=trace_lib.reduce(path), device={}, window={})
    return out


def test_known_answers(traced):
    read = loader._module("metrics", "chunk_attn_share",
                          "chunk_attn_share").read
    # projections 1 + append 0.5 + kernel 1.5 of 10 ms
    assert read(traced["kernel"]) == pytest.approx(100 * 3.0 / 10.0)
    # the parent's chunk: projections, append, scores and p.V of 14.5 ms
    assert read(traced["xla"]) == pytest.approx(100 * 7.5 / 14.5)
    # no name to read, and a run without a trace: nothing, nothing raised
    assert read(traced["unnamed"]) is None
    assert read(types.SimpleNamespace(cell=traced["kernel"].cell, trace=None,
                                      device={}, window={})) is None


def test_listed_last_in_the_serving_cells():
    with open(os.path.join(loader.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = bench["per_layer"][-1]
    assert entry == {"name": "chunk_attn_share", "unit": "%",
                     "better": "lower", "source": "device_trace",
                     "layer": "model", "moves": "serve_tok_s",
                     "workloads": SERVING}
    for name in SERVING:
        assert "chunk_attn_share" in {
            m["name"] for m in loader.load(name).per_layer}
