"""The trace reduction on a trace whose times are known."""

import os

import pytest

from chipbench.harness import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _event(meta, offset_ps, dur_ps):
    return (f"events {{ metadata_id: {meta} offset_ps: {offset_ps} "
            f"duration_ps: {dur_ps} }}")


def _plane(pid, name, lines, metas):
    md = "\n".join(
        f'event_metadata {{ key: {k} value {{ id: {k} name: "{n}" }} }}'
        for k, n in metas.items())
    ls = "\n".join(
        f'lines {{ id: {i} name: "{ln}" timestamp_ns: 1000\n{chr(10).join(ev)}\n}}'
        for i, (ln, ev) in enumerate(lines.items()))
    return f'planes {{ id: {pid} name: "{name}"\n{md}\n{ls}\n}}'


@pytest.fixture(scope="module")
def known(tmp_path_factory):
    """Device 0: a while loop 0-6 ms holding a fusion 0-2 ms and a kernel
    3-5 ms, then idle until a fusion 10-12 ms. Host: bench.fence 0-7 ms,
    bench.batch 7-12 ms."""
    from jax.profiler import ProfileData

    ms = 10 ** 9   # picoseconds
    dev = _plane(1, "/device:TPU:0", {
        "XLA Modules": [_event(5, 0, 6 * ms), _event(5, 10 * ms, 2 * ms)],
        "XLA Ops": [_event(1, 0, 6 * ms), _event(2, 0, 2 * ms),
                    _event(3, 3 * ms, 2 * ms), _event(4, 10 * ms, 2 * ms)],
    }, {1: "while.3", 2: "fusion.17", 3: "flash_kernel.2", 4: "fusion.18",
        5: "jit_step(123)"})
    host = _plane(2, "/host:CPU", {
        "python3": [_event(1, 0, 7 * ms), _event(2, 7 * ms, 5 * ms)],
    }, {1: "bench.fence", 2: "bench.batch"})
    raw = ProfileData.text_proto_to_serialized_xspace(dev + "\n" + host)
    p = tmp_path_factory.mktemp("trace") / "known.xplane.pb"
    p.write_bytes(raw)
    return trace.reduce(str(p))


def test_known_busy_idle_and_kernel_times(known):
    ops = known.devices[0]["ops"]
    assert trace.busy_seconds(ops) == pytest.approx(8e-3)
    leaves = trace.leaf_ops(ops)
    assert sorted(n for n, _, _ in leaves) == ["flash_kernel.2", "fusion.17",
                                               "fusion.18"]
    top = dict(trace.top_ops(leaves))
    assert top["fusion"] == pytest.approx(4e-3)
    assert top["flash_kernel"] == pytest.approx(2e-3)
    s = trace.summary(known, 1)
    assert s["busy_s"] == pytest.approx(8e-3)
    assert s["span_s"] == pytest.approx(12e-3)
    gaps = dict(s["idle_gaps"])
    assert gaps["bench.batch"] == pytest.approx(4e-3)   # 6..10 ms, mid 8
    assert len(known.devices[0]["modules"]) == 2
    assert [h[0] for h in known.host] == ["bench.fence", "bench.batch"]


def test_union_merges_overlaps():
    assert trace.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert trace.op_family("fusion.123") == "fusion"
    assert trace.op_family("copy-start.4") == "copy-start"


@pytest.mark.skipif(not os.path.isfile(os.path.join(DATA, "small.xplane.pb")),
                    reason="no recorded chip trace in tests/data")
def test_recorded_chip_trace_reduces():
    red = trace.reduce(os.path.join(DATA, "small.xplane.pb"))
    assert 0 in red.devices and red.devices[0]["ops"]
    s = trace.summary(red, 1)
    assert 0 < s["busy_s"] < s["span_s"]
    # two bursts of matmuls around a 20 ms host sleep
    assert dict(s["idle_gaps"]).get("bench.sleep", 0) > 0.015
