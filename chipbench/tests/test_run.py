"""run.py as the driver calls it: no chip, no result; the rehearsal's last
line has exactly the contract's keys."""

import json
import os
import subprocess
import sys

import pytest

from chipbench.harness import loader

RUN = [sys.executable, os.path.join(loader.HERE, "run.py")]
ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def _cells():
    with open(os.path.join(loader.ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


def test_without_a_tpu_no_result_and_nonzero():
    p = subprocess.run(RUN + ["--workload", _cells()[0], "--seed", "1",
                              "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=ENV, timeout=300)
    assert p.returncode not in (0, 3)
    assert not any(l.startswith("{") for l in p.stdout.splitlines())
    assert "not 'tpu'" in p.stderr


def test_outside_a_checkout_no_result_and_nonzero(tmp_path):
    """In a directory that holds only BENCHMARK.json and chipbench/."""
    import shutil

    shutil.copy(os.path.join(loader.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(loader.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        _cells()[0], "--seed", "1", "--seconds", "1",
                        "--trace", "0", "--rehearse"], cwd=tmp_path,
                       capture_output=True, text=True, env=ENV, timeout=300)
    assert p.returncode not in (0, 3)
    assert not any(l.startswith("{") for l in p.stdout.splitlines())


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", _cells())
def test_rehearsal_last_line_has_the_contract_keys(cell, trace):
    p = subprocess.run(RUN + ["--workload", cell, "--seed",
                              str(2 ** 31 + 12345), "--seconds", "3",
                              "--trace", str(trace), "--rehearse"],
                       capture_output=True, text=True, env=ENV, timeout=900)
    assert p.returncode == 3, p.stderr[-2000:]     # never taken for a result
    line = json.loads(p.stdout.strip().splitlines()[-1])
    want = {"correct", "attempted", "failed", "metrics", "device"}
    assert set(line) == want | ({"breakdown"} if trace else set())
    assert line["device"]["platform"] == "cpu"
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    c = loader.load(cell)
    declared = c.per_layer if trace else c.end_to_end
    units = {m["name"]: m["unit"] for m in declared}
    assert set(line["metrics"]) <= set(units)
    if not trace:
        assert set(line["metrics"]) == set(units)
        assert line["metrics"]["setup_s"]["value"] > 0
    else:
        assert {"busy_s", "window_s"} <= set(line["device"])
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        assert all(len(v) <= 10 for v in line["breakdown"].values())
    for name, m in line["metrics"].items():
        assert set(m) == {"value", "unit"} and m["unit"] == units[name]
    assert "compared " in p.stdout and "(limit " in p.stdout
