import json
import os

import pytest

from chipbench.harness import loader


def _bench():
    with open(os.path.join(loader.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("rehearse", [False, True])
def test_every_cell_loads_by_name(rehearse):
    for w in _bench()["workloads"]:
        cell = loader.load(w["name"], rehearse=rehearse)
        assert cell.name == w["name"] and cell.chips == w["chips"]
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert callable(cell.readers[m["name"]].read)
            assert m["moves"] in {e["name"] for e in cell.end_to_end}
        assert cell.generator and cell.adapter and cell.reference


def test_unknown_workload_is_refused():
    with pytest.raises(loader.CellError):
        loader.load("no-such.cell")


@pytest.mark.parametrize("drop", ["traffic", "config"])
def test_cell_with_a_missing_file_is_refused(tmp_path, drop):
    bench = _bench()
    w = bench["workloads"][0]
    if drop == "traffic":
        w["traffic"] = "no-such-mix"
    else:
        for c in bench["configs"]:
            if c["name"] == w["config"]:
                c["file"] = "chipbench/configs/no-such.json"
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(loader.CellError, match="no file"):
        loader.load(w["name"], root=str(tmp_path))


def test_benchmark_json_names_only_files_under_paths():
    bench = _bench()
    assert bench["paths"] == ["chipbench"]
    for c in bench["configs"]:
        assert c["file"].startswith("chipbench/")
        cfg = json.load(open(os.path.join(loader.ROOT, c["file"])))
        assert cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
    per = {m["name"] for m in bench["per_layer"]}
    for name in per:
        assert os.path.isfile(os.path.join(loader.HERE, "metrics",
                                           name + ".py")), name
