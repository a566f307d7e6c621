"""Find everything a cell needs, by name, from its entry in BENCHMARK.json.

Adding a cell is adding an entry and files; nothing here names a cell, a
configuration, a traffic mix or a metric.

    BENCHMARK.json workloads[name]      -> config, traffic, chips
    BENCHMARK.json configs[config].file -> the configuration as it is run
    chipbench/traffic/<traffic>.json    -> the mix (kind -> generators/<kind>.py)
    chipbench/cells/<name>.json         -> driver, engine/trainer sizes, limits
    chipbench/metrics/<metric>.py       -> reader of each per-layer metric
    chipbench/adapters/<adapter>.py, chipbench/reference/<reference>.py
                                        (the reference states the leaf table)
    chipbench/rehearse/<name>.json      -> tiny preset for --rehearse (optional)
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)


class CellError(Exception):
    """The cell cannot be assembled: a file or an entry is missing."""


def _json(path: str, what: str) -> dict:
    if not os.path.isfile(path):
        raise CellError(f"{what}: no file {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def _module(kind: str, name: str, what: str):
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise CellError(f"{what}: no file {os.path.relpath(path, ROOT)}")
    # by path: a metric's name may hold dots (device_idle.serve)
    mod_name = f"chipbench.{kind}.{name.replace('.', '__')}"
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str, reported: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") is None or metric["moves"] in reported


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    why: str
    config: dict          # the configuration file (HF keys + chipbench's)
    traffic: dict         # the mix's parameters
    spec: dict            # chipbench/cells/<name>.json
    end_to_end: list      # metric entries this cell reports
    per_layer: list       # metric entries this cell reports with --trace 1
    readers: dict         # per-layer metric name -> module with read(run)
    generator: object     # module of the traffic kind
    adapter: object
    reference: object

    @property
    def leaf_table(self) -> dict:
        """The family's leaves as its reference states them for this
        configuration: what ``harness/weights.py`` draws."""
        return self.reference.leaf_table(self.config)


def merge(base: dict, over: dict) -> dict:
    """``over`` laid on ``base``, dictionaries merged key by key."""
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(out[k], v) if (isinstance(v, dict)
                                      and isinstance(out.get(k), dict)) else v
    return out


def load(name: str, rehearse: bool = False, root: str = ROOT) -> Cell:
    bench = _json(os.path.join(root, "BENCHMARK.json"), "benchmark")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise CellError(f"no workload {name!r} in BENCHMARK.json "
                        f"(has: {', '.join(sorted(cells))})")
    w = cells[name]
    cfgs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in cfgs:
        raise CellError(f"{name}: no configuration {w['config']!r}")
    config = _json(os.path.join(root, cfgs[w["config"]]["file"]),
                   f"configuration {w['config']}")
    traffic = _json(os.path.join(HERE, "traffic", w["traffic"] + ".json"),
                    f"traffic mix {w['traffic']}")
    spec = _json(os.path.join(HERE, "cells", name + ".json"), f"cell {name}")
    if rehearse:
        tiny = _json(os.path.join(HERE, "rehearse", name + ".json"),
                     f"rehearsal preset of {name}")
        config = merge(config, tiny.get("config", {}))
        traffic = merge(traffic, tiny.get("traffic", {}))
        spec = merge(spec, tiny.get("cell", {}))
    e2e = [m for m in bench["end_to_end"] if _applies(m, name, set())]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, name, reported)]
    for m in per_layer:
        if m["moves"] not in reported:
            raise CellError(f"{name}: per-layer metric {m['name']} moves "
                            f"{m['moves']}, which this cell does not report")
    readers = {m["name"]: _module("metrics", m["name"],
                                  f"per-layer metric {m['name']}")
               for m in per_layer}
    return Cell(
        name=name, chips=int(w["chips"]), why=w["why"], config=config,
        traffic=traffic, spec=spec, end_to_end=e2e, per_layer=per_layer,
        readers=readers,
        generator=_module("generators", traffic["kind"],
                          f"traffic kind {traffic['kind']}"),
        adapter=_module("adapters", config["adapter"],
                        f"adapter {config['adapter']}"),
        reference=_module("reference", config["reference"],
                          f"reference {config['reference']}"))
