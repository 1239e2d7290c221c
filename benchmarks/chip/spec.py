"""``BENCHMARK.json`` and the files a cell resolves to, found by name.

A cell ``<config>.<traffic>`` names a configuration entry (whose ``file`` is
a JSON file of sizes with a ``kind``), a traffic mix (``traffic/<traffic>
.json``) and, through ``BENCHMARK.json``, its metrics: every end-to-end
metric whose ``workloads`` list it (or that has none), and every per-layer
metric likewise, each read by ``metrics/<name>.py``. The configuration's
``kind`` is the module ``kinds/<kind>.py`` that drives the program.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re
import sys
from typing import Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    bench_dir: str

    def kind_module(self):
        return load_module(os.path.join(self.bench_dir, "kinds",
                                        f"{self.config['kind']}.py"))

    def metric_reader(self, metric: dict):
        return load_module(os.path.join(self.bench_dir, "metrics",
                                        f"{metric['name']}.py")).read


def load_module(path: str):
    """Import a file of the benchmark by its path, once per process."""
    rel = os.path.relpath(path, ROOT)
    name = "_bench_" + re.sub(r"[^A-Za-z0-9_]", "_", rel)
    if name in sys.modules:
        return sys.modules[name]
    if not os.path.isfile(path):
        raise FileNotFoundError(f"benchmark file {rel} not found")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(name: str, root: str = ROOT, bench: dict = None) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files read."""
    bench = load_benchmark(root) if bench is None else bench
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    entry = configs[w["config"]]
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    bench_dir = os.path.join(root, os.path.dirname(os.path.dirname(
        entry["file"])))
    with open(os.path.join(bench_dir, "traffic", f"{w['traffic']}.json")) as f:
        traffic = json.load(f)
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=config, traffic_name=w["traffic"], traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
                bench_dir=bench_dir)


def name_errors(bench: dict) -> List[str]:
    """Names and units outside the allowed characters, and duplicates."""
    errs: List[str] = []
    seen: Dict[str, set] = {}
    groups = (("configs", bench["configs"]), ("workloads", bench["workloads"]),
              ("metrics", bench["end_to_end"] + bench["per_layer"]))
    for group, entries in groups:
        for e in entries:
            names = [e["name"]]
            if group == "workloads":
                names += [e["config"], e["traffic"]]
            if group == "configs":
                names += list(e["reduced"])
            for n in names:
                if not NAME_RE.match(n):
                    errs.append(f"{group}: bad name {n!r}")
            if e["name"] in seen.setdefault(group, set()):
                errs.append(f"{group}: duplicate name {e['name']!r}")
            seen[group].add(e["name"])
            if group == "metrics" and not UNIT_RE.match(e["unit"]):
                errs.append(f"metric {e['name']}: bad unit {e['unit']!r}")
    return errs
