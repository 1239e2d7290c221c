"""BENCHMARK.json against its contract, and discovery of cells, mixes and
metrics by name alone."""

import json
import os
import shutil

import pytest

from benchmarks.chip import spec

ROOT = spec.ROOT
BENCH = spec.load_benchmark(ROOT)
CELLS = [w["name"] for w in BENCH["workloads"]]

KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def test_keys_and_limits():
    assert set(BENCH) == KEYS["top"]
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[group]:
            extra = set(e) - KEYS[group]
            assert extra <= ({"workloads"} if group in ("end_to_end",
                                                        "per_layer") else set())
            assert KEYS[group] <= set(e), (group, e)
    assert 1 <= BENCH["run_seconds"] <= 51
    names = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in names
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert m["better"] in ("lower", "higher")
    for m in BENCH["per_layer"]:
        assert m["moves"] in names
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for w in BENCH["workloads"]:
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200


def test_names_and_units_use_allowed_characters():
    assert spec.name_errors(BENCH) == []
    bad = json.loads(json.dumps(BENCH))
    bad["per_layer"][0]["unit"] = "tokens per second"
    bad["workloads"][0]["name"] = "has space"
    assert len(spec.name_errors(bad)) == 2


def test_paths_hold_the_command():
    for p in BENCH["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p)) and not p.startswith("/")
    script = BENCH["command"][1]
    assert any(script.startswith(p + "/") for p in BENCH["paths"])
    assert os.path.isfile(os.path.join(ROOT, script))


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves(cell):
    c = spec.resolve(cell, ROOT, BENCH)
    assert c.config["kind"] in ("conv", "lm_serve")
    assert hasattr(c.kind_module(), "System")
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert callable(c.metric_reader(m))
        assert m["moves"] in e2e


def test_config_entries_name_their_files():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    for c in BENCH["configs"]:
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        with open(os.path.join(ROOT, c["file"])) as f:
            assert json.load(f)["name"] == c["name"]


def test_new_files_are_found_by_name(tmp_path):
    """A configuration, a mix and a metric added as files, with entries in
    BENCHMARK.json, resolve without an edit to any existing file."""
    os.symlink(os.path.join(ROOT, "src"), tmp_path / "src")
    bench_dir = tmp_path / "benchmarks" / "chip"
    shutil.copytree(os.path.join(ROOT, "benchmarks", "chip"), bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in bench_dir.rglob("*") if p.is_file()}
    cfg = json.loads((bench_dir / "configs" / "resnet50-b1000.json").read_text())
    cfg.update(name="alexnet-b128", batch=128)
    (bench_dir / "configs" / "alexnet-b128.json").write_text(json.dumps(cfg))
    (bench_dir / "traffic" / "late.json").write_text(json.dumps(
        {"kind": "conv_passes", "layers": ["conv5_x"]}))
    (bench_dir / "metrics" / "probe.conv.py").write_text(
        "def read(r):\n    return 42.0\n")
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "alexnet-b128", "source": "test",
                             "file": "benchmarks/chip/configs/alexnet-b128.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "alexnet-b128.late",
                               "config": "alexnet-b128", "traffic": "late",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "probe.conv", "unit": "%",
                               "better": "higher", "source": "device_trace",
                               "layer": "kernels", "moves": "pass_ms",
                               "workloads": ["alexnet-b128.late"]})
    bench["end_to_end"][0]["workloads"].append("alexnet-b128.late")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    c = spec.resolve("alexnet-b128.late", str(tmp_path))
    assert c.config["batch"] == 128 and c.traffic["layers"] == ["conv5_x"]
    assert [m["name"] for m in c.per_layer] == ["probe.conv"]
    assert c.metric_reader(c.per_layer[0])(None) == 42.0
    assert {m["name"] for m in c.end_to_end} == {"pass_ms", "setup_s"}
    after = {p: p.read_bytes() for p in before}
    assert after == before
