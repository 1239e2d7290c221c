"""Shared set-up of the chip benchmark's tests: the repository root on the
import path (for ``benchmarks.chip``), and a copy of the benchmark with tiny
cells that run on the CPU."""

import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

def committed_config(name):
    return json.load(open(os.path.join(ROOT, "benchmarks", "chip", "configs",
                                       f"{name}.json")))


# The conv cell keeps the committed limit: the program reads 0 at this size
# on the CPU and the fp8 control 0.038-0.063 (seeds 1, 2, 3, 2**31 + 9).
TINY_CONV = {
    "name": "tiny-conv", "kind": "conv", "source": "test", "batch": 4,
    "dtype": "bfloat16", "out_dtype": "float32", "padding": "VALID",
    "layers": {
        "a": {"c_I": 3, "c_O": 8, "h_O": 6, "w_O": 6, "h_F": 3, "w_F": 3,
              "stride": 2},
        "b": {"c_I": 8, "c_O": 8, "h_O": 5, "w_O": 5, "h_F": 3, "w_F": 3,
              "stride": 1}},
    "check": committed_config("resnet50-b1000")["check"],
}
# A model 32 times narrower has logits about 5.7 times smaller, so the LM's
# limit is its own: the widest logit gap reads 0-0.0023 at this size on the
# CPU, the fp8 control 0.032-0.061 (seeds 1, 2, 3, 2**31 + 9).
TINY_LM = dict(committed_config("qwen2.5-3b"),
    name="tiny-lm", hidden_size=64, intermediate_size=128,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    vocab_size=512, check={"logit_gap_limit": 0.01})
TINY_JOBS = {
    "kind": "offline_jobs", "job_requests": 6,
    "prompt": {"dist": "lognormal", "median": 8, "sigma": 0.7, "min": 3,
               "max": 20},
    "output": {"dist": "uniform", "min": 2, "max": 9},
    "max_len": 32, "check_requests": 6,
}


@pytest.fixture
def tiny_root(tmp_path):
    """A checkout-like tree: the program, a copy of the benchmark, and a
    ``BENCHMARK.json`` of two tiny cells (``tiny-conv.pair``, ``tiny-lm.jobs``)."""
    os.symlink(os.path.join(ROOT, "src"), tmp_path / "src")
    bench = tmp_path / "benchmarks" / "chip"
    shutil.copytree(os.path.join(ROOT, "benchmarks", "chip"), bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    (bench / "configs" / "tiny-conv.json").write_text(json.dumps(TINY_CONV))
    (bench / "configs" / "tiny-lm.json").write_text(json.dumps(TINY_LM))
    (bench / "traffic" / "pair.json").write_text(json.dumps(
        {"kind": "conv_passes", "layers": ["a", "b"]}))
    (bench / "traffic" / "jobs.json").write_text(json.dumps(TINY_JOBS))
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    spec["configs"] = [
        {"name": n, "source": "test", "file": f"benchmarks/chip/configs/{n}.json",
         "reduced": [], "why": "test"} for n in ("tiny-conv", "tiny-lm")]
    spec["workloads"] = [
        {"name": "tiny-conv.pair", "config": "tiny-conv", "traffic": "pair",
         "chips": 1, "why": "test"},
        {"name": "tiny-lm.jobs", "config": "tiny-lm", "traffic": "jobs",
         "chips": 1, "why": "test"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = (["tiny-conv.pair"] if m["name"] == "pass_ms"
                              or m["name"].endswith(".conv")
                              or m["name"] == "conv2d_roofline"
                              else ["tiny-lm.jobs"])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return str(tmp_path)


def run_cell(root, cell, capsys, seed=2**31 + 11, seconds=0.3):
    """One CPU run of ``cell`` through the harness; its result line."""
    from benchmarks.chip import run

    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", "0"], root=root, chip=False)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def control_readings(root, cell, seed=2**31 + 11):
    """(program's checks, control's readings) of one short CPU run."""
    from benchmarks.chip import spec

    c = spec.resolve(cell, root)
    system = c.kind_module().System(c, seed, lambda msg: None)
    system.setup()
    system.window(0.2)
    system.release()
    return system.check(), system.control()
