"""A conv cell driven end to end on the CPU at a tiny size: correct as it
stands, not correct with an answer altered or with the fp8 control in the
program's place, and no result at all without a chip."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import ROOT, committed_config, control_readings, run_cell


def test_conv_cell_runs_correct(tiny_root, capsys):
    out = run_cell(tiny_root, "tiny-conv.pair", capsys)
    assert out["correct"] is True and out["failed"] == 0
    assert list(out)[-1] == "checks" and out["checks"]["rel_err"]["value"] == 0
    assert set(out["metrics"]) == {"pass_ms", "setup_s"}
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] == 1


def altered_conv(real):
    """``ops.conv2d`` with one output altered where it is produced."""
    def conv(x, w, *args, **kw):
        return real(x, w, *args, **kw).at[1, 2, 0, 3].add(0.5)
    return conv


def control_conv(real):
    """The fp8 control in ``ops.conv2d``'s place."""
    from benchmarks.chip.reference import conv as ref

    def conv(x, w, stride=(1, 1), **kw):
        return ref.conv_f32(ref.fp8_round(x), ref.fp8_round(w), stride[0])
    return conv


@pytest.mark.parametrize("fault", [altered_conv, control_conv],
                         ids=["altered_answer", "fp8_control"])
def test_conv_fault_is_not_correct(tiny_root, capsys, monkeypatch, fault):
    """Run through the whole harness under the committed limit, an altered
    answer and the fp8 control each read ``correct: false``."""
    from repro import ops

    monkeypatch.setattr(ops, "conv2d", fault(ops.conv2d))
    out = run_cell(tiny_root, "tiny-conv.pair", capsys)
    rel = out["checks"]["rel_err"]
    assert rel["limit"] == committed_config("resnet50-b1000")["check"][
        "rel_err_limit"]
    assert out["correct"] is False and rel["value"] > rel["limit"]


def test_conv_control_is_not_correct(tiny_root):
    checks, control = control_readings(tiny_root, "tiny-conv.pair")
    (name, value, limit), = checks
    (cname, cvalue), = control
    assert name == cname == "rel_err"
    assert value <= limit < cvalue


def test_no_chip_no_result(capsys):
    from benchmarks.chip import run

    rc = run.main(["--workload", "resnet50-b1000.body", "--seed", "1",
                   "--seconds", "1", "--trace", "0"], root=ROOT, chip=True)
    captured = capsys.readouterr()
    assert rc != 0 and captured.out == ""
    assert "no TPU" in captured.err


def test_benchmark_files_alone_do_not_run(tmp_path):
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for p in bench["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    proc = subprocess.run(
        [sys.executable] + bench["command"][1:] + [
            "--workload", "resnet50-b1000.body", "--seed", "1", "--seconds",
            "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0 and proc.stdout == ""
