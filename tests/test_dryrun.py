"""Dry-run machinery tests.

The lowering pipeline itself is exercised on a small fake-device mesh in a
subprocess (jax locks the device count on first init, so the 8-device run
cannot share this process). The HLO collective parser and the roofline math
are tested in-process.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.analysis.roofline import (PEAKS, V5E_KIND, Roofline,
                                     collective_bytes, model_flops)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_collective_parser_counts_kinds():
    hlo = textwrap.dedent("""
      %ar = f32[128,256]{1,0} all-reduce(%x), replica_groups={}, to_apply=%add
      %ag = bf16[64,512]{1,0} all-gather(%y), dimensions={1}
      %rs = f32[32]{0} reduce-scatter(%z), dimensions={0}, to_apply=%add
      %a2a = bf16[16,16]{1,0} all-to-all(%w), dimensions={0}
      %cp = u32[8]{0} collective-permute(%v), source_target_pairs={{0,1}}
      %other = f32[4]{0} add(%a, %b)
    """)
    out = collective_bytes(hlo)
    assert out["all-reduce"] == 128 * 256 * 4 * 2  # 2x wire multiplier
    assert out["all-gather"] == 64 * 512 * 2
    assert out["reduce-scatter"] == 32 * 4
    assert out["all-to-all"] == 16 * 16 * 2
    assert out["collective-permute"] == 8 * 4
    assert out["total"] == sum(out[k] for k in
                               ("all-reduce", "all-gather", "reduce-scatter",
                                "all-to-all", "collective-permute"))


def test_collective_parser_skips_async_done():
    hlo = ("%s = f32[1024]{0} all-reduce-start(%x), to_apply=%add\n"
           "%d = f32[1024]{0} all-reduce-done(%s)\n")
    out = collective_bytes(hlo)
    assert out["all-reduce"] == 1024 * 4 * 2  # start counted once


def test_roofline_terms_and_dominance():
    peak = PEAKS[V5E_KIND]
    r = Roofline(arch="a", shape="train_4k", mesh="16x16", chips=256,
                 hlo_flops=256 * peak.flops_bf16,  # exactly 1s of compute
                 hlo_bytes=0.0, wire_bytes_per_chip=peak.ici_bw / 2,
                 collectives={}, model_flops=0.5 * 256 * peak.flops_bf16,
                 bytes_per_device={})
    assert r.compute_s == pytest.approx(1.0)
    assert r.collective_s == pytest.approx(0.5)
    assert r.dominant == "compute"
    assert r.mfu == pytest.approx(0.5)
    assert r.useful_flops_frac == pytest.approx(0.5)


def test_model_flops_regimes():
    train = model_flops("qwen2_5_3b", "train_4k")
    prefill = model_flops("qwen2_5_3b", "prefill_32k")
    decode = model_flops("qwen2_5_3b", "decode_32k")
    assert train == pytest.approx(6 * 3.40e9 * 4096 * 256, rel=0.02)
    assert prefill == pytest.approx(2 * 3.40e9 * 32768 * 32, rel=0.02)
    assert decode == pytest.approx(2 * 3.40e9 * 128, rel=0.02)


def test_input_specs_shapes():
    from repro.launch import specs as sp

    tr = sp.input_specs("qwen2_5_3b", "train_4k")
    assert tr["batch"]["tokens"].shape == (256, 4096)
    assert tr["params"]["head"].shape[1] % 256 == 0  # padded vocab

    de = sp.input_specs("jamba_1_5_large", "long_500k")
    assert de["token"].shape == (1, 1)
    # attention cache depth = seq_len
    k = de["cache"]["b4"]["k"]
    assert k.shape == (9, 1, 8, 524288, 128)  # (repeats, B, kv, L, hd)

    enc = sp.input_specs("hubert_xlarge", "prefill_32k")
    assert enc["batch"]["embeds"].shape == (32, 32768, 1280)


@pytest.mark.slow
def test_small_mesh_dryrun_subprocess():
    """lower+compile one train cell and one decode cell on 16 fake devices."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
        os.environ["REPRO_UNROLL_SCANS"] = "0"  # rolled: fast compile
        from repro.launch.dryrun import run_cell
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(data=4, model=4)
        r1 = run_cell("internvl2_1b", "train_4k", mesh=mesh, save=False)
        assert r1["status"] == "ok", r1
        assert r1["hlo_flops"] > 0
        r2 = run_cell("qwen2_5_3b", "decode_32k", mesh=mesh, save=False)
        assert r2["status"] == "ok", r2
        assert r2["collectives"]["total"] > 0
        print("SUBPROCESS_OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"  # host devices; never the accelerator
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=560, env=env)
    assert "SUBPROCESS_OK" in out.stdout, out.stderr[-2000:]
