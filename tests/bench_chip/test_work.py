"""The benchmark's work functions and peaks, tied to the program's own
shape arithmetic and to numbers worked out by hand."""

import json
import os

import pytest

from benchmarks.chip import peaks, work

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _config(name):
    with open(os.path.join(ROOT, "benchmarks", "chip", "configs",
                           f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("layer", ["conv1", "conv2_x", "conv3_x", "conv4_x",
                                   "conv5_x"])
def test_conv_work_matches_convshape(layer):
    from repro.core.conv_model import resnet50_layers

    cfg = _config("resnet50-b1000")
    shape = resnet50_layers(cfg["batch"])[layer]
    spec = cfg["layers"][layer]
    assert work.conv_flops(cfg["batch"], spec) == shape.flops
    H, W = work.conv_input_hw(spec)
    assert (H, W) == ((shape.h_O - 1) * shape.sh + shape.h_F,
                      (shape.w_O - 1) * shape.sw + shape.w_F)
    nbytes = work.conv_bytes(cfg["batch"], spec, "bfloat16", "float32")
    assert nbytes == (2 * (shape.N * shape.c_I * H * W + shape.filter_size)
                      + 4 * shape.output_size)


def test_conv_work_by_hand():
    """conv1 at batch 1000: 2*1000*3*64*112*112*49 FLOPs; input
    1000x3x229x229 bf16, output 1000x64x112x112 f32."""
    spec = _config("resnet50-b1000")["layers"]["conv1"]
    assert work.conv_flops(1000, spec) == 236_027_904_000
    assert work.conv_bytes(1000, spec, "bfloat16", "float32") == (
        2 * 1000 * 3 * 229 * 229 + 2 * 64 * 3 * 49 + 4 * 1000 * 64 * 112 * 112)
    t, bound = work.roofline_s(236_027_904_000, 3_525_000_000,
                               peaks.peaks_for("TPU v5 lite"))
    assert bound == "memory" and t == pytest.approx(3.525e9 / 819e9)
    t, bound = work.roofline_s(2.31e11, 0.19e9, peaks.peaks_for("TPU v5 lite"))
    assert bound == "compute" and t == pytest.approx(2.31e11 / 197e12)


def test_qwen_param_count_matches_program():
    from repro.configs import get_config

    cfg = _config("qwen2.5-3b")
    untied = work.lm_params(cfg, tied=False)
    assert untied == get_config("qwen2_5_3b").param_count()
    assert round(untied / 1e9, 3) == 3.397
    assert untied - work.lm_params(cfg, tied=True) == 151936 * 2048


def test_lm_request_flops_by_hand():
    """Two layers of width 8 (4 heads, 2 KV heads, d_ff 16, vocab 10); a
    3-token prompt and 2 generated tokens go through the layers as 4 tokens
    at positions 0..3 (keys 1+2+3+4 = 10), the head runs twice."""
    dims = {"hidden_size": 8, "intermediate_size": 16, "num_hidden_layers": 2,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "vocab_size": 10}
    per_layer = 8 * 8 + 2 * 8 * 4 + 8 * 8 + 3 * 8 * 16  # q, k+v, o, mlp
    assert work.lm_layer_matmul_params(dims) == per_layer
    want = 4 * 2 * 2 * per_layer + 4 * 2 * 4 * 2 * 10 + 2 * 8 * 10 * 2
    assert work.lm_request_flops(dims, 3, 2) == want
    assert work.lm_job_flops(dims, [(3, 2), (3, 2)]) == 2 * want


def test_unknown_device_kind_is_an_error():
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")
