"""The main-path Pallas kernels, compiled at real widths for a TPU v5e.

No chip is needed: the TPU compiler runs here against a described topology
(``jax.experimental.topologies``) and refuses what it would refuse on the
chip — slices not aligned to the (sublane, lane) tiling, strided vector
slices, scoped-VMEM overflow — none of which interpret mode can see. Every
test asserts that the kernel was lowered through Mosaic (``tpu_custom_call``
in the compiled HLO), not interpreted. The topology is described inside a
fixture, so collecting this file touches no TPU library; the tests skip
where no v5e can be described.
"""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import ops
from repro.configs.qwen2_5_3b import CONFIG as QWEN
from repro.configs.resnet50_convs import RESNET50
from repro.plan import TPU_V5E

# kernel modules that ask repro.plan.pallas_interpret where they run
KERNEL_MODULES = ("conv2d", "matmul", "flash_attention")
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_for_chip(one_chip, monkeypatch):
    """``compile_for_chip(fn, *(shape, dtype))`` -> the HLO text of ``fn``
    compiled for one described v5e. The process runs on the CPU, where the
    kernels would interpret; the fixture points them at Mosaic instead. The
    persistent compile cache is off around the compiles: an executable for
    a described device cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    for name in KERNEL_MODULES:
        monkeypatch.setattr(sys.modules[f"repro.kernels.{name}"],
                            "pallas_interpret", lambda: False)
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()

    def compile_(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()

    yield compile_
    jax.config.update("jax_enable_compilation_cache", cache_on)
    cc.reset_cache()


def _ctx(backend):
    return ops.ExecutionContext(target=TPU_V5E, backend=backend)


def _conv_shapes(layer):
    s = RESNET50[layer]
    H = (s.h_O - 1) * s.sh + s.h_F
    W = (s.w_O - 1) * s.sw + s.w_F
    return s, ((s.N, s.c_I, H, W), BF16), ((s.c_O, s.c_I, s.h_F, s.w_F), BF16)


@pytest.mark.parametrize("layer", ["conv1", "conv2_x", "conv5_x"])
def test_pallas_conv_compiles(compile_for_chip, layer):
    """The paper's kernel on a strided stem, the widest halo and the
    deepest reduction of ResNet-50 at batch 1000."""
    s, x, w = _conv_shapes(layer)
    hlo = compile_for_chip(functools.partial(
        ops.conv2d, stride=(s.sh, s.sw), ctx=_ctx("pallas")), x, w)
    assert "tpu_custom_call" in hlo
    assert "%conv2d_lp." in hlo  # the kernel's name, as a trace shows it


def test_im2col_gemm_compiles_conv3_x(compile_for_chip):
    """The im2col baseline's Pallas GEMM at conv3_x's patch matrix, the
    shape whose plan once overflowed the compiler's scoped VMEM."""
    s, _, _ = _conv_shapes("conv3_x")
    m, k = s.N * s.h_O * s.w_O, s.c_I * s.h_F * s.w_F
    hlo = compile_for_chip(functools.partial(ops.matmul, ctx=_ctx("pallas")),
                           ((m, k), BF16), ((k, s.c_O), BF16))
    assert "tpu_custom_call" in hlo


def test_matmul_compiles_lm_width(compile_for_chip):
    hlo = compile_for_chip(functools.partial(ops.matmul, ctx=_ctx("pallas")),
                           ((2048, QWEN.d_model), BF16),
                           ((QWEN.d_model, QWEN.d_ff), BF16))
    assert "tpu_custom_call" in hlo


def test_flash_prefill_compiles_qwen_widths(compile_for_chip):
    hd = QWEN.d_model // QWEN.n_heads
    hlo = compile_for_chip(
        functools.partial(ops.attention, causal=True, ctx=_ctx("pallas")),
        ((1, QWEN.n_heads, 512, hd), BF16),
        ((1, QWEN.n_kv_heads, 512, hd), BF16),
        ((1, QWEN.n_kv_heads, 512, hd), BF16))
    assert "tpu_custom_call" in hlo
    assert "%flash_attention." in hlo


def test_paged_decode_compiles_qwen_widths(compile_for_chip):
    hd = QWEN.d_model // QWEN.n_heads
    B, blocks, bs, width = 8, 512, 16, 36
    pool = ((blocks, QWEN.n_kv_heads, bs, hd), BF16)
    hlo = compile_for_chip(
        functools.partial(ops.attention_decode, ctx=_ctx("pallas")),
        ((B, QWEN.n_heads, 1, hd), BF16), pool, pool,
        ((B, width), jnp.int32), ((B,), jnp.int32))
    assert "tpu_custom_call" in hlo
    assert "%paged_decode_attention." in hlo


def test_paged_decode_compiles_decode_cell_shape(compile_for_chip):
    """The benchmark's decode cell: 64 slots, its 1,537-block pool and its
    widest table, 24 blocks."""
    hd = QWEN.d_model // QWEN.n_heads
    B, blocks, bs, width = 64, 1537, 16, 24
    pool = ((blocks, QWEN.n_kv_heads, bs, hd), BF16)
    hlo = compile_for_chip(
        functools.partial(ops.attention_decode, ctx=_ctx("pallas")),
        ((B, QWEN.n_heads, 1, hd), BF16), pool, pool,
        ((B, width), jnp.int32), ((B,), jnp.int32))
    assert "tpu_custom_call" in hlo
    assert "%paged_decode_attention." in hlo
