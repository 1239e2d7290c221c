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

import collections
import dataclasses
import functools
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import ops
from repro.configs.lfm2_8b_a1b import CONFIG as LFM2
from repro.configs.qwen2_5_3b import CONFIG as QWEN
from repro.configs.resnet50_convs import RESNET50
from repro.plan import TPU_V5E

# kernel modules that ask repro.plan.pallas_interpret where they run
KERNEL_MODULES = ("conv2d", "matmul", "flash_attention", "conv1d",
                  "grouped_matmul")
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
    """``compile_for_chip(fn, *args)`` -> the HLO text of ``fn`` compiled
    for one described v5e, each argument a ``(shape, dtype)`` pair or a
    pytree of ``jax.ShapeDtypeStruct``. A function jitted already is
    compiled as it is, with its own donation. The process runs on the CPU,
    where the kernels would interpret; the fixture points them at Mosaic
    instead. The persistent compile cache is off around the compiles: an
    executable for a described device cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    for name in KERNEL_MODULES:
        monkeypatch.setattr(sys.modules[f"repro.kernels.{name}"],
                            "pallas_interpret", lambda: False)
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()

    def place(a):
        if isinstance(a, tuple) and len(a) == 2 and isinstance(a[0], tuple):
            return jax.ShapeDtypeStruct(*a, sharding=one_chip)
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), a)

    def compile_(fn, *args):
        jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
        return jitted.lower(*map(place, args)).compile().as_text()

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


def _decode_shapes(B, H, KV, hd, hp, blocks, width, layers=2):
    """The decode op's arguments: q, the stacked pools, tables, lengths,
    the layer, the step's k/v rows."""
    pool = ((layers, blocks, KV, 16, hp), BF16)
    row = ((B, KV, hd), BF16)
    return (((B, H, 1, hd), BF16), pool, pool, ((B, width), jnp.int32),
            ((B,), jnp.int32), ((), jnp.int32), row, row)


def test_paged_decode_compiles_qwen_widths(compile_for_chip):
    hd = QWEN.d_model // QWEN.n_heads
    hlo = compile_for_chip(
        functools.partial(ops.attention_decode, ctx=_ctx("pallas")),
        *_decode_shapes(8, QWEN.n_heads, QWEN.n_kv_heads, hd, hd, 512, 36))
    assert "tpu_custom_call" in hlo
    assert "%paged_decode_attention." in hlo


def test_paged_decode_compiles_decode_cell_shape(compile_for_chip):
    """The benchmark's decode cell: 64 slots, its 1,537-block pool and its
    widest table, 24 blocks, in a stack of 36 layers."""
    hd = QWEN.d_model // QWEN.n_heads
    hlo = compile_for_chip(
        functools.partial(ops.attention_decode, ctx=_ctx("pallas")),
        *_decode_shapes(64, QWEN.n_heads, QWEN.n_kv_heads, hd, hd, 1537, 24,
                        layers=QWEN.repeats))
    assert "tpu_custom_call" in hlo
    assert "%paged_decode_attention." in hlo


# LFM2-8B-A1B as the benchmark serves it: one chip's 8 of 32 experts, 256
# decode slots, prefills of about 19 tokens, the paged pool's 6,145 blocks.
LFM2_SHARE = dataclasses.replace(LFM2, expert_shards=4)


@pytest.mark.parametrize("tokens,proj", [(256, "gate"), (256, "down"),
                                         (19, "gate"), (19, "down")])
def test_grouped_matmul_compiles_lfm2_widths(compile_for_chip, tokens, proj):
    from repro.models.moe import dropless_rows

    D, F = LFM2.d_model, LFM2.expert_d_ff
    K, N = (D, F) if proj == "gate" else (F, D)
    held = LFM2_SHARE.experts_held[1]
    hlo = compile_for_chip(
        functools.partial(ops.grouped_matmul, ctx=_ctx("pallas")),
        ((dropless_rows(tokens, LFM2_SHARE), K), BF16), ((held, K, N), BF16),
        ((held,), jnp.int32))
    assert "tpu_custom_call" in hlo
    assert "%grouped_matmul." in hlo


def test_paged_decode_compiles_lfm2_widths(compile_for_chip):
    """8 kv heads of 64, which the pool pads to the 128 lanes a page's DMA
    needs (``ops.pool_head_dim``); q, the rows, the result and the scores'
    1/sqrt(64) keep the model's head dim."""
    ctx = _ctx("pallas")
    hp = ops.pool_head_dim("attention_decode", LFM2.hd, ctx)
    assert hp == 128
    hlo = compile_for_chip(
        functools.partial(ops.attention_decode, ctx=ctx),
        *_decode_shapes(256, LFM2.n_heads, LFM2.n_kv_heads, LFM2.hd, hp,
                        6145, 24, layers=1))
    assert "tpu_custom_call" in hlo
    assert "%paged_decode_attention." in hlo


# ops that may hold a K/V pool in a decode step: the kernel that updates it
# in place, and the plumbing that passes a buffer along without copying it
POOL_PLUMBING = {"parameter", "get-tuple-element", "tuple", "while",
                 "custom-call", "bitcast", "opt-barrier"}
HLO_TYPES = {"bfloat16": "bf16", "float32": "f32"}


def _entry_aliases(hlo):
    """(shape -> parameter numbers of the entry computation, the parameter
    numbers aliased to an output)."""
    entry = hlo[hlo.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    params = {}
    for shape, n in re.findall(r"= (\w+\[[\d,]*\])\S* parameter\((\d+)\)",
                               entry):
        params.setdefault(shape, set()).add(int(n))
    header = hlo.splitlines()[0]
    aliased = {int(n) for n in re.findall(r"\((\d+), \{\}, \w+-alias\)",
                                          header)}
    return params, aliased


@pytest.mark.parametrize("model", ["qwen", "lfm2"])
def test_paged_decode_step_updates_the_pool_in_place(compile_for_chip,
                                                     model):
    """The serving engine's whole decode step, at a cell's slots, pool and
    widths: no op but the paged kernel writes a K/V pool, so nothing
    pool-sized is copied, sliced out, relaid or stacked anew, and every
    leaf of the cache (pools and short-conv states) is aliased from the
    input the step donates to its output. qwen keeps 2 of its 36 layers (the scan
    carries the stack); LFM2 keeps layers 0-6, attention at 2 and 6."""
    from repro.models import transformer as T
    from repro.serving.kv import PagedStore

    ctx = _ctx("pallas")
    if model == "qwen":
        cfg, B, blocks = dataclasses.replace(QWEN, n_layers=2), 64, 1537
    else:
        cfg, B, blocks = dataclasses.replace(
            LFM2_SHARE, n_layers=7, pattern=LFM2.pattern[:7]), 256, 6145
    hp = ops.pool_head_dim("attention_decode", cfg.hd, ctx)
    params = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0), cfg))
    pool = jax.eval_shape(lambda: T.init_paged_cache(
        cfg, blocks, 16, batch=B, head_dim=hp))
    decode = PagedStore(cfg, 384, B, ctx, 16, blocks).decode_step
    hlo = compile_for_chip(decode, params, pool, ((B, 1), jnp.int32),
                           ((B,), jnp.int32), ((B, 384 // 16), jnp.int32))
    assert "%paged_decode_attention." in hlo

    kv = [leaf.shape for leaf in jax.tree.leaves(pool) if leaf.ndim == 5]
    assert len(kv) == 2 * sum(k == "attn" for k in cfg.pattern)
    pool_dims = {f"[{','.join(map(str, s))}]" for s in kv}
    pool_dims |= {f"[{','.join(map(str, s[1:]))}]" for s in kv}
    writers = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (.*?) ([a-z][\w-]*)\(", line)
        if m and m.group(3) not in POOL_PLUMBING and any(
                d in m.group(2) for d in pool_dims):
            writers.append(f"{m.group(3)} %{m.group(1)}")
    assert not writers, writers

    params_by_shape, aliased = _entry_aliases(hlo)
    leaves = collections.Counter(
        f"{HLO_TYPES[leaf.dtype.name]}[{','.join(map(str, leaf.shape))}]"
        for leaf in jax.tree.leaves(pool))
    for shape, n in leaves.items():  # leaves of one shape: as many aliased
        assert len(params_by_shape.get(shape, set()) & aliased) >= n, shape


@pytest.mark.parametrize("rows,length", [(256, 3), (1, 130)],
                         ids=["decode", "prefill"])
def test_short_conv_compiles_lfm2_widths(compile_for_chip, rows, length):
    """The gated short conv's depthwise conv over its state and the new
    tokens: a decode step (2 + 1 positions) and the longest prefill."""
    hlo = compile_for_chip(
        functools.partial(ops.conv1d_causal, ctx=_ctx("pallas")),
        ((rows, length, LFM2.d_model), BF16),
        ((LFM2.conv_kernel, LFM2.d_model), BF16))
    assert "tpu_custom_call" in hlo
