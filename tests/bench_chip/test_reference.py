"""The plain references: the f32 conv against a loop written out by hand,
and the f32 Qwen2 decoder against the serving engine's greedy tokens at the
program's smoke size."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip.reference import conv as conv_ref
from benchmarks.chip.reference import qwen2 as lm_ref
from benchmarks.chip.traffic import seed_key
from conftest import ROOT


def _naive_conv(x, w, s):
    N, C, H, W = x.shape
    O, _, hF, wF = w.shape
    hO, wO = (H - hF) // s + 1, (W - wF) // s + 1
    out = np.zeros((N, O, hO, wO))
    for i in range(hO):
        for j in range(wO):
            patch = x[:, :, i * s:i * s + hF, j * s:j * s + wF]
            out[:, :, i, j] = np.einsum("nchw,ochw->no", patch, w)
    return out


@pytest.mark.parametrize("stride", [1, 2])
def test_conv_reference_against_loops(stride):
    rng = np.random.default_rng(stride)
    x = rng.standard_normal((4, 3, 11, 9)).astype(np.float32)
    w = rng.standard_normal((5, 3, 3, 3)).astype(np.float32)
    got = np.asarray(conv_ref.conv_f32(jnp.asarray(x), jnp.asarray(w), stride))
    np.testing.assert_allclose(got, _naive_conv(x, w, stride), rtol=1e-5,
                               atol=1e-5)
    out = jnp.asarray(got).at[3, 4, 1, 2].add(0.25)
    err, mag = conv_ref.error(out, jnp.asarray(x), jnp.asarray(w),
                              stride=stride)
    assert float(err) == pytest.approx(0.25, rel=1e-5)
    assert float(mag) == pytest.approx(np.abs(got).max(), rel=1e-6)
    cerr, _ = conv_ref.control_error(jnp.asarray(x), jnp.asarray(w),
                                     stride=stride)
    assert float(cerr) > 1e-3 * float(mag)


def test_weights_are_the_same_generated_whole_or_by_layer():
    dims = {"hidden_size": 32, "intermediate_size": 48,
            "num_hidden_layers": 3, "num_attention_heads": 4,
            "num_key_value_heads": 2, "vocab_size": 64, "head_dim": 8}
    key = seed_key(2**33 + 3)
    whole = jax.jit(lambda k: jax.lax.map(
        lambda i: lm_ref.layer_weights(k, dims, i), jnp.arange(3)))(key)
    for layer in range(3):
        one = lm_ref.layer_weights(key, dims, layer)
        for leaf, a in one.items():
            assert a.dtype == jnp.bfloat16
            assert bool(jnp.all(whole[leaf][layer] == a)), (leaf, layer)


def test_engine_greedy_tokens_pass_the_reference_at_smoke_size():
    """The chip run's comparison, at ``get_smoke("qwen2_5_3b")``'s widths:
    every served token of every request, first and later, lies within the
    tiny cells' limit of the reference's best logit."""
    from conftest import TINY_LM
    from benchmarks.chip.kinds import lm_serve
    from repro.configs import get_smoke
    from repro.serving.engine import Engine, Request

    smoke = get_smoke("qwen2_5_3b")
    config = dict(json.load(open(os.path.join(
        ROOT, "benchmarks", "chip", "configs", "qwen2.5-3b.json"))),
        hidden_size=smoke.d_model, intermediate_size=smoke.d_ff,
        num_hidden_layers=smoke.n_layers, num_attention_heads=smoke.n_heads,
        num_key_value_heads=smoke.n_kv_heads, vocab_size=smoke.vocab_size)
    cfg = lm_serve.model_config(config)
    seed = 7
    eng = Engine(cfg, lm_serve.program_weights(config, cfg, seed),
                 max_len=48, batch_size=2)
    rng = np.random.default_rng(0)
    reqs = [Request(prompt=rng.integers(0, smoke.vocab_size, n,
                                        dtype=np.int32), max_new_tokens=m)
            for n, m in ((5, 12), (19, 20), (33, 8))]
    eng.serve(reqs)
    assert [len(r.out_tokens) for r in reqs] == [12, 20, 8]
    out = lm_ref.served_gaps(config, seed,
                             [(r.prompt, r.out_tokens) for r in reqs],
                             length=64, group_tokens=128)
    assert out["gap"].shape == (40,)
    assert float(out["gap"].min()) >= 0.0
    assert float(out["gap"].max()) <= TINY_LM["check"]["logit_gap_limit"]
    assert float(np.mean(out["gap"] == 0)) > 0.8
