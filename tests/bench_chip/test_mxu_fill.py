"""``mxu_fill.conv``: the conv FLOPs over the MXU FLOPs the kernels' launch
geometry executes, read from the dispatch decisions a conv cell records."""

import os
import types

import jax
import jax.numpy as jnp
import pytest

from benchmarks.chip import spec, work

READ = spec.load_module(os.path.join(spec.HERE, "metrics",
                                     "mxu_fill.conv.py")).read

# 3 channels at 7x7, stride 2: 12 space-to-depth channels x 4 column taps
# fill 48 of 128 lanes
LAYER = {"c_I": 3, "c_O": 16, "h_O": 16, "w_O": 16, "h_F": 7, "w_F": 7,
         "stride": 2}


def _reading(decisions, batch=8):
    layers = [("a", work.conv_flops(batch, LAYER), 0)] * len(decisions)
    return types.SimpleNamespace(info={"layers": layers,
                                       "decisions": decisions})


def test_nothing_to_read_without_the_counter():
    """Decisions of a program that does not count MXU FLOPs (an older
    ``DispatchDecision``, or a backend without the counter) read None."""
    assert READ(_reading([types.SimpleNamespace(measured_words=1.0)])) is None
    assert READ(_reading([types.SimpleNamespace(mxu_flops=None)])) is None
    assert READ(types.SimpleNamespace(info={"decisions": []})) is None


def test_matches_a_hand_count():
    """At v5e alignment the planner takes one tile, (16, 3, 128, 16, 16):
    a grid of one step whose 16 x 16 x 16 output rows each run 4 x 1 taps of
    128 x 128, so the MXU does 2 * 4,096 * 4 * 128 * 128 = 536,870,912 FLOPs
    for the conv's 2 * 8 * 3 * 16 * 16 * 16 * 7 * 7 = 9,633,792."""
    from repro import ops
    from repro.plan import TPU_V5E

    d = ops.explain(
        "conv2d", ops.ExecutionContext(target=TPU_V5E, backend="pallas"),
        spec_args=(jax.ShapeDtypeStruct((8, 3, 37, 37), jnp.bfloat16),
                   jax.ShapeDtypeStruct((16, 3, 7, 7), jnp.bfloat16)),
        spec_kw={"stride": (2, 2)})
    assert d.plan.tiles == (16, 3, 128, 16, 16)
    assert d.mxu_flops == 536_870_912
    assert work.conv_flops(8, LAYER) == 9_633_792
    assert READ(_reading([d, d])) == pytest.approx(
        100 * 9_633_792 / 536_870_912)
