"""Per-kernel validation: shape/dtype sweeps, Pallas (interpret=True) vs the
pure-jnp oracles in kernels/ref.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import ops
from repro.configs.resnet50_convs import RESNET50
from repro.core.conv_model import Precision
from repro.kernels import ref
from repro.kernels.conv1d import conv1d_causal
from repro.kernels.conv2d import _geometry, conv2d
from repro.kernels.flash_attention import flash_attention
from repro.kernels.matmul import matmul
from repro.plan import ConvSpec, MatmulSpec, Planner, TPU_V5E

KEY = jax.random.PRNGKey(0)
K2 = jax.random.PRNGKey(1)


def _tol(dtype):
    return dict(rtol=3e-2, atol=3e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,n,k", [
    (8, 128, 128), (256, 512, 128), (100, 300, 77), (512, 512, 512),
    (1, 128, 64), (130, 257, 129),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_sweep(m, n, k, dtype):
    a = jax.random.normal(KEY, (m, k), dtype)
    b = jax.random.normal(K2, (k, n), dtype)
    got = matmul(a, b)
    want = ref.matmul_ref(a, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **_tol(dtype))


def test_matmul_tiles_divide_padded_problem():
    for (m, n, k) in [(4096, 4096, 4096), (512, 11008, 2048), (7, 13, 5)]:
        bm, bn, bk = Planner(TPU_V5E).plan(
            MatmulSpec(m, n, k, prec=Precision(0.5, 0.5, 1.0))).matmul_tiles()
        assert bm >= 1 and bn >= 1 and bk >= 1


# ---------------------------------------------------------------------------
# conv2d
# ---------------------------------------------------------------------------

# the few-channel cases, whose column taps fold into the lanes at v5e
# alignment: c_I 8 at 3x3, c_I 3 at 7x7 stride 2, c_I 5 at 3x5 stride (1, 2)
FOLDED_CASES = [
    (2, 8, 16, 12, 12, 3, 3, 1, 1),
    (4, 3, 32, 20, 20, 7, 7, 2, 2),
    (3, 5, 7, 11, 13, 3, 5, 1, 2),
]


def _conv2d_agrees(case, dtype, ctx=None):
    N, cI, cO, H, W, hF, wF, sh, sw = case
    x = jax.random.normal(KEY, (N, cI, H, W), dtype)
    w = jax.random.normal(K2, (cO, cI, hF, wF), dtype)
    got = conv2d(x, w, stride=(sh, sw), ctx=ctx)
    want = ref.conv2d_ref(x, w, stride=(sh, sw))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **_tol(dtype))


@pytest.mark.parametrize("case", [
    (2, 8, 16, 12, 12, 3, 3, 1, 1),
    (4, 3, 32, 20, 20, 7, 7, 2, 2),
    (1, 16, 8, 9, 9, 1, 1, 1, 1),
    (3, 5, 7, 11, 13, 3, 5, 1, 2),
    (2, 4, 4, 8, 8, 2, 2, 2, 2),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_conv2d_sweep(case, dtype):
    _conv2d_agrees(case, dtype)


@pytest.mark.parametrize("case", FOLDED_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_conv2d_sweep_v5e_alignment(case, dtype):
    """The same sweep laid out for a v5e (interpreted): lane-padded
    channels, and the column taps folded into the lanes."""
    ctx = ops.ExecutionContext(target=TPU_V5E)
    N, cI, cO, H, W, hF, wF, sh, sw = case
    x = jax.ShapeDtypeStruct((N, cI, H, W), dtype)
    w = jax.ShapeDtypeStruct((cO, cI, hF, wF), dtype)
    assert _geometry(x, w, (sh, sw), ctx=ctx)[1].fold == -(-wF // sw)
    _conv2d_agrees(case, dtype, ctx)


def _v5e_geometry(layer):
    s = RESNET50[layer]
    x = jax.ShapeDtypeStruct(
        (s.N, s.c_I, (s.h_O - 1) * s.sh + s.h_F, (s.w_O - 1) * s.sw + s.w_F),
        jnp.bfloat16)
    w = jax.ShapeDtypeStruct((s.c_O, s.c_I, s.h_F, s.w_F), jnp.bfloat16)
    return _geometry(x, w, (s.sh, s.sw),
                     ctx=ops.ExecutionContext(target=TPU_V5E))[1]


def test_only_the_stem_folds_at_v5e():
    """ResNet-50's conv1 (12 space-to-depth channels x 4 column taps = 48
    lanes) runs 4 x 1 taps on 128 lanes with no column halo; conv2_x
    (64 x 3 = 192 lanes) and the full-lane layers keep their geometry."""
    g = _v5e_geometry("conv1")
    assert (g.hF, g.wF, g.fold, g.b_c) == (4, 1, 4, 128)
    assert g.w_in == g.tiles[4] and g.Wp == g.wOp
    for layer, grid in (("conv2_x", (63, 1, 4, 2, 1)),
                        ("conv3_x", (63, 1, 2, 1, 1)),
                        ("conv4_x", (21, 1, 2, 1, 2)),
                        ("conv5_x", (16, 1, 3, 1, 4))):
        g = _v5e_geometry(layer)
        assert (g.hF, g.wF, g.fold, g.b_c, g.grid) == (3, 3, 1, 128, grid)
        assert g.w_in == g.tiles[4] + 2 and g.Wp == g.wOp + 2


def test_conv2d_tiles_from_lp_fit_vmem():
    """The kernel tiles (halo windows and lane/sublane padding included)
    must fit inside half-VMEM."""
    from repro.core.tiling import TPU_VMEM_WORDS
    N, cI, cO, hO, wO, hF, wF = 64, 64, 256, 56, 56, 3, 3
    spec = ConvSpec(N=N, c_I=cI, c_O=cO, w_O=wO, h_O=hO, w_F=wF, h_F=hF,
                    prec=Precision(0.5, 0.5, 1.0))
    ep = Planner(TPU_V5E).plan(spec)
    bN, bcI, bcO, bh, bw = ep.conv_tiles()
    assert all(b >= 1 for b in ep.conv_tiles())
    # channels on 128 lanes, the bf16 batch in 16-row sublane tiles
    assert bcO % 128 == 0 and bN % 16 == 0
    lanes = -(-bcI // 128) * 128
    fp = ep.kernel_footprints()
    words = (0.5 * bN * (bh + hF - 1) * (bw + wF - 1) * lanes
             + 0.5 * hF * wF * lanes * bcO + 1.0 * bN * bh * bw * bcO)
    assert words == pytest.approx(sum(fp.values()))
    assert words <= TPU_VMEM_WORDS / 2 * 1.01


@pytest.mark.parametrize("tiles", [
    (1, 4, 8, 5, 7),      # spatial blocks with halo overlap, ragged edges
    (2, 4, 8, 1, 23),     # single-row blocks (maximal halo reuse on h)
    (1, 4, 8, 23, 4),     # w-only spatial tiling
])
@pytest.mark.parametrize("stride", [(1, 1), (2, 2), (2, 1)])
def test_conv2d_spatial_tiling_agrees(tiles, stride):
    """Halo-aware spatial tiling vs the XLA oracle: stride > 1, block sizes
    that do not divide h_O/w_O, and windows sharing h_F - s row halos."""
    x = jax.random.normal(KEY, (2, 4, 25, 25), jnp.float32)
    w = jax.random.normal(K2, (8, 4, 3, 3), jnp.float32)
    with pytest.deprecated_call(match="legacy kernel kwargs"):
        got = conv2d(x, w, stride=stride, tiles=tiles)
    want = ref.conv2d_ref(x, w, stride=stride)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_conv2d_no_halo_when_unit_filter():
    """h_F == w_F == 1: windows are disjoint (halo = h_F - s <= 0), spatial
    tiling degenerates to plain blocking and must still agree."""
    x = jax.random.normal(KEY, (2, 6, 16, 16), jnp.float32)
    w = jax.random.normal(K2, (8, 6, 1, 1), jnp.float32)
    for stride in ((1, 1), (2, 2)):
        with pytest.deprecated_call(match="legacy kernel kwargs"):
            got = conv2d(x, w, stride=stride, tiles=(1, 6, 8, 3, 5))
        want = ref.conv2d_ref(x, w, stride=stride)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


def test_conv2d_plan_tiles_spatial_when_footprint_demands():
    """A batch-1 megapixel conv cannot shrink N or c_O any further, so the
    LP has to block the spatial axes — the v1 full-extent kernel could not
    have run this shape inside VMEM at all."""
    spec = ConvSpec(N=1, c_I=8, c_O=8, w_O=512, h_O=512, w_F=3, h_F=3,
                    prec=Precision(0.5, 0.5, 1.0))
    ep = Planner(TPU_V5E).plan(spec)
    bN, bcI, bcO, bh, bw = ep.conv_tiles()
    assert bh < 512 or bw < 512
    from repro.core.tiling import TPU_VMEM_WORDS
    assert sum(ep.kernel_footprints().values()) <= TPU_VMEM_WORDS / 2 * 1.01


# ---------------------------------------------------------------------------
# conv1d
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,L,D,K", [(2, 16, 32, 4), (3, 100, 64, 3),
                                     (1, 7, 5, 2), (2, 33, 130, 4)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_conv1d_sweep(B, L, D, K, dtype):
    x = jax.random.normal(KEY, (B, L, D), dtype)
    w = jax.random.normal(K2, (K, D), dtype)
    got = conv1d_causal(x, w)
    want = ref.conv1d_causal_ref(x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **_tol(dtype))


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,H,Hkv,Lq,Lk,Dh,causal,off", [
    (1, 4, 4, 64, 64, 32, True, 0),
    (2, 8, 2, 33, 33, 64, True, 0),
    (1, 2, 2, 1, 100, 32, True, 99),   # decode: 1 query vs deep cache
    (1, 2, 1, 50, 70, 16, False, 0),   # encoder + ragged padding
    (1, 1, 1, 200, 200, 128, True, 0),
])
def test_flash_attention_sweep(B, H, Hkv, Lq, Lk, Dh, causal, off):
    q = jax.random.normal(KEY, (B, H, Lq, Dh), jnp.float32) * 0.3
    k = jax.random.normal(K2, (B, Hkv, Lk, Dh), jnp.float32) * 0.3
    v = jax.random.normal(jax.random.PRNGKey(2), (B, Hkv, Lk, Dh), jnp.float32)
    want = ref.flash_attention_ref(q, k, v, causal=causal, q_offset=off)
    rep = H // Hkv
    kk = jnp.repeat(k, rep, axis=1).reshape(B * H, Lk, Dh)
    vv = jnp.repeat(v, rep, axis=1).reshape(B * H, Lk, Dh)
    got = flash_attention(q.reshape(B * H, Lq, Dh), kk, vv, causal=causal,
                          q_offset=off, block_q=32, block_k=32
                          ).reshape(B, H, Lq, Dh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_flash_attention_gqa_group_folding():
    """q_seq_len folds GQA query groups onto the sequence axis: positions
    restart per group, so the grouped call matches per-head flash calls
    without ever repeating K/V (backend-agreement lives in test_ops)."""
    B, Hkv, g, Lq, Dh = 1, 2, 3, 40, 16
    q = jax.random.normal(KEY, (B, Hkv, g, Lq, Dh), jnp.float32) * 0.3
    k = jax.random.normal(K2, (B, Hkv, Lq, Dh), jnp.float32) * 0.3
    v = jax.random.normal(jax.random.PRNGKey(2), (B, Hkv, Lq, Dh), jnp.float32)
    got = flash_attention(q.reshape(B * Hkv, g * Lq, Dh),
                          k.reshape(B * Hkv, Lq, Dh),
                          v.reshape(B * Hkv, Lq, Dh),
                          causal=True, q_seq_len=Lq, block_q=32, block_k=32)
    want = jnp.stack([
        flash_attention(q[:, :, j].reshape(B * Hkv, Lq, Dh),
                        k.reshape(B * Hkv, Lq, Dh),
                        v.reshape(B * Hkv, Lq, Dh),
                        causal=True, block_q=32, block_k=32)
        for j in range(g)], axis=1)  # (B*Hkv, g, Lq, Dh)
    np.testing.assert_allclose(
        np.asarray(got).reshape(B * Hkv, g, Lq, Dh), np.asarray(want),
        rtol=2e-3, atol=2e-3)
