"""repro.distributed: geometry, counters, and shard-count invariance.

Geometry/counter tests are device-free. The invariance tests execute the
halo-exchange conv on real fake-device meshes: the CI ``distributed`` job
gives the whole pytest process 8 fake devices via
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the tests skip
above the process's device count, so the tier-1 single-device run stays
green), and a subprocess smoke keeps the executed path covered on tier-1.
"""

import math
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import distributed, ops
from repro.core.conv_model import ConvShape
from repro.core.parallel_tiling import ParallelBlocking
from repro.distributed import DistConvGeometry, dist_grid
from repro.launch import fake_devices, make_conv_mesh
from repro.plan import ConvSpec, ExecutionPlan, Planner, TPU_V5E

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_DEV = len(jax.devices())

XLA = ops.ExecutionContext(target=TPU_V5E, backend="xla")


def _shape(N=4, c_I=8, c_O=6, H=18, W=18, h_F=3, w_F=3, s=1):
    x = jax.random.normal(jax.random.PRNGKey(0), (N, c_I, H, W), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (c_O, c_I, h_F, w_F),
                          jnp.float32)
    return x, w, (s, s)


def _ref(x, w, stride):
    return np.asarray(ops.conv2d(x, w, stride=stride, ctx=XLA))


def _blocking(x, w, stride, grid):
    sh, sw = stride
    N, c_I, H, W = x.shape
    c_O, _, h_F, w_F = w.shape
    shape = ConvShape(N=N, c_I=c_I, c_O=c_O, h_O=(H - h_F) // sh + 1,
                      w_O=(W - w_F) // sw + 1, h_F=h_F, w_F=w_F, sh=sh, sw=sw)
    return ParallelBlocking.from_grid(shape, grid)


# ---------------------------------------------------------------------------
# Geometry (device-free)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("h_O,ghO,sh,h_F", [
    (16, 4, 1, 3), (13, 4, 1, 3), (11, 2, 2, 3), (7, 8, 1, 3),
    (56, 4, 1, 1), (112, 4, 2, 7), (9, 3, 3, 5),
])
def test_geometry_padding_invariants(h_O, ghO, sh, h_F):
    g = DistConvGeometry.build(N=2, c_I=4, c_O=4, h_O=h_O, w_O=8, h_F=h_F,
                               w_F=1, sh=sh, sw=1, grid={"hO": ghO})
    # every real output row is assigned to some device ...
    assert g.hOp >= h_O
    # ... and the disjoint owned slabs cover the tight VALID input extent,
    # so ring-wraparound halo rows only ever feed padded outputs
    assert g.Hp >= (h_O - 1) * sh + h_F
    assert g.halo_h == max(h_F - sh, 0)
    assert g.h_ext == (g.bh - 1) * sh + h_F
    # the halo plus owned slab exactly assembles the conv window
    assert g.h_ext <= g.bh * sh + g.halo_h


def test_dist_grid_rejects_unservable_axes():
    with pytest.raises(ValueError, match="cannot split"):
        dist_grid({"cO": 2})
    with pytest.raises(ValueError, match="unknown loop axis"):
        dist_grid({"zz": 2})
    assert dist_grid({"hO": 4, "cI": 2}) == (1, 2, 4, 1)


def test_geometry_validate_rejects_too_fine_spatial_grid():
    # 8 output rows over 8 devices -> 1-row slabs, but a 9-tap filter needs
    # an 8-row halo: more than one neighbor owns it
    g = DistConvGeometry.build(N=1, c_I=1, c_O=1, h_O=8, w_O=8, h_F=9, w_F=1,
                               sh=1, sw=1, grid={"hO": 8})
    with pytest.raises(ValueError, match="too fine"):
        g.validate()


def test_counters_pure_data_parallel_moves_nothing():
    x, w, stride = _shape()
    pb = _blocking(x, w, stride, {"N": 4})
    assert distributed.conv2d_dist_comm_words(x, w, stride, pb) == 0.0
    assert distributed.allgather_comm_words(x, w, stride, pb) > 0.0


def test_counters_halo_and_psum_components():
    x, w, stride = _shape()  # 18x18 input, 3x3 filter -> 16x16 out
    shape = _blocking(x, w, stride, {}).shape
    geom = DistConvGeometry.from_shape(shape, {"hO": 2, "wO": 2})
    # 16 output rows over 2 devices pad to 9-row blocks (the owned slabs
    # must cover the 18-row tight input extent, see geometry.py)
    assert (geom.bh, geom.bw) == (9, 9)
    # rows: 2-row halo over the owned 9-col width; cols: 2 cols over 9+2 rows
    assert geom.halo_words() == 4 * 8 * 2 * 9 + 4 * 8 * 11 * 2
    assert geom.psum_words() == 0.0
    g2 = DistConvGeometry.from_shape(shape, {"cI": 2})
    # ring all-reduce: 2 * (g-1)/g * the f32 output block (unsplit spatial
    # axes keep the whole 18-row padded extent in the slab)
    assert g2.psum_words() == 2 * 0.5 * 4 * 6 * g2.bh * g2.bw
    assert g2.halo_words() == 0.0


def test_counter_scales_with_dtype_words():
    x, w, stride = _shape()
    pb = _blocking(x, w, stride, {"hO": 2})
    full = distributed.conv2d_dist_comm_words(x, w, stride, pb)
    half = distributed.conv2d_dist_comm_words(
        jax.ShapeDtypeStruct(x.shape, jnp.bfloat16),
        jax.ShapeDtypeStruct(w.shape, jnp.bfloat16), stride, pb)
    assert half == full / 2  # halo volume is pure input-stream traffic


# ---------------------------------------------------------------------------
# fake_devices (the one process-wide knob)
# ---------------------------------------------------------------------------

def test_fake_devices_idempotent_and_fails_late():
    assert fake_devices(N_DEV) == N_DEV  # already initialized at this count
    with pytest.raises(RuntimeError, match="already initialized"):
        fake_devices(N_DEV + 1)
    with pytest.raises(ValueError):
        fake_devices(0)


# ---------------------------------------------------------------------------
# Plan format v3+: the parallel section (v4 added attention op specs)
# ---------------------------------------------------------------------------

def test_plan_parallel_section_roundtrip():
    from repro.plan import PLAN_FORMAT_VERSION

    tgt = TPU_V5E.with_mesh((("N", 2), ("cI", 2), ("hO", 2), ("wO", 1)))
    p = Planner(tgt).plan(
        ConvSpec(N=8, c_I=16, c_O=16, w_O=16, h_O=16, w_F=3, h_F=3))
    assert p.parallel is not None
    assert p.parallel.P == 8
    assert math.prod(dict(p.parallel.grid).values()) == 8
    assert p.parallel.comm_words >= 0.0
    d = p.to_dict()
    assert d["version"] == PLAN_FORMAT_VERSION >= 3
    assert ExecutionPlan.from_dict(d) == p


def test_plan_v2_dump_loads_with_parallel_none():
    p = Planner(TPU_V5E).plan(
             ConvSpec(N=4, c_I=8, c_O=8, w_O=8, h_O=8, w_F=3, h_F=3))
    d = p.to_dict()
    d.pop("parallel")
    d["version"] = 2
    restored = ExecutionPlan.from_dict(d)
    assert restored.parallel is None
    assert restored.tiles == p.tiles


def test_single_device_plan_has_no_parallel_section():
    p = Planner(TPU_V5E).plan(
             ConvSpec(N=4, c_I=8, c_O=8, w_O=8, h_O=8, w_F=3, h_F=3))
    assert p.parallel is None and p.sharding is None


# ---------------------------------------------------------------------------
# Dispatch: conv2d_dist through the registry
# ---------------------------------------------------------------------------

def test_conv2d_dist_explain_reports_interdevice_words_vs_parallel_bound():
    x = jax.ShapeDtypeStruct((4, 8, 18, 18), jnp.float32)
    w = jax.ShapeDtypeStruct((6, 8, 3, 3), jnp.float32)
    pb = _blocking(x, w, (1, 1), {"cI": 2, "hO": 2})
    ctx = ops.ExecutionContext(
        target=TPU_V5E.with_mesh((("N", 1), ("cI", 2), ("hO", 2), ("wO", 1))),
        backend="pallas")
    dec = ops.explain("conv2d_dist", ctx, dtype="float32", spec_args=(x, w),
                      spec_kw={"stride": (1, 1), "blocking": pb})
    assert dec.chosen == "pallas"
    assert dec.measured_words == distributed.conv2d_dist_comm_words(
        x, w, (1, 1), pb)
    assert dec.measured_words > 0
    # the ratio divides by the plan's Thm 2.2/2.3 parallel bound, not Thm 2.1
    assert dec.plan.parallel is not None
    assert dec.lower_bound == dec.plan.parallel.lower_bound
    assert "inter-device words" in dec.why()


def test_conv2d_shard_rejects_inexact_windows():
    from repro.kernels.conv2d import conv2d_shard, exact_window

    assert exact_window(18, 18, 3, 3, 1, 1)
    assert not exact_window(18, 18, 3, 3, 2, 2)
    x, w, _ = _shape()
    with pytest.raises(ValueError, match="not exact"):
        conv2d_shard(x, w, stride=(2, 2))


# ---------------------------------------------------------------------------
# Shard-count invariance (needs fake devices; CI distributed job has 8)
# ---------------------------------------------------------------------------

# (P, grid): bitwise grids never split cI — the psum would reassociate the
# reduction; cI grids assert allclose instead (below).
BITWISE_GRIDS = [(1, {}), (2, {"hO": 2}), (4, {"hO": 2, "wO": 2}),
                 (8, {"N": 2, "hO": 2, "wO": 2})]
PSUM_GRIDS = [(2, {"cI": 2}), (8, {"cI": 2, "hO": 2, "wO": 2})]


def _needs(P):
    return pytest.mark.skipif(
        N_DEV < P, reason=f"needs {P} devices (run under "
                          f"XLA_FLAGS=--xla_force_host_platform_device_count=8)")


@pytest.mark.parametrize("P,grid", [pytest.param(P, g, marks=_needs(P))
                                    for P, g in BITWISE_GRIDS])
def test_dist_conv_bitwise_invariant_across_shard_counts(P, grid):
    """fp32 halo-exchange conv == the single-device conv bitwise when the
    reduction axis is unsplit, on 1/2/4/8 devices."""
    x, w, stride = _shape()
    pb = _blocking(x, w, stride, grid)
    got = np.asarray(ops.conv2d_dist(x, w, stride=stride, blocking=pb,
                                     ctx=XLA, out_dtype=jnp.float32))
    assert np.array_equal(got, _ref(x, w, stride))


@pytest.mark.parametrize("P,grid", [pytest.param(P, g, marks=_needs(P))
                                    for P, g in PSUM_GRIDS])
def test_dist_conv_psum_grids_allclose(P, grid):
    x, w, stride = _shape()
    pb = _blocking(x, w, stride, grid)
    got = np.asarray(ops.conv2d_dist(x, w, stride=stride, blocking=pb,
                                     ctx=XLA, out_dtype=jnp.float32))
    np.testing.assert_allclose(got, _ref(x, w, stride), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("P,grid,shape_kw", [
    pytest.param(2, {"hO": 2}, dict(H=23, W=19, s=2), marks=_needs(2)),
    pytest.param(4, {"hO": 2, "wO": 2}, dict(H=23, W=19, s=2),
                 marks=_needs(4)),
    pytest.param(8, {"hO": 8}, dict(H=15, W=15), marks=_needs(8)),  # ragged
    pytest.param(4, {"hO": 4}, dict(H=15, W=15), marks=_needs(4)),  # 13/4
])
def test_dist_conv_stride_and_ragged_h_O(P, grid, shape_kw):
    """stride > 1 and non-divisible h_O stay bitwise (no cI split)."""
    x, w, stride = _shape(**shape_kw)
    pb = _blocking(x, w, stride, grid)
    got = np.asarray(ops.conv2d_dist(x, w, stride=stride, blocking=pb,
                                     ctx=XLA, out_dtype=jnp.float32))
    assert np.array_equal(got, _ref(x, w, stride))


@pytest.mark.parametrize("P,grid", [
    pytest.param(4, {"cI": 2, "hO": 2}, marks=_needs(4))])
def test_dist_conv_pallas_local_shards(P, grid):
    """The shard-local conv dispatches to the PR-4 LP-tiled Pallas kernel."""
    x, w, stride = _shape()
    pb = _blocking(x, w, stride, grid)
    ctx = ops.ExecutionContext(target=TPU_V5E, backend="pallas")
    with ops.record_dispatch() as log:
        got = np.asarray(ops.conv2d_dist(x, w, stride=stride, blocking=pb,
                                         ctx=ctx, out_dtype=jnp.float32))
    np.testing.assert_allclose(got, _ref(x, w, stride), rtol=2e-5, atol=2e-5)
    dist_decs = [d for d in log if d.op == "conv2d_dist"]
    local_decs = [d for d in log if d.op == "conv2d"]
    assert dist_decs and dist_decs[0].chosen == "pallas"
    # the shard-local conv went through the registry on the pallas backend
    assert local_decs and local_decs[0].chosen == "pallas"


@pytest.mark.parametrize("P,grid", [
    pytest.param(2, {"wO": 2}, marks=_needs(2)),
    pytest.param(8, {"N": 2, "cI": 2, "hO": 2}, marks=_needs(8))])
def test_allgather_baseline_matches_reference(P, grid):
    x, w, stride = _shape()
    pb = _blocking(x, w, stride, grid)
    got = np.asarray(distributed.allgather_conv(x, w, stride=stride,
                                                blocking=pb,
                                                local_backend="xla"))
    np.testing.assert_allclose(got, _ref(x, w, stride), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("P,grid", [
    pytest.param(4, {"hO": 2, "wO": 2}, marks=_needs(4))])
def test_measured_halo_words_match_lowered_collectives(P, grid):
    """The counter and the lowering share one geometry: the ppermute bytes in
    the compiled HLO equal the predicted halo words exactly."""
    from repro.analysis.roofline import collective_bytes

    x, w, stride = _shape()
    pb = _blocking(x, w, stride, grid)
    mesh = make_conv_mesh(pb)
    # full_output keeps the padded sharded result: the lowering then contains
    # exactly the algorithm's collectives (slicing ragged padding would add
    # small re-layout permutes the counter rightly never charges)
    f = jax.jit(lambda a, b: distributed.halo_conv(
        a, b, stride=stride, blocking=pb, mesh=mesh, local_backend="xla",
        full_output=True))
    hlo = f.lower(x, w).compile().as_text()
    cb = collective_bytes(hlo)
    geom = DistConvGeometry.from_shape(pb.shape, grid)
    assert cb["collective-permute"] == geom.halo_words(p_in=1.0) * 4
    assert cb["all-reduce"] == 0.0  # no cI split -> no psum

    pb2 = _blocking(x, w, stride, {"cI": 2, "hO": 2})
    f2 = jax.jit(lambda a, b: distributed.halo_conv(
        a, b, stride=stride, blocking=pb2, mesh=make_conv_mesh(pb2),
        local_backend="xla", full_output=True))
    cb2 = collective_bytes(f2.lower(x, w).compile().as_text())
    assert cb2["all-reduce"] > 0.0  # the psum is really on the wire

    # single-shard hO with a live row halo: the window's tail rows are a
    # *local* zero fill, never wire traffic — counter still exact
    pb3 = _blocking(x, w, stride, {"wO": 2})
    f3 = jax.jit(lambda a, b: distributed.halo_conv(
        a, b, stride=stride, blocking=pb3, mesh=make_conv_mesh(pb3),
        local_backend="xla", full_output=True))
    cb3 = collective_bytes(f3.lower(x, w).compile().as_text())
    geom3 = DistConvGeometry.from_shape(pb3.shape, {"wO": 2})
    assert cb3["collective-permute"] == geom3.halo_words(p_in=1.0) * 4


def test_psum_counter_is_out_dtype_invariant():
    """The reduction runs on f32 partials before the astype, so the counter
    must not scale psum words with out_dtype (device-free check)."""
    x, w, stride = _shape()
    pb = _blocking(x, w, stride, {"cI": 2})
    w32 = distributed.conv2d_dist_comm_words(x, w, stride, pb,
                                             out_dtype=jnp.float32)
    w16 = distributed.conv2d_dist_comm_words(x, w, stride, pb,
                                             out_dtype=jnp.bfloat16)
    assert w32 == w16 > 0.0


@pytest.mark.parametrize("P,grid", [
    pytest.param(4, {"hO": 2, "cI": 2}, marks=_needs(4))])
def test_dist_conv_differentiates(P, grid):
    x, w, stride = _shape()
    pb = _blocking(x, w, stride, grid)

    def loss(a, b):
        return ops.conv2d_dist(a, b, stride=stride, blocking=pb,
                               ctx=XLA).sum()

    gx, gw = jax.grad(loss, argnums=(0, 1))(x, w)
    assert np.all(np.isfinite(np.asarray(gx)))
    assert np.all(np.isfinite(np.asarray(gw)))


@pytest.mark.slow
def test_dist_conv_subprocess_smoke():
    """Tier-1 coverage of the executed path on a single-device host: a fresh
    subprocess gets 4 fake devices via launch.fake_devices (the supported
    route) and checks halo-exchange == single-device bitwise."""
    code = textwrap.dedent("""
        from repro.launch import fake_devices, make_conv_mesh
        fake_devices(4)
        import jax, jax.numpy as jnp, numpy as np
        from repro import distributed, ops
        from repro.core.conv_model import ConvShape
        from repro.core.parallel_tiling import ParallelBlocking
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 12, 12),
                              jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(1), (3, 4, 3, 3),
                              jnp.float32)
        shape = ConvShape(N=2, c_I=4, c_O=3, h_O=10, w_O=10, h_F=3, w_F=3)
        pb = ParallelBlocking.from_grid(shape, {"hO": 2, "wO": 2})
        from repro.plan import TPU_V5E
        ctx = ops.ExecutionContext(target=TPU_V5E, backend="xla")
        got = ops.conv2d_dist(x, w, blocking=pb, ctx=ctx,
                              out_dtype=jnp.float32)
        ref = ops.conv2d(x, w, ctx=ctx, out_dtype=jnp.float32)
        assert np.array_equal(np.asarray(got), np.asarray(ref))
        print("SUBPROCESS_OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"  # host devices; never the accelerator
    env.pop("XLA_FLAGS", None)  # prove fake_devices sets it, not the env
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=560, env=env)
    assert "SUBPROCESS_OK" in out.stdout, out.stderr[-2000:]
