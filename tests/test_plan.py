"""Tests for the unified ``repro.plan`` subsystem: cache identity, JSON
round-trips, kernel parity between ExecutionPlan and legacy tiles, and the
GEMMINI split-buffer footprint discipline."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.conv_model import INT8_ACC32, Precision, resnet50_layers
from repro.kernels.conv2d import conv2d
from repro.kernels.matmul import matmul
from repro.kernels.ref import conv2d_ref, matmul_ref
from repro.ops import ExecutionContext
from repro.plan import (CPU_INTERPRET, GEMMINI, PLAN_FORMAT_VERSION, TPU_V5E,
                        AttentionSpec, ConvSpec, ExecutionPlan, HardwareTarget,
                        MatmulSpec, Planner, TunedSection, get_target,
                        load_plan_cache, plan, save_plan_cache)

KEY = jax.random.PRNGKey(0)
K2 = jax.random.PRNGKey(1)

CONV = ConvSpec(N=4, c_I=8, c_O=16, w_O=10, h_O=10, w_F=3, h_F=3)
GEMM = MatmulSpec(256, 512, 128, prec=Precision(0.5, 0.5, 1.0))


def _plan(op, target):
    """The post-redesign planning path (no deprecation warning)."""
    return Planner(target).plan(op)


# ---------------------------------------------------------------------------
# plan cache
# ---------------------------------------------------------------------------

def test_plan_cache_returns_identical_object():
    assert _plan(CONV, TPU_V5E) is _plan(CONV, TPU_V5E)
    assert _plan(GEMM, TPU_V5E) is _plan(GEMM, TPU_V5E)
    # equal-by-value keys hit the same entry even via fresh objects
    assert _plan(dataclasses.replace(CONV), TPU_V5E) is _plan(CONV, TPU_V5E)
    # a different target is a different plan
    assert _plan(CONV, CPU_INTERPRET) is not _plan(CONV, TPU_V5E)


def test_target_presets_and_registry():
    assert get_target("tpu_v5e") is TPU_V5E
    assert get_target("gemmini").memory == "split"
    with pytest.raises(KeyError):
        get_target("abacus")


# ---------------------------------------------------------------------------
# JSON round-trip + offline reuse
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("op,target", [
    (CONV, TPU_V5E),
    (GEMM, TPU_V5E),
    (ConvSpec.from_shape(resnet50_layers(64)["conv3_x"]), GEMMINI),
    (MatmulSpec(4096, 2048, 512), TPU_V5E.with_mesh((("data", 4), ("model", 2)))),
])
def test_plan_json_roundtrip(op, target):
    ep = _plan(op, target)
    back = ExecutionPlan.from_json(ep.to_json())
    assert back == ep
    assert back.op == op and back.target == target
    assert back.tiles == ep.tiles and back.grid == ep.grid
    if target.mesh_axes:
        assert back.sharding == ep.sharding
        assert back.sharding.output_spec == ep.sharding.output_spec


def test_v1_conv_plan_json_upgrades():
    """Pre-spatial-tiling (format v1) conv dumps carried 3-tuple tiles and a
    3-axis grid; loading one must yield a working 5-tuple plan (spatial kept
    whole, the old kernel behavior) instead of crashing the new accessors."""
    ep = _plan(CONV, TPU_V5E)
    d = ep.to_dict()
    d["version"] = 1
    d["tiles"] = d["tiles"][:3]
    d["grid"] = [d["grid"][0], d["grid"][1], d["grid"][4]]
    back = ExecutionPlan.from_dict(d)
    assert back.tiles == tuple(d["tiles"]) + (CONV.h_O, CONV.w_O)
    assert len(back.grid) == 5
    assert back.kernel_footprints()["output"] > 0
    back.pallas_specs()


def test_plan_json_upgrade_chain_v1_to_v6():
    """Walk one conv dump through every historical format. v1 (3-tuple tiles,
    3-axis grid, no ``parallel``), v2 (spatial tiles, still no ``parallel``),
    v3 (``parallel`` present), v4 (no per-operand ``dtypes``), v5 (no
    ``tuned`` section), and current v6 fixtures must all load, and each
    upgraded plan must agree with the live plan on everything its era
    recorded — including the ``tuned`` autotune provenance, round-tripped
    when present and defaulted to None on every pre-v6 format."""
    meshed = TPU_V5E.with_mesh((("data", 4), ("model", 2)))
    ep = _plan(CONV, meshed)
    v6 = ep.to_dict()
    assert v6["version"] == PLAN_FORMAT_VERSION == 6
    assert v6["parallel"] is not None
    assert dict(v6["dtypes"])["accum"] == "float32"
    assert v6["tuned"] is None  # analytic plan: no autotune provenance

    # a tuned v6 dump round-trips its provenance section
    ts = TunedSection(source="roofline", candidates_timed=7,
                      winner_words=123.0, winner_seconds=4.5e-6)
    tuned_ep = dataclasses.replace(ep, tuned=ts)
    back_tuned = ExecutionPlan.from_dict(tuned_ep.to_dict())
    assert back_tuned == tuned_ep and back_tuned.tuned == ts

    # v5 predates the tuned section — the key is absent.
    v5 = {k: v for k, v in v6.items() if k != "tuned"}
    v5["version"] = 5
    # v4 predates the per-operand dtypes section — the key is absent.
    v4 = {k: v for k, v in v5.items() if k != "dtypes"}
    v4["version"] = 4
    # v3 conv dumps are layout-identical to v4 (v4 only added attention).
    v3 = dict(v4, version=3)
    # v2 predates the parallel section entirely — the key is absent.
    v2 = {k: v for k, v in v4.items() if k != "parallel"}
    v2["version"] = 2
    # v1 additionally predates spatial tiling: 3-tuple tiles, 3-axis grid.
    v1 = dict(v2, version=1, tiles=v4["tiles"][:3],
              grid=[v4["grid"][0], v4["grid"][1], v4["grid"][4]])

    no_dtypes = dataclasses.replace(ep, dtypes=())
    assert ExecutionPlan.from_dict(v6) == ep
    assert ExecutionPlan.from_dict(v5) == ep  # tuned defaults to None
    assert ExecutionPlan.from_dict(v4) == no_dtypes
    assert ExecutionPlan.from_dict(v3) == no_dtypes
    assert ExecutionPlan.from_dict(v2) == dataclasses.replace(
        no_dtypes, parallel=None)

    from_v1 = ExecutionPlan.from_dict(v1)
    assert from_v1.parallel is None
    assert from_v1.tiles == tuple(v6["tiles"][:3]) + (CONV.h_O, CONV.w_O)
    assert from_v1.grid == (v6["grid"][0], v6["grid"][1], 1, 1, v6["grid"][4])
    assert from_v1.sharding == ep.sharding

    for back in (from_v1, ExecutionPlan.from_dict(v2),
                 ExecutionPlan.from_dict(v3), ExecutionPlan.from_dict(v4),
                 ExecutionPlan.from_dict(v5)):
        assert back.op == ep.op and back.target == ep.target
        assert back.lower_bound == ep.lower_bound
        assert back.tuned is None
        assert back.kernel_footprints()["output"] > 0
        back.pallas_specs()


def test_attention_plan_v4_roundtrip_and_future_version_rejected():
    ep = _plan(AttentionSpec(B=2, H=8, KV=8, Lq=128, Lk=128, hd=64), TPU_V5E)
    back = ExecutionPlan.from_dict(ep.to_dict())
    assert back == ep and isinstance(back.op, AttentionSpec)
    bad = dict(ep.to_dict(), version=PLAN_FORMAT_VERSION + 1)
    with pytest.raises(ValueError, match="newer than"):
        ExecutionPlan.from_dict(bad)


def test_plan_cache_dump_load(tmp_path):
    ep = _plan(CONV, TPU_V5E)
    path = str(tmp_path / "plans.json")
    assert Planner.cache.save(path) >= 1
    n = Planner.cache.load(path)
    assert n >= 1
    # the loaded entries are equal-by-value to the live ones
    assert _plan(CONV, TPU_V5E) == ep


def test_legacy_planning_shims_warn_and_delegate(tmp_path):
    """The pre-redesign module-level surface still works but deprecates:
    every shim warns (message prefixed "legacy" so CI's -W error leg can
    target them) and delegates to the Planner front door."""
    ep = _plan(CONV, TPU_V5E)
    with pytest.deprecated_call(match="legacy planning API"):
        assert plan(CONV, TPU_V5E) is ep
    path = str(tmp_path / "plans.json")
    with pytest.deprecated_call(match="legacy planning API"):
        assert save_plan_cache(path) == Planner.cache.size() + len(
            __import__("repro.plan.autotune", fromlist=["records"]).records())
    with pytest.deprecated_call(match="legacy planning API"):
        assert load_plan_cache(path) >= 1
    from repro.plan import plan_cache_size, clear_plan_cache
    with pytest.deprecated_call(match="legacy planning API"):
        assert plan_cache_size() == Planner.cache.size()
    with pytest.deprecated_call(match="legacy planning API"):
        clear_plan_cache()
    assert Planner.cache.size() == 0


# ---------------------------------------------------------------------------
# kernel parity: ExecutionPlan vs legacy tiles argument
# ---------------------------------------------------------------------------

def test_conv2d_plan_matches_legacy_tiles():
    x = jax.random.normal(KEY, (2, 8, 12, 12), jnp.float32)
    w = jax.random.normal(K2, (16, 8, 3, 3), jnp.float32)
    spec = ConvSpec(N=2, c_I=8, c_O=16, w_O=10, h_O=10, w_F=3, h_F=3,
                    prec=Precision(1.0, 1.0, 1.0))
    ep = _plan(spec, TPU_V5E)
    got_plan = conv2d(x, w, plan=ep)  # explicit plan handoff: no warning
    # the kernel lays its tiles out for the target they were planned for
    with pytest.deprecated_call(match="legacy kernel kwargs"):
        got_tiles = conv2d(x, w, tiles=ep.conv_tiles(), target=TPU_V5E)
    # plans internally through the same cache
    got_default = conv2d(x, w, ctx=ExecutionContext(target=TPU_V5E))
    np.testing.assert_array_equal(np.asarray(got_plan), np.asarray(got_tiles))
    np.testing.assert_array_equal(np.asarray(got_plan), np.asarray(got_default))
    np.testing.assert_allclose(np.asarray(got_plan),
                               np.asarray(conv2d_ref(x, w)),
                               rtol=1e-4, atol=1e-4)


def test_matmul_plan_matches_legacy_tiles():
    a = jax.random.normal(KEY, (100, 77), jnp.float32)
    b = jax.random.normal(K2, (77, 130), jnp.float32)
    ep = _plan(MatmulSpec(100, 130, 77, prec=Precision(1.0, 1.0, 1.0)), TPU_V5E)
    got_plan = matmul(a, b, plan=ep)
    with pytest.deprecated_call(match="legacy kernel kwargs"):
        got_tiles = matmul(a, b, tiles=ep.matmul_tiles())
    np.testing.assert_array_equal(np.asarray(got_plan), np.asarray(got_tiles))
    np.testing.assert_allclose(np.asarray(got_plan),
                               np.asarray(matmul_ref(a, b)),
                               rtol=1e-4, atol=1e-4)


def test_kernel_rejects_mismatched_plan():
    x = jax.random.normal(KEY, (2, 8, 12, 12), jnp.float32)
    w = jax.random.normal(K2, (16, 8, 3, 3), jnp.float32)
    wrong = _plan(ConvSpec(N=4, c_I=8, c_O=16, w_O=10, h_O=10, w_F=3, h_F=3),
                  TPU_V5E)
    with pytest.raises(ValueError):
        conv2d(x, w, plan=wrong)
    a = jax.random.normal(KEY, (64, 32), jnp.float32)
    b = jax.random.normal(K2, (32, 48), jnp.float32)
    with pytest.raises(ValueError):
        matmul(a, b, plan=_plan(MatmulSpec(65, 48, 32), TPU_V5E))
    # a plan solved for narrower input streams than the data must be rejected
    bf16_plan = _plan(MatmulSpec(64, 48, 32, prec=Precision(0.5, 0.5, 1.0)),
                      TPU_V5E)
    with pytest.raises(ValueError, match="word input streams"):
        matmul(a, b, plan=bf16_plan)


def test_legacy_shims_retired():
    """The pre-redesign per-module planners are gone; ``repro.plan.plan`` is
    the single entry point (ROADMAP open item closed in PR 2)."""
    import repro.kernels as kernels
    import repro.kernels.conv2d as conv2d_mod
    import repro.kernels.matmul as matmul_mod
    for mod in (kernels, conv2d_mod, matmul_mod):
        assert not hasattr(mod, "plan_conv_tiles")
        assert not hasattr(mod, "plan_tiles")
    # the replacement path produces the same aligned tiles the shims did
    bm, bn, bk = _plan(MatmulSpec(512, 512, 512, prec=Precision(0.5, 0.5, 1.0)),
                       TPU_V5E).matmul_tiles()
    assert bm % 8 == 0 and bn % 128 == 0 and bk % 128 == 0


# ---------------------------------------------------------------------------
# GEMMINI split-buffer discipline
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("lname", ["conv2_x", "conv4_x"])
def test_gemmini_plans_respect_macc_footprint(lname):
    s = resnet50_layers(1000)[lname].with_precision(INT8_ACC32)
    ep = _plan(ConvSpec.from_shape(s), GEMMINI)
    mem = GEMMINI.memory_model()
    fp = ep.footprints()
    assert fp["input"] + fp["filter"] <= mem.M_eff
    assert fp["output"] <= mem.M_acc_eff
    assert ep.efficiency < 8.0  # stays near the Thm 2.1 bound (paper Fig 4)


# ---------------------------------------------------------------------------
# mesh targets -> sharding plans
# ---------------------------------------------------------------------------

def test_mesh_target_attaches_sharding_plan():
    target = TPU_V5E.with_mesh((("data", 16), ("model", 16)))
    ep = _plan(MatmulSpec(65536, 11008, 2048), target)
    assert ep.sharding is not None
    assert ep.sharding.binding.get("N") == "data"
    assert ep.sharding.binding.get("cO") == "model"
    # single-device plans carry no sharding
    assert _plan(GEMM, TPU_V5E).sharding is None


def test_hardware_target_from_dict_roundtrip():
    t = HardwareTarget.from_dict(GEMMINI.to_dict())
    assert t == GEMMINI


def test_from_dict_ignores_interpret_key_of_old_plan_files():
    old = dict(TPU_V5E.to_dict(), interpret=True)
    assert HardwareTarget.from_dict(old) == TPU_V5E


# ---------------------------------------------------------------------------
# the platform rule: target, backend and interpret mode follow the device
# ---------------------------------------------------------------------------

class _Device:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


@pytest.mark.parametrize("platform,kind,want", [
    ("cpu", "cpu", CPU_INTERPRET),
    ("tpu", "TPU v5 lite", TPU_V5E),
], ids=["cpu", "v5e"])
def test_default_target_follows_the_device(monkeypatch, platform, kind, want):
    from repro.plan import default_target

    monkeypatch.setattr(jax, "devices", lambda: [_Device(platform, kind)])
    monkeypatch.delenv("REPRO_BACKEND", raising=False)
    assert default_target() is want
    assert ExecutionContext().resolved_backend() == (
        "pallas" if platform == "tpu" else "xla")


def test_unknown_accelerator_is_an_error(monkeypatch):
    from repro.analysis.roofline import chip_peaks
    from repro.plan import default_target

    monkeypatch.setattr(jax, "devices", lambda: [_Device("tpu", "TPU v9")])
    with pytest.raises(ValueError, match="TPU v9"):
        default_target()
    with pytest.raises(ValueError, match="TPU v9"):
        chip_peaks("TPU v9")


def test_pallas_interprets_exactly_on_the_cpu(monkeypatch):
    from repro.plan import pallas_interpret

    assert pallas_interpret() is (jax.default_backend() == "cpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pallas_interpret() is False


def test_compile_cache_dir(monkeypatch, tmp_path):
    """The env var is left to JAX; otherwise the cache sits at the fixed
    <checkout>/.jax_cache."""
    import pathlib

    from repro.launch import devices

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *kv: updates.append(kv))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert devices.use_compile_cache() == str(tmp_path)
    assert updates == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    checkout = pathlib.Path(__file__).resolve().parents[1]
    want = str(checkout / ".jax_cache")
    assert devices.use_compile_cache() == want
    assert updates == [("jax_compilation_cache_dir", want)]


def test_plan_pallas_specs_shapes():
    from jax.experimental import pallas as pl

    ep = _plan(GEMM, TPU_V5E)
    grid, in_specs, out_spec = ep.pallas_specs()
    assert grid == ep.grid and len(in_specs) == 2
    bm, bn, bk = ep.tiles
    # inputs stay in ANY/HBM (the kernels stream double-buffered DMA windows
    # themselves); only the output block is lowered via a blocked BlockSpec
    assert all(s.memory_space == pl.ANY for s in in_specs)
    assert out_spec.block_shape == (bm, bn)
    cep = _plan(CONV, TPU_V5E)
    cgrid, _, cout = cep.pallas_specs()
    assert cgrid == cep.grid and len(cgrid) == 5
    bN, bcI, bcO, bh, bw = cep.conv_tiles()
    assert cout.block_shape == (bh, bw, bN, bcO)  # (H, W, N, C) output
