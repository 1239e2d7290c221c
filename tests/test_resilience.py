"""Resilience tests: the fault taxonomy, deterministic seeded campaigns,
runtime-failure fallback in dispatch (quarantine + repriced degradation),
graceful degradation in the serving engine (retries, per-row failure,
backpressure, pool rebuild, deadlines), the distributed re-dispatch smoke,
the VRF014 lint rule, and the ``fault_swallowed`` mutant."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import ops
from repro.configs import get_smoke
from repro.models import transformer as T
from repro.plan import CPU_INTERPRET
from repro.resilience import errors as flt
from repro.resilience import faults as fj
from repro.serving import kv
from repro.serving.engine import Engine, Request

KEY = jax.random.PRNGKey(0)
PALLAS = ops.ExecutionContext(target=CPU_INTERPRET, backend="pallas")
IM2COL = ops.ExecutionContext(target=CPU_INTERPRET, backend="im2col")
XLA = ops.ExecutionContext(target=CPU_INTERPRET, backend="xla")

P1 = np.array([3, 1, 4, 1, 5], np.int32)


@pytest.fixture(autouse=True)
def _clean_slate():
    """Quarantine and campaign state are process-global; isolate tests."""
    ops.clear_quarantine()
    fj.install(None)
    yield
    ops.clear_quarantine()
    fj.install(None)


@pytest.fixture(scope="module")
def engine_setup():
    cfg = dataclasses.replace(get_smoke("stablelm_1_6b"),
                              compute_dtype="float32")
    return cfg, T.init_params(KEY, cfg)


def _reqs(n=4, max_new=6, **kw):
    return [Request(prompt=P1.copy(), max_new_tokens=max_new, rng_seed=i,
                    **kw) for i in range(n)]


def _conv_args():
    x = jax.random.normal(KEY, (2, 8, 12, 12), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (8, 8, 3, 3), jnp.float32)
    return x, w


# ---------------------------------------------------------------------------
# Taxonomy
# ---------------------------------------------------------------------------

def test_taxonomy_transient_vs_fatal():
    assert issubclass(flt.Fault, RuntimeError)  # legacy except-clauses work
    for cls in (flt.KernelLaunchError, flt.NumericFault, flt.DmaTimeout,
                flt.PoolIntegrityFault):
        assert issubclass(cls, flt.TransientFault) and cls("x").transient
    for cls in (flt.DeviceLost, flt.AdmissionImpossible, flt.SchedulerStall,
                flt.FaultAccountingError):
        assert issubclass(cls, flt.FatalFault) and not cls("x").transient


def test_fault_str_carries_diagnostics():
    e = flt.KernelLaunchError("boom", op="conv2d", backend="pallas",
                              grid=(4, 4))
    s = str(e)
    assert "boom" in s and "op=conv2d" in s and "backend=pallas" in s
    assert "grid=(4, 4)" in s
    assert e.diagnostics["grid"] == (4, 4)


def test_blockoom_reclassified_transient():
    from repro.serving.kv import BlockOOM
    assert issubclass(BlockOOM, flt.TransientFault)
    assert issubclass(BlockOOM, RuntimeError)


def test_allocator_check_raises_typed_fault_with_occupancy():
    from repro.serving import kv
    alloc = kv.BlockAllocator(8)
    alloc.alloc()
    camp = fj.FaultCampaign(seed=0, rate=1.0, kinds=("pool",))
    inj = camp.draw("decode/pool")
    camp.corrupt_allocator(alloc, inj)
    with pytest.raises(flt.PoolIntegrityFault) as ei:
        alloc.check()
    assert ei.value.transient
    assert ei.value.diagnostics["num_blocks"] == 8
    assert "corruption" in inj.detail


# ---------------------------------------------------------------------------
# Campaign determinism + spec parsing
# ---------------------------------------------------------------------------

def test_campaign_is_deterministic_per_seed():
    def run(seed):
        c = fj.FaultCampaign(seed=seed, rate=0.3)
        return [(c.draw(f"site{i}") or None) and (c.injections[-1].site,
                                                  c.injections[-1].kind)
                for i in range(40)]
    assert run(7) == run(7)
    assert run(7) != run(8)


def test_campaign_max_faults_caps_injections():
    c = fj.FaultCampaign(seed=0, rate=1.0, max_faults=3)
    for i in range(10):
        c.draw(f"s{i}")
    assert len(c.injections) == 3 and c.draws == 10


def test_campaign_rejects_bad_config():
    with pytest.raises(ValueError, match="unknown fault kinds"):
        fj.FaultCampaign(kinds=("warp_drive",))
    with pytest.raises(ValueError, match="rate"):
        fj.FaultCampaign(rate=1.5)


def test_campaign_from_spec_round_trip():
    c = fj.campaign_from_spec(
        "rate=0.25,seed=9,kinds=launch+pool,ops=conv2d,max=5")
    assert (c.rate, c.seed, c.kinds, c.ops, c.max_faults) == \
        (0.25, 9, ("launch", "pool"), ("conv2d",), 5)
    with pytest.raises(ValueError, match="unknown REPRO_FAULTS field"):
        fj.campaign_from_spec("rate=0.1,typo=1")
    with pytest.raises(ValueError, match="expected key=value"):
        fj.campaign_from_spec("justarate")


def test_verify_accounted_flags_swallowed_fault():
    c = fj.FaultCampaign(seed=0, rate=1.0, max_faults=1)
    inj = c.draw("dispatch/conv2d")
    assert inj is not None
    with pytest.raises(flt.FaultAccountingError, match="swallowed"):
        c.verify_accounted()
    c.resolve(inj, "retried")
    c.verify_accounted()  # now clean


def test_fault_swallowed_mutant_is_caught():
    from repro.verify.mutants import run_seeded_mutants
    results = {name: caught for name, caught, _ in run_seeded_mutants()}
    assert results["fault_swallowed"]


# ---------------------------------------------------------------------------
# Dispatch: runtime-failure fallback
# ---------------------------------------------------------------------------

def test_launch_fault_degrades_conv2d_to_im2col_and_reprices():
    x, w = _conv_args()
    want = np.asarray(ops.conv2d(x, w, ctx=IM2COL))
    camp = fj.FaultCampaign(seed=0, rate=1.0, kinds=("launch",),
                            ops=("conv2d",), max_faults=1)
    spec = {"spec_args": (jax.ShapeDtypeStruct(x.shape, x.dtype),
                          jax.ShapeDtypeStruct(w.shape, w.dtype)),
            "spec_kw": {"stride": (1, 1), "out_dtype": jnp.float32}}
    clean = ops.explain("conv2d", PALLAS, **spec)
    with fj.activate(camp):
        got = np.asarray(ops.conv2d(x, w, ctx=PALLAS))
    camp.verify_accounted()
    assert camp.summary()["resolutions"] == {"degraded": 1}
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # the degradation is visible and re-priced in ops.explain
    dec = ops.explain("conv2d", PALLAS, **spec)
    assert dec.degraded and dec.fault == "KernelLaunchError"
    assert dec.chosen == "im2col" and dec.requested == "pallas"
    assert dec.measured_words > clean.measured_words
    assert dec.bound_ratio > clean.bound_ratio
    assert "degraded" in dec.why() and "re-priced" in dec.why()
    (key,) = ops.quarantined()
    assert key[0] == "conv2d" and key[1] == "pallas"


def test_quarantine_probes_primary_after_n_dispatches():
    x, w = _conv_args()
    camp = fj.FaultCampaign(seed=0, rate=1.0, kinds=("launch",),
                            ops=("conv2d",), max_faults=1)
    with fj.activate(camp):
        ops.conv2d(x, w, ctx=PALLAS)
    assert ops.quarantined()
    # the demoting dispatch consumed one probe on its own re-resolve; the
    # quarantine holds (serving im2col) for PROBE_AFTER-1 more dispatches...
    for _ in range(ops.QUARANTINE_PROBE_AFTER - 1):
        assert ops.quarantined()
        ops.conv2d(x, w, ctx=PALLAS)
    # ...then the primary is probed again and, healthy, fully restored
    assert not ops.quarantined()
    dec = ops.explain(
        "conv2d", PALLAS,
        spec_args=(jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(w.shape, w.dtype)),
        spec_kw={"stride": (1, 1), "out_dtype": jnp.float32})
    assert not dec.degraded and dec.chosen == "pallas"


def test_quarantine_is_shape_keyed():
    x, w = _conv_args()
    camp = fj.FaultCampaign(seed=0, rate=1.0, kinds=("launch",),
                            ops=("conv2d",), max_faults=1)
    with fj.activate(camp):
        ops.conv2d(x, w, ctx=PALLAS)
    # a different launch geometry is untouched by the quarantine
    x2 = jnp.concatenate([x, x], axis=0)
    dec = ops.explain(
        "conv2d", PALLAS,
        spec_args=(jax.ShapeDtypeStruct(x2.shape, x2.dtype),
                   jax.ShapeDtypeStruct(w.shape, w.dtype)),
        spec_kw={"stride": (1, 1), "out_dtype": jnp.float32})
    assert not dec.degraded and dec.chosen == "pallas"


def test_terminal_backend_retries_in_place():
    x, w = _conv_args()
    want = np.asarray(ops.conv2d(x, w, ctx=XLA))
    camp = fj.FaultCampaign(seed=0, rate=1.0, kinds=("launch",),
                            ops=("conv2d",), max_faults=1)
    with fj.activate(camp):
        got = np.asarray(ops.conv2d(x, w, ctx=XLA))
    camp.verify_accounted()
    assert camp.summary()["resolutions"] == {"retried": 1}
    assert not ops.quarantined()  # nothing to demote to: no quarantine
    np.testing.assert_allclose(got, want)


def test_persistent_transient_fault_exhausts_attempts():
    x, w = _conv_args()
    camp = fj.FaultCampaign(seed=0, rate=1.0, kinds=("launch",),
                            ops=("conv2d",))  # unbounded: every attempt fails
    with fj.activate(camp), pytest.raises(flt.KernelLaunchError):
        ops.conv2d(x, w, ctx=XLA)


def test_device_lost_is_fatal_and_propagates():
    x, w = _conv_args()
    camp = fj.FaultCampaign(seed=0, rate=1.0, kinds=("device",),
                            ops=("conv2d",), max_faults=1)
    with fj.activate(camp), pytest.raises(flt.DeviceLost):
        ops.conv2d(x, w, ctx=PALLAS)
    camp.verify_accounted()  # stamped "fatal" at the raise site
    assert camp.injections[0].resolution == "fatal"
    assert not ops.quarantined()  # fatal faults never demote


@pytest.mark.parametrize("error", [
    jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: Ran out of memory in "
                               "memory space vmem"),
    ValueError("Mosaic failed to compile the kernel"),
], ids=["runtime", "compile"])
def test_real_kernel_errors_propagate_undegraded(monkeypatch, error):
    """Only the TransientFault taxonomy degrades: a real compile or runtime
    error from a kernel surfaces unchanged and quarantines nothing, so a
    pallas kernel that fails on the chip can never be served by im2col or
    xla in silence."""
    from repro.ops import registry

    def fails(*args, **kwargs):
        raise error

    monkeypatch.setattr(registry, "_conv2d_pallas", fails)
    x, w = _conv_args()
    with ops.record_dispatch() as log, pytest.raises(type(error)):
        ops.conv2d(x, w, ctx=PALLAS)
    assert not ops.quarantined()
    assert log == []  # no decision claims the call was served


def test_numeric_fault_corrupts_then_degrades():
    x, w = _conv_args()
    want = np.asarray(ops.conv2d(x, w, ctx=IM2COL))
    camp = fj.FaultCampaign(seed=0, rate=1.0, kinds=("numeric",),
                            ops=("conv2d",), max_faults=1)
    with fj.activate(camp):
        got = np.asarray(ops.conv2d(x, w, ctx=PALLAS))
    camp.verify_accounted()
    assert np.all(np.isfinite(got))  # the NaN output never escaped
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_faults_never_fire_under_tracing():
    a = jax.random.normal(KEY, (8, 8), jnp.float32)
    camp = fj.FaultCampaign(seed=0, rate=1.0, kinds=("launch", "numeric"))
    with fj.activate(camp):
        out = jax.jit(lambda p, q: ops.matmul(p, q, ctx=XLA))(a, a)
    assert camp.injections == []  # tracer args -> the hook stands down
    np.testing.assert_allclose(np.asarray(out), np.asarray(a @ a),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Serving engine: graceful degradation
# ---------------------------------------------------------------------------

def test_deadline_expires_with_partial_output(engine_setup):
    cfg, params = engine_setup
    eng = Engine(cfg, params, max_len=32, batch_size=1)
    out = eng.serve([Request(prompt=P1.copy(), max_new_tokens=5000,
                             deadline_s=1e-9)])
    assert out[0].finish_reason == "timeout"
    assert 0 < len(out[0].out_tokens) < 5000
    assert eng.stats.failures == {"timeout": 1}


def test_deadline_must_be_positive(engine_setup):
    cfg, params = engine_setup
    eng = Engine(cfg, params, max_len=32, batch_size=1)
    with pytest.raises(ValueError, match="deadline_s"):
        eng.serve([Request(prompt=P1.copy(), deadline_s=0.0)])


def test_admission_retry_exhaustion_fails_one_request(engine_setup):
    cfg, params = engine_setup
    # 4 launch faults = 1 admission (3 retries + terminal failure); the
    # remaining requests admit cleanly and complete
    camp = fj.FaultCampaign(seed=0, rate=1.0, kinds=("launch",),
                            max_faults=4)
    eng = Engine(cfg, params, max_len=32, batch_size=2)
    with fj.activate(camp):
        out = eng.serve(_reqs(3, max_new=4))
    camp.verify_accounted()
    reasons = [r.finish_reason for r in out]
    assert reasons == ["error", "length", "length"]
    assert len(out[0].out_tokens) == 0
    assert camp.summary()["resolutions"] == {"retried": 3, "row_failed": 1}
    s = eng.stats
    assert (s.admissions, s.admit_retries, s.failures) == (3, 3, {"admit": 1})
    assert out[0].t_first is None and out[0].t_done >= out[0].t_admit


def test_decode_nan_fails_only_bad_rows_with_clean_prefix(engine_setup):
    cfg, params = engine_setup
    eng = Engine(cfg, params, max_len=32, batch_size=2)
    clean = eng.serve(_reqs(2))
    camp = fj.FaultCampaign(seed=3, rate=1.0, kinds=("numeric",),
                            ops=("decode",), max_faults=2, )
    eng = Engine(cfg, params, max_len=32, batch_size=2, numeric_retries=0)
    with fj.activate(camp):
        out = eng.serve(_reqs(2))
    camp.verify_accounted()
    assert camp.summary()["resolutions"] == {"row_failed": 2}
    assert (eng.stats.numeric_retries, eng.stats.failures) == (
        0, {"numeric": 2})
    for c, f in zip(clean, out):
        assert f.finish_reason == "error"
        # no tokens recorded from the faulted step; the prefix is the
        # clean run's tokens bit for bit
        assert len(f.out_tokens) < len(c.out_tokens)
        assert np.array_equal(f.out_tokens,
                              np.asarray(c.out_tokens)[:len(f.out_tokens)])


def test_decode_nan_retry_recovers_idempotently(engine_setup):
    cfg, params = engine_setup
    eng = Engine(cfg, params, max_len=32, batch_size=2)
    clean = eng.serve(_reqs(2))
    camp = fj.FaultCampaign(seed=3, rate=1.0, kinds=("numeric",),
                            ops=("decode",), max_faults=1)
    eng = Engine(cfg, params, max_len=32, batch_size=2)
    with fj.activate(camp):
        out = eng.serve(_reqs(2))
    camp.verify_accounted()
    assert camp.summary()["resolutions"] == {"retried": 1}
    assert eng.stats.numeric_retries == 1 and not eng.stats.failures
    for c, f in zip(clean, out):  # the retried step changed nothing
        assert f.finish_reason == c.finish_reason
        assert np.array_equal(f.out_tokens, c.out_tokens)


def test_injected_oom_rides_backpressure_to_completion(engine_setup):
    cfg, params = engine_setup
    eng = Engine(cfg, params, max_len=32, batch_size=2)
    clean = eng.serve(_reqs(3))
    camp = fj.FaultCampaign(seed=0, rate=1.0, kinds=("oom",), max_faults=3)
    eng = Engine(cfg, params, max_len=32, batch_size=2)
    with fj.activate(camp):
        out = eng.serve(_reqs(3))
    camp.verify_accounted()
    assert camp.summary()["resolutions"] == {"backpressure": 3}
    assert eng.stats.backpressure == 3
    for c, f in zip(clean, out):
        assert f.finish_reason == c.finish_reason
        assert np.array_equal(f.out_tokens, c.out_tokens)


def test_pool_corruption_triggers_exact_rebuild(engine_setup):
    cfg, params = engine_setup
    eng = Engine(cfg, params, max_len=32, batch_size=2)
    assert eng.paged  # the rebuild path is the paged engine's
    clean = eng.serve(_reqs(4))
    camp = fj.FaultCampaign(seed=2, rate=0.5, kinds=("pool",))
    eng = Engine(cfg, params, max_len=32, batch_size=2)
    with fj.activate(camp):
        out = eng.serve(_reqs(4))
    camp.verify_accounted()
    assert camp.summary()["resolutions"].get("rebuilt", 0) >= 1
    for c, f in zip(clean, out):  # rebuilds reproduce the cache exactly
        assert f.finish_reason == c.finish_reason
        assert np.array_equal(f.out_tokens, c.out_tokens)


def test_pool_rebuild_keeps_shared_prefixes(engine_setup, monkeypatch):
    """Rebuilds while live requests share a prompt-prefix block: every
    request ends with the clean run's tokens and finish reason, and at every
    decode step each slot's blocks carry the clean run's refcounts, so the
    rebuilt pool shares the prefix block again rather than copying it."""
    cfg, params = engine_setup
    prefix = np.arange(1, 17, dtype=np.int32)  # one full block of 16
    specs = ((2, 6), (3, 4), (5, 7), (1, 5), (4, 3))

    def reqs():
        return [Request(prompt=np.concatenate(
            [prefix, np.full(n, 20 + n, np.int32)]), max_new_tokens=m)
            for n, m in specs]

    steps, shared_at_rebuild = [], []
    decode, check = kv.PagedStore.decode, kv.PagedStore.check

    def spy_decode(self, *args):
        steps[-1].append([[self.alloc.refcount(b) for b in blocks]
                          for blocks in self.blocks])
        return decode(self, *args)

    def spy_check(self):
        sound = check(self)
        if not sound:
            shared_at_rebuild.append(max(
                (self.alloc.refcount(b) for bl in self.blocks for b in bl),
                default=0))
        return sound

    monkeypatch.setattr(kv.PagedStore, "decode", spy_decode)
    monkeypatch.setattr(kv.PagedStore, "check", spy_check)
    camp = fj.FaultCampaign(seed=2, rate=0.5, kinds=("pool",))
    runs = []
    for c in (None, camp):
        steps.append([])
        with fj.activate(c):
            runs.append(Engine(cfg, params, max_len=32,
                               batch_size=2).serve(reqs()))
    camp.verify_accounted()
    assert max(shared_at_rebuild) == 2  # rebuilt with both slots sharing
    assert steps[0] == steps[1]
    for c, f in zip(*runs):
        assert f.finish_reason == c.finish_reason
        assert np.array_equal(f.out_tokens, c.out_tokens)


def test_admission_impossible_is_typed_with_diagnostics(engine_setup):
    cfg, params = engine_setup
    eng = Engine(cfg, params, max_len=32, batch_size=1, num_blocks=2)
    with pytest.raises(flt.AdmissionImpossible,
                       match="cannot ever admit") as ei:
        eng.serve([Request(prompt=np.arange(1, 30, dtype=np.int32),
                           max_new_tokens=4)])
    d = ei.value.diagnostics  # block 0 is the reserved garbage block
    assert d["num_blocks"] == 2 and d["blocks_needed"] > d["available_blocks"]


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_chaos_schedule_no_deadlock_and_unaffected_identical(
        engine_setup, seed):
    """Seeded chaos over every engine site: the loop always terminates,
    every injection is accounted, completed requests are bit-identical to
    the fault-free run and failed ones a clean prefix of it."""
    cfg, params = engine_setup
    eng = Engine(cfg, params, max_len=32, batch_size=2)
    clean = eng.serve(_reqs(5))
    camp = fj.FaultCampaign(
        seed=seed, rate=0.2,
        kinds=("launch", "dma", "numeric", "oom", "pool"))
    eng = Engine(cfg, params, max_len=32, batch_size=2)
    with fj.activate(camp):
        out = eng.serve(_reqs(5))
    camp.verify_accounted()
    for c, f in zip(clean, out):
        assert f.finish_reason is not None  # nobody is left hanging
        c_toks = np.asarray(c.out_tokens)
        if f.finish_reason == "error":
            assert np.array_equal(f.out_tokens, c_toks[:len(f.out_tokens)])
        else:
            assert f.finish_reason == c.finish_reason
            assert np.array_equal(f.out_tokens, c_toks)


def test_chaos_schedules_hypothesis():
    """Property-based chaos: any (seed, rate, kinds) campaign terminates
    with full fault accounting and clean-prefix outputs."""
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")
    cfg = dataclasses.replace(get_smoke("stablelm_1_6b"),
                              compute_dtype="float32")
    params = T.init_params(KEY, cfg)
    eng = Engine(cfg, params, max_len=32, batch_size=2)
    clean = eng.serve(_reqs(3, max_new=4))

    @hyp.settings(max_examples=10, deadline=None)
    @hyp.given(seed=st.integers(0, 2**31 - 1),
               rate=st.floats(0.0, 0.5),
               kinds=st.sets(st.sampled_from(
                   ("launch", "dma", "numeric", "oom", "pool")),
                   min_size=1))
    def run(seed, rate, kinds):
        camp = fj.FaultCampaign(seed=seed, rate=rate, kinds=tuple(kinds))
        e = Engine(cfg, params, max_len=32, batch_size=2)
        with fj.activate(camp):
            out = e.serve(_reqs(3, max_new=4))
        camp.verify_accounted()
        for c, f in zip(clean, out):
            c_toks = np.asarray(c.out_tokens)
            assert f.finish_reason is not None
            if f.finish_reason == "error":
                assert np.array_equal(f.out_tokens,
                                      c_toks[:len(f.out_tokens)])
            else:
                assert np.array_equal(f.out_tokens, c_toks)

    run()


# ---------------------------------------------------------------------------
# Distributed: shard fault re-dispatches through the xla leg
# ---------------------------------------------------------------------------

def test_dist_shard_fault_redispatches_through_xla():
    from repro.core.conv_model import ConvShape
    from repro.core.parallel_tiling import ParallelBlocking

    x = jax.random.normal(KEY, (2, 4, 18, 18), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 4, 3, 3), jnp.float32)
    shape = ConvShape(N=2, c_I=4, c_O=4, h_O=16, w_O=16, h_F=3, w_F=3,
                      sh=1, sw=1)
    pb = ParallelBlocking.from_grid(shape, {})  # 1-device smoke grid
    want = np.asarray(ops.conv2d_dist(x, w, stride=(1, 1), blocking=pb,
                                      ctx=XLA, out_dtype=jnp.float32))
    camp = fj.FaultCampaign(seed=0, rate=1.0, kinds=("launch",),
                            ops=("conv2d_dist",), max_faults=1)
    with fj.activate(camp):
        got = np.asarray(ops.conv2d_dist(x, w, stride=(1, 1), blocking=pb,
                                         ctx=PALLAS, out_dtype=jnp.float32))
    camp.verify_accounted()
    assert camp.summary()["resolutions"] == {"degraded": 1}
    (key,) = ops.quarantined()
    assert key[:2] == ("conv2d_dist", "pallas")  # xla leg served the call
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# VRF014: no bare RuntimeError in runtime layers
# ---------------------------------------------------------------------------

def _lint_snippet(tmp_path, rel_parts, src):
    from repro.verify.lint import lint_file
    p = tmp_path.joinpath(*rel_parts)
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(src)
    return [v.code for v in lint_file(p, tmp_path)]


def test_vrf014_flags_bare_runtime_error(tmp_path):
    codes = _lint_snippet(
        tmp_path, ("src", "repro", "serving", "x.py"),
        "def f():\n    raise RuntimeError('boom')\n")
    assert codes == ["VRF014"]


def test_vrf014_allows_taxonomy_and_other_scopes(tmp_path):
    # taxonomy raises and re-raises are fine in runtime scope
    assert _lint_snippet(
        tmp_path, ("src", "repro", "serving", "x.py"),
        "from repro.resilience import errors as flt\n"
        "def f():\n"
        "    try:\n"
        "        raise flt.DeviceLost('gone')\n"
        "    except flt.Fault:\n"
        "        raise\n") == []
    # bare RuntimeError outside the runtime layers is not VRF014's business
    assert _lint_snippet(
        tmp_path, ("src", "repro", "models", "x.py"),
        "def f():\n    raise RuntimeError('boom')\n") == []


def test_runtime_tree_is_vrf014_clean():
    from pathlib import Path

    from repro.verify.lint import lint_sources
    root = Path(__file__).resolve().parents[1]
    found = [v for v in lint_sources([root / "src" / "repro"], root)
             if v.code == "VRF014"]
    assert found == []
