"""The repro.ops dispatch subsystem: backend agreement for every registered
op, capability fallback (observable via explain/record_dispatch), environment
resolution, precision policy, and the deprecation shim."""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import ops
from repro.kernels import ref
from repro.plan import CPU_INTERPRET, GEMMINI, MatmulSpec, Planner, TPU_V5E
from repro.models import layers
from repro.models.config import ModelConfig

KEY = jax.random.PRNGKey(0)
K2 = jax.random.PRNGKey(1)
K3 = jax.random.PRNGKey(2)

XLA = ops.ExecutionContext(target=TPU_V5E, backend="xla")
PALLAS = ops.ExecutionContext(target=TPU_V5E, backend="pallas")


# ---------------------------------------------------------------------------
# One parametrized sweep: every non-xla backend agrees with the xla oracle
# for EVERY registered op it implements (replaces the per-kernel agreement
# tests; partial backends like im2col are swept only on their own entries —
# the rest would just re-test xla through the fallback chain).
# ---------------------------------------------------------------------------

def _quantize_pool(pool):
    """Per-(block, head, position) int8 quantization of a KV pool leaf —
    the layout the engine's quantizing insert writes."""
    from repro.quant import quantize_symmetric

    flat = pool.reshape(-1, pool.shape[-1])
    q, s = quantize_symmetric(flat, axis=0)
    return (q.reshape(pool.shape), s.reshape(pool.shape[:-1]))


def _op_case(op: str):
    """Canonical inputs + call kwargs for one registered op."""
    if op == "matmul":
        return (jax.random.normal(KEY, (64, 96)),
                jax.random.normal(K2, (96, 128))), {}
    if op == "conv2d":
        return (jax.random.normal(KEY, (2, 8, 12, 12)),
                jax.random.normal(K2, (16, 8, 3, 3))), {"stride": (1, 1)}
    if op == "conv1d_causal":
        return (jax.random.normal(KEY, (2, 33, 130)),
                jax.random.normal(K2, (4, 130))), {}
    if op == "attention":  # GQA shape: exercises the repeat-free group fold
        return (jax.random.normal(KEY, (2, 8, 33, 16)) * 0.3,
                jax.random.normal(K2, (2, 2, 33, 16)) * 0.3,
                jax.random.normal(K3, (2, 2, 33, 16))), {"causal": True}
    if op == "attention_decode":  # paged decode: block-table gather +
        # lengths, the step's rows written into layer 1 of a 2-layer stack
        return (jax.random.normal(KEY, (2, 4, 1, 16)) * 0.3,
                jax.random.normal(K2, (2, 7, 2, 16, 16)) * 0.3,
                jax.random.normal(K3, (2, 7, 2, 16, 16)),
                jnp.asarray([[1, 3, 0], [4, 2, 6]], jnp.int32),
                jnp.asarray([20, 45], jnp.int32), jnp.int32(1),
                jax.random.normal(KEY, (2, 2, 16)),
                jax.random.normal(K2, (2, 2, 16))), {}
    if op == "grouped_matmul":  # rows sorted by group, every row placed
        return (jax.random.normal(KEY, (160, 64)),
                jax.random.normal(K2, (4, 64, 128)),
                jnp.asarray([32, 0, 64, 64], jnp.int32)), {}
    if op == "matmul_q":  # int8 streams + folded per-column scale
        from repro.quant import quantize_matmul_operands

        a = jax.random.normal(KEY, (64, 96))
        b = jax.random.normal(K2, (96, 128))
        return quantize_matmul_operands(a, b), {}
    if op == "conv2d_q":
        from repro.quant import quantize_conv_operands

        x = jax.random.normal(KEY, (2, 8, 12, 12))
        w = jax.random.normal(K2, (16, 8, 3, 3))
        return quantize_conv_operands(x, w), {"stride": (1, 1)}
    if op == "attention_decode_quant":  # int8 pools + per-position scales
        kp, ks = _quantize_pool(jax.random.normal(K2, (2, 7, 2, 16, 16)) * 0.3)
        vp, vs = _quantize_pool(jax.random.normal(K3, (2, 7, 2, 16, 16)))
        return (jax.random.normal(KEY, (2, 4, 1, 16)) * 0.3, kp, ks, vp, vs,
                jnp.asarray([[1, 3, 0], [4, 2, 6]], jnp.int32),
                jnp.asarray([20, 45], jnp.int32), jnp.int32(1),
                jax.random.normal(KEY, (2, 2, 16)),
                jax.random.normal(K2, (2, 2, 16))), {}
    if op == "conv2d_dist":  # P=1 grid: the mesh is one device, so the
        # sweep runs on any host; the real multi-device grids live in
        # tests/test_distributed.py under the CI distributed job
        from repro.core.conv_model import ConvShape
        from repro.core.parallel_tiling import ParallelBlocking

        shape = ConvShape(N=2, c_I=8, c_O=16, h_O=10, w_O=10, h_F=3, w_F=3)
        return (jax.random.normal(KEY, (2, 8, 12, 12)),
                jax.random.normal(K2, (16, 8, 3, 3))), {
                    "stride": (1, 1),
                    "blocking": ParallelBlocking.from_grid(shape, {})}
    raise NotImplementedError(
        f"op {op!r} is registered but has no agreement-sweep case; add one")


@pytest.mark.parametrize("backend", [b for b in ops.backends() if b != "xla"])
@pytest.mark.parametrize("op", ops.registered_ops())
def test_backends_agree(op, backend):
    if op not in ops.get_backend(backend).ops:
        pytest.skip(f"{backend} serves {op} through the fallback chain")
    args, kw = _op_case(op)
    fn = getattr(ops, op)
    ctx = ops.ExecutionContext(target=TPU_V5E, backend=backend)
    got_x = jax.tree.leaves(fn(*args, ctx=XLA, **kw))
    got_b = jax.tree.leaves(fn(*args, ctx=ctx, **kw))
    assert len(got_x) == len(got_b)
    for x, b in zip(got_x, got_b):  # decode returns its updated pools too
        np.testing.assert_allclose(np.asarray(x), np.asarray(b), rtol=2e-3,
                                   atol=2e-3,
                                   err_msg=f"xla and {backend} disagree on "
                                           f"{op}")


def test_every_registered_op_is_swept():
    assert set(ops.backends()) == {"xla", "pallas", "im2col"}
    assert set(ops.registered_ops()) == {
        "matmul", "conv2d", "conv1d_causal", "attention", "attention_decode",
        "attention_decode_quant", "conv2d_q", "matmul_q", "conv2d_dist",
        "grouped_matmul"}
    for op in ops.registered_ops():
        _op_case(op)  # raises if an op was registered without a sweep case


# ---------------------------------------------------------------------------
# GQA group folding (the jnp.repeat replacement)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("H,Hkv,Lq,Lk,causal", [
    (8, 2, 33, 33, True), (4, 1, 17, 17, True), (8, 8, 16, 16, True),
    (6, 3, 20, 20, False),
])
def test_pallas_gqa_grouping_matches_oracle(H, Hkv, Lq, Lk, causal):
    q = jax.random.normal(KEY, (2, H, Lq, 16)) * 0.3
    k = jax.random.normal(K2, (2, Hkv, Lk, 16)) * 0.3
    v = jax.random.normal(K3, (2, Hkv, Lk, 16))
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    got = ops.attention(q, k, v, causal=causal, ctx=PALLAS)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# Capability dispatch: decode offsets are pallas-native since the paged-KV
# PR (scalar-prefetched into the kernel); only key masks still fall back.
# ---------------------------------------------------------------------------

def test_explain_decode_offsets_stay_on_pallas():
    # static prefill call: pallas serves it
    assert ops.explain("attention", PALLAS).chosen == "pallas"
    # in-cache decode: the traced q_offset is scalar-prefetched -> no fallback
    needs = ops.attention_needs(q_offset=jnp.asarray(5, jnp.int32))
    dec = ops.explain("attention", PALLAS, needs=needs)
    assert dec.requested == "pallas" and dec.chosen == "pallas"
    assert not dec.missing and not dec.fell_back
    # continuous-batching decode: per-row offsets are served natively too
    needs = ops.attention_needs(q_offset=jnp.arange(4))
    assert ops.explain("attention", PALLAS, needs=needs).chosen == "pallas"
    # padded prefill: key mask still falls back to masked XLA by capability
    dec = ops.explain("attention", PALLAS, needs=("key_mask",))
    assert dec.chosen == "xla" and "key_mask" in dec.missing and dec.fell_back
    assert "xla" in dec.why()


def _tiny_cfg():
    return ModelConfig(name="t", family="dense", n_layers=1, d_model=32,
                       n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=64,
                       param_dtype="float32", compute_dtype="float32")


def test_in_cache_decode_stays_on_pallas_end_to_end():
    """The PR-6 acceptance check: the in-cache decode path dispatches to
    pallas with NO capability fallback (PR 3 sent it to masked XLA), and the
    two backends agree numerically; REPRO_BACKEND=xla still selects the old
    masked-XLA path as the *requested* backend, not a fallback."""
    cfg = _tiny_cfg()
    p = layers.init_attention(KEY, cfg)
    x = jax.random.normal(K2, (2, 1, cfg.d_model))
    kv = (jax.random.normal(K3, (2, 2, 16, cfg.hd)) * 0.3,
          jax.random.normal(KEY, (2, 2, 16, cfg.hd)))
    with ops.record_dispatch() as log:
        out_p, _ = layers.attention_block(p, x, cfg,
                                          positions=jnp.asarray([3]),
                                          cache=kv, cache_index=jnp.asarray(3),
                                          ctx=PALLAS)
    att = [d for d in log if d.op == "attention"]
    assert att and att[-1].requested == "pallas"
    assert att[-1].chosen == "pallas" and not att[-1].fell_back
    with ops.record_dispatch() as log:
        out_x, _ = layers.attention_block(p, x, cfg,
                                          positions=jnp.asarray([3]),
                                          cache=kv, cache_index=jnp.asarray(3),
                                          ctx=XLA)
    att = [d for d in log if d.op == "attention"]
    assert att and att[-1].chosen == "xla" and not att[-1].fell_back
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_x),
                               rtol=2e-3, atol=2e-3)
    # ...and the no-cache prefill path stays on pallas as before
    with ops.record_dispatch() as log:
        layers.attention_block(p, x, cfg, positions=jnp.asarray([0]),
                               ctx=PALLAS)
    att = [d for d in log if d.op == "attention"]
    assert att and att[-1].chosen == "pallas" and not att[-1].fell_back


def test_paged_decode_explain_no_fallback_and_bound(monkeypatch):
    """Pooled decode dispatch is shape-only explainable: pallas chosen with
    no fallback, measured decode words reported against the Lq=1 attention
    bound; forcing REPRO_BACKEND=xla picks xla as requested (no fallback)."""
    spec = (jax.ShapeDtypeStruct((2, 4, 1, 16), jnp.bfloat16),
            jax.ShapeDtypeStruct((3, 7, 2, 16, 16), jnp.bfloat16),
            jax.ShapeDtypeStruct((3, 7, 2, 16, 16), jnp.bfloat16),
            jax.ShapeDtypeStruct((2, 3), jnp.int32),
            jax.ShapeDtypeStruct((2,), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((2, 2, 16), jnp.bfloat16),
            jax.ShapeDtypeStruct((2, 2, 16), jnp.bfloat16))
    dec = ops.explain("attention_decode", PALLAS, spec_args=spec)
    assert dec.chosen == "pallas" and not dec.fell_back
    assert dec.measured_words is not None and dec.plan is not None
    assert dec.bound_ratio == pytest.approx(
        dec.measured_words / dec.plan.lower_bound, rel=1e-6)
    assert "HBM words" in dec.why()
    monkeypatch.setenv(ops.BACKEND_ENV, "xla")
    dec = ops.explain("attention_decode", ops.ExecutionContext(target=TPU_V5E),
                      spec_args=spec)
    assert dec.requested == "xla" and dec.chosen == "xla"
    assert not dec.fell_back


def test_pallas_backend_is_differentiable():
    """pallas_call has no JVP rule, so the pallas entries wrap the kernel in
    custom_vjp with an XLA-recompute backward: gradients match the pure-XLA
    path even through lax.scan (where call-time fallback could never work
    because scan differentiates its traced jaxpr, not the python)."""
    a = jax.random.normal(KEY, (16, 24))
    b = jax.random.normal(K2, (24, 8))

    def loss(ctx):
        def f(a_):
            out = ops.matmul(a_, b, ctx=ctx)
            s, _ = jax.lax.scan(lambda c, _: (c + ops.matmul(
                a_, b, ctx=ctx).sum(), None), 0.0, None, length=2)
            return out.sum() + s
        return jax.grad(f)(a)

    g_p = loss(PALLAS)
    g_x = loss(XLA)
    np.testing.assert_allclose(np.asarray(g_p), np.asarray(g_x),
                               rtol=1e-4, atol=1e-4)

    q = jax.random.normal(KEY, (1, 4, 16, 8)) * 0.3
    k = jax.random.normal(K2, (1, 2, 16, 8)) * 0.3
    v = jax.random.normal(K3, (1, 2, 16, 8))
    ga = jax.grad(lambda q_: ops.attention(q_, k, v, ctx=PALLAS).sum())(q)
    gx = jax.grad(lambda q_: ops.attention(q_, k, v, ctx=XLA).sum())(q)
    np.testing.assert_allclose(np.asarray(ga), np.asarray(gx),
                               rtol=1e-3, atol=1e-3)


def test_dispatch_resolves_execution_plan():
    a = jax.random.normal(KEY, (128, 64))
    b = jax.random.normal(K2, (64, 256))
    dec = ops.explain("matmul", PALLAS, spec_args=(a, b))
    assert dec.plan is not None
    want = Planner(TPU_V5E).plan(MatmulSpec(128, 256, 64,
                                            prec=dec.plan.op.prec))
    assert dec.plan is want  # same memoized object: one process-wide cache
    # xla delegates tiling to the compiler: no LP plan resolved
    assert ops.explain("matmul", XLA, spec_args=(a, b)).plan is None


# ---------------------------------------------------------------------------
# Measured HBM-word counters: every instrumented dispatch reports words
# moved next to the paper's lower bound.
# ---------------------------------------------------------------------------

def test_explain_reports_measured_words_vs_bound():
    xs = jax.ShapeDtypeStruct((8, 64, 30, 30), jnp.bfloat16)
    ws = jax.ShapeDtypeStruct((64, 64, 3, 3), jnp.bfloat16)
    kw = {"spec_args": (xs, ws), "spec_kw": {"stride": (1, 1)}}
    tiled = ops.explain("conv2d", PALLAS, **kw)
    im2col = ops.explain("conv2d", ops.ExecutionContext(
        target=TPU_V5E, backend="im2col"), **kw)
    for dec in (tiled, im2col):
        assert dec.measured_words is not None and dec.plan is not None
        assert dec.bound_ratio == pytest.approx(
            dec.measured_words / dec.plan.lower_bound, rel=1e-6)
        assert "HBM words" in dec.why() and "lower bound" in dec.why()
    # both entries report against the identical conv plan/lower bound,
    # and the LP tiling moves fewer words than the im2col baseline
    assert tiled.plan is im2col.plan
    assert tiled.measured_words < im2col.measured_words
    # xla is not instrumented (the compiler owns its data movement)
    assert ops.explain("conv2d", XLA, **kw).measured_words is None


def test_record_dispatch_captures_measured_words():
    a = jax.random.normal(KEY, (64, 32))
    b = jax.random.normal(K2, (32, 48))
    with ops.record_dispatch() as log:
        ops.matmul(a, b, ctx=PALLAS)
    mm = [d for d in log if d.op == "matmul"]
    assert mm and mm[-1].measured_words is not None
    assert mm[-1].measured_words >= mm[-1].plan.lower_bound * 0.5


# ---------------------------------------------------------------------------
# ExecutionContext: resolution order, env vars, precision policy
# ---------------------------------------------------------------------------

def test_backend_resolution_order(monkeypatch):
    monkeypatch.delenv(ops.BACKEND_ENV, raising=False)
    # target default
    assert ops.ExecutionContext(target=TPU_V5E).resolved_backend() == "pallas"
    assert ops.ExecutionContext(target=CPU_INTERPRET).resolved_backend() == "xla"
    # env overrides target
    monkeypatch.setenv(ops.BACKEND_ENV, "xla")
    assert ops.ExecutionContext(target=TPU_V5E).resolved_backend() == "xla"
    # explicit override beats env
    assert ops.ExecutionContext(target=TPU_V5E,
                                backend="pallas").resolved_backend() == "pallas"
    assert ops.default_context().resolved_backend() == "xla"
    monkeypatch.setenv(ops.BACKEND_ENV, "nope")
    with pytest.raises(ValueError):
        ops.ExecutionContext().resolved_backend()


def test_legacy_env_var_retired(monkeypatch):
    # the PR-3 REPRO_USE_PALLAS shim is gone: the name is no longer exported
    # and setting the variable changes nothing
    monkeypatch.delenv(ops.BACKEND_ENV, raising=False)
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    assert not hasattr(ops, "LEGACY_BACKEND_ENV")
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert ops.env_backend() is None


def test_resolved_pins_backend(monkeypatch):
    monkeypatch.setenv(ops.BACKEND_ENV, "pallas")
    pinned = ops.ExecutionContext(target=CPU_INTERPRET).resolved()
    monkeypatch.delenv(ops.BACKEND_ENV)
    assert pinned.backend == "pallas"  # env read once, cache-key safe


def test_precision_policy_dtypes():
    assert ops.ExecutionContext(target=TPU_V5E).stream_dtype == jnp.bfloat16
    assert ops.ExecutionContext(target=TPU_V5E).acc_dtype == jnp.float32
    assert ops.ExecutionContext(target=GEMMINI).stream_dtype == jnp.int8
    assert ops.ExecutionContext(target=CPU_INTERPRET).stream_dtype == jnp.float32
    # out dtype of a dispatched op defaults to the policy's accumulator
    a = jax.random.normal(KEY, (8, 8), jnp.bfloat16)
    assert ops.matmul(a, a, ctx=XLA).dtype == jnp.float32


# ---------------------------------------------------------------------------
# The use_pallas= shim (kernels/ops.py) is gone: ExecutionContext is the one
# way to pick a backend.
# ---------------------------------------------------------------------------

def test_use_pallas_shim_removed():
    import repro.kernels as kernels

    with pytest.raises(ImportError):
        from repro.kernels import ops as _legacy  # noqa: F401
    assert not hasattr(kernels, "ops")
