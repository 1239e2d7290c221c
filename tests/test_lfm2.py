"""LFM2's mechanisms at a tiny size on the CPU: the gated short conv served
from its state, the dropless expert layer and its shares, the grouped
matmul kernel, and the paged engine with per-slot conv state.

Every comparison runs in float32 unless it says otherwise, so its
tolerance covers only summation order (differences measured here are
below 1e-6 of the values compared)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import ops
from repro.configs.lfm2_8b_a1b import CONFIG, SMOKE
from repro.kernels.grouped_matmul import ROW_TILE, grouped_matmul
from repro.models import moe as moe_lib
from repro.models import transformer as T
from repro.models.layers import init_short_conv, short_conv_block
from repro.serving import kv
from repro.serving.engine import Engine, Request

F32 = dataclasses.replace(SMOKE, param_dtype="float32",
                          compute_dtype="float32")
KEY = jax.random.PRNGKey(7)
TOL = dict(rtol=1e-5, atol=1e-5)  # f32 both sides: summation order only


def test_config_is_the_published_model():
    assert CONFIG.layer_types.count("attn") == 6
    assert [i for i, t in enumerate(CONFIG.layer_types) if t == "attn"] \
        == [2, 6, 10, 14, 18, 21]
    # 8.34 B with the head tied (param_count counts it untied), 1.5 B active
    tied = CONFIG.param_count() - CONFIG.vocab_size * CONFIG.d_model
    assert 8.30e9 < tied < 8.38e9
    assert 1.45e9 < CONFIG.active_param_count() - CONFIG.vocab_size \
        * CONFIG.d_model < 1.6e9
    assert dataclasses.replace(CONFIG, expert_shards=4,
                               expert_shard=1).experts_held == (8, 8)


# ---------------------------------------------------------------------------
# Gated short conv: decode from state == the full causal conv
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,bucket", [(5, 8), (8, 8), (3, 16), (1, 4)])
def test_short_conv_from_state_matches_full_conv(n, bucket):
    """Prefill the first ``n`` tokens right-padded to ``bucket`` (the state
    read at the true length), then decode the rest one at a time from the
    state: every output equals the conv over the whole sequence."""
    p = init_short_conv(KEY, F32)
    L, D = 12, F32.d_model
    x = jax.random.normal(jax.random.PRNGKey(1), (2, L, D))
    full, _ = short_conv_block(p, x, F32)
    state0 = jnp.zeros((2, F32.conv_kernel - 1, D))  # a sequence's start
    xp = jnp.pad(x[:, :n], ((0, 0), (0, bucket - n), (0, 0)))
    mask = jnp.arange(bucket)[None, :] < n
    mask = jnp.broadcast_to(mask, (2, bucket))
    pre, state = short_conv_block(p, xp, F32, state=state0, attn_mask=mask)
    np.testing.assert_allclose(pre[:, :n], full[:, :n], **TOL)
    for t in range(n, L):
        out, state = short_conv_block(p, x[:, t:t + 1], F32, state=state)
        np.testing.assert_allclose(out[:, 0], full[:, t], **TOL)


# ---------------------------------------------------------------------------
# Dropless experts
# ---------------------------------------------------------------------------

def _moe_cfg(shards=1, shard=0):
    return dataclasses.replace(F32, expert_shards=shards, expert_shard=shard)


def test_dropless_layer_matches_dense_oracle():
    cfg = _moe_cfg()
    p = moe_lib.init_moe(KEY, cfg)
    p["expert_bias"] = 0.3 * jax.random.normal(jax.random.PRNGKey(2),
                                               (cfg.n_experts,))
    x = jax.random.normal(jax.random.PRNGKey(3), (3, 7, cfg.d_model))
    out, counts = moe_lib.moe_dropless_block(p, x, cfg)
    np.testing.assert_allclose(out, moe_lib.moe_block_dense_ref(p, x, cfg),
                               **TOL)
    routed, held, rows, groups = (int(c) for c in counts)
    assert routed == held == 21 * cfg.experts_per_token
    assert rows % ROW_TILE == 0 and rows >= held and groups <= cfg.n_experts


def test_expert_shares_sum_to_the_uncut_layer():
    """Four chips' shares of an expert layer (each told which experts it
    holds, routing over all of them) add up to the whole layer."""
    whole_cfg = _moe_cfg()
    p = moe_lib.init_moe(KEY, whole_cfg)
    p["expert_bias"] = 0.3 * jax.random.normal(jax.random.PRNGKey(2),
                                               (whole_cfg.n_experts,))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 9, whole_cfg.d_model))
    whole, _ = moe_lib.moe_dropless_block(p, x, whole_cfg)
    parts, held = 0.0, 0
    for s in range(4):
        cfg = _moe_cfg(4, s)
        lo, n = cfg.experts_held
        share = dict(p, **{k: p[k][lo:lo + n]
                           for k in ("w_gate", "w_up", "w_down")})
        out, counts = moe_lib.moe_dropless_block(share, x, cfg)
        parts = parts + out
        held += int(counts[1])
    np.testing.assert_allclose(parts, whole, **TOL)
    assert held == 18 * whole_cfg.experts_per_token


def test_masked_tokens_route_nowhere():
    cfg = _moe_cfg()
    p = moe_lib.init_moe(KEY, cfg)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 4, cfg.d_model))
    mask = jnp.array([[True, True, False, False], [True, False, False, False]])
    out, counts = moe_lib.moe_dropless_block(p, x, cfg, token_mask=mask)
    want = moe_lib.moe_block_dense_ref(p, x, cfg) * mask[..., None]
    np.testing.assert_allclose(out, want, **TOL)
    assert int(counts[0]) == 3 * cfg.experts_per_token


# ---------------------------------------------------------------------------
# Grouped matmul: Pallas (interpret) == ragged_dot == per-expert einsum
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sizes", [
    (0, 32, 0, 64),        # empty experts
    (128, 0, 0, 0),        # one expert holds every row
    (32, 96, 64, 0),       # uneven tile counts
    (0, 0, 0, 0),          # nothing held: no tile runs
], ids=["empty", "one_expert", "uneven", "none"])
def test_grouped_matmul_matches_ragged_dot_and_einsum(sizes):
    G, K, N, M = len(sizes), 64, 256, 224  # rows past the groups are slack
    kx, kw = jax.random.split(jax.random.PRNGKey(6))
    x = jax.random.normal(kx, (M, K), jnp.float32)
    w = jax.random.normal(kw, (G, K, N), jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)
    used = sum(sizes)
    got = grouped_matmul(x, w, gs)[:used]
    rag = jax.lax.ragged_dot(x, w, gs, preferred_element_type=jnp.float32)
    group = np.repeat(np.arange(G), sizes)
    ein = jnp.einsum("mk,mkn->mn", x[:used], w[group])
    np.testing.assert_allclose(got, rag[:used], **TOL)
    np.testing.assert_allclose(got, ein, **TOL)


def test_grouped_matmul_dispatch_is_audited():
    """The pallas entry's words and access plan agree (``verify.audit``),
    and the xla entry serves the same call."""
    x = jax.ShapeDtypeStruct((1280, 2048), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((8, 2048, 1792), jnp.bfloat16)
    gs = jax.ShapeDtypeStruct((8,), jnp.int32)
    from repro.plan import TPU_V5E

    d = ops.explain("grouped_matmul", ops.ExecutionContext(
        target=TPU_V5E, backend="pallas"), spec_args=(x, w, gs), audit=True)
    assert d.chosen == "pallas" and d.audited == d.measured_words
    assert ops.explain("grouped_matmul", ops.ExecutionContext(
        backend="xla")).chosen == "xla"


# ---------------------------------------------------------------------------
# Serving: paged pool + per-slot conv state against the contiguous path
# ---------------------------------------------------------------------------

def _serve(params, prompts, **kw):
    eng = Engine(SMOKE, params, max_len=64, batch_size=2, **kw)
    reqs = [Request(prompt=p, max_new_tokens=6) for p in prompts]
    eng.serve(reqs)
    return eng, [r.out_tokens.tolist() for r in reqs]


def test_paged_engine_with_conv_state_matches_contiguous():
    """Greedy tokens of the paged engine (conv state per slot, bucketed
    prefill, slots reused) equal the contiguous engine's, and the expert
    counters say free rows route nowhere in the paged step."""
    params = T.init_params(KEY, SMOKE)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, SMOKE.vocab_size, n).astype(np.int32)
               for n in (3, 17, 9, 33, 5)]
    paged, a = _serve(params, prompts)
    contiguous, b = _serve(params, prompts, paged=False)
    assert paged.paged and paged.prefill_bucket == 16
    assert not contiguous.paged
    assert a == b
    assert paged.stats.moe_routed["prefill"] \
        == contiguous.stats.moe_routed["prefill"]
    assert paged.stats.moe_routed["decode"] \
        < contiguous.stats.moe_routed["decode"]


def test_shared_prefix_blocks_keep_conv_state_exact():
    """Two requests sharing two full prompt blocks share them in the pool;
    prefill still runs each whole prompt, so each slot's conv state is its
    own and the tokens equal those served one request at a time."""
    params = T.init_params(KEY, SMOKE)
    rng = np.random.default_rng(1)
    prefix = rng.integers(0, SMOKE.vocab_size, 32).astype(np.int32)
    prompts = [np.concatenate([prefix, rng.integers(
        0, SMOKE.vocab_size, n).astype(np.int32)]) for n in (3, 7)]
    _, together = _serve(params, prompts)
    alone = [_serve(params, [p])[1][0] for p in prompts]
    assert together == alone


def test_pool_head_dim_pads_to_lanes_for_the_kernel():
    """Only an entry that copies whole pages (the Pallas decode kernel,
    ``OpEntry.lane_pages``) widens the pool; the int8 pool is read by the
    xla entry alone."""
    from repro.plan import CPU_INTERPRET, TPU_V5E

    pallas = ops.ExecutionContext(target=TPU_V5E, backend="pallas")
    xla = ops.ExecutionContext(target=TPU_V5E, backend="xla")
    assert ops.pool_head_dim("attention_decode", 64, pallas) == 128
    assert ops.pool_head_dim("attention_decode", 128, pallas) == 128
    assert ops.pool_head_dim("attention_decode_quant", 64, pallas) == 64
    assert ops.pool_head_dim("attention_decode", 64, xla) == 64
    # a target without lanes (the CPU interpreter) pads nothing
    assert ops.pool_head_dim("attention_decode", 64, ops.ExecutionContext(
        target=CPU_INTERPRET, backend="pallas")) == 64
    pool = jax.eval_shape(lambda: T.init_paged_cache(
        SMOKE, 9, 16, batch=3, head_dim=128))
    assert pool["b2"]["kp"].shape == (1, 9, SMOKE.n_kv_heads, 16, 128)
    assert pool["b0"]["conv"].shape == (1, 3, SMOKE.conv_kernel - 1,
                                        SMOKE.d_model)


@pytest.mark.parametrize("backend", ["pallas", "xla"])
def test_decode_on_a_lane_padded_pool_matches_the_unpadded_pool(backend):
    """A pool whose heads are zero-padded past q's (as the Pallas entry
    needs on a TPU) gives what the unpadded pool gives, on either entry:
    same scores' scale, result at q's head dim, the new rows written at
    the model's head dim with zeros past it."""
    B, H, KV, hd, hp, bs, nb, w = 3, 4, 2, 16, 128, 16, 7, 3
    ks = jax.random.split(KEY, 5)
    q = jax.random.normal(ks[0], (B, H, 1, hd))
    kp, vp = (jax.random.normal(k, (2, nb, KV, bs, hd)) for k in ks[1:3])
    k_new, v_new = (jax.random.normal(k, (B, KV, hd)) for k in ks[3:])
    tables = jnp.asarray([[1, 2, 3], [4, 5, 0], [6, 0, 0]], jnp.int32)
    lengths = jnp.asarray([40, 17, 1], jnp.int32)
    step = (tables, lengths, jnp.int32(1), k_new, v_new)
    want = ops.attention_decode(q, kp, vp, *step,
                                ctx=ops.ExecutionContext(backend="xla"))
    pad = ((0, 0),) * 4 + ((0, hp - hd),)
    got = ops.attention_decode(q, jnp.pad(kp, pad), jnp.pad(vp, pad), *step,
                               ctx=ops.ExecutionContext(backend=backend))
    assert got[0].shape == (B, H, 1, hd)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]), **TOL)
    for g, p in zip(got[1:], want[1:]):
        np.testing.assert_array_equal(np.asarray(g[..., :hd]), np.asarray(p))
        assert not np.asarray(g[..., hd:]).any()


def test_one_short_request_compiles_every_program_serving_needs():
    """After one request of two tokens per prefill bucket, a batch of longer
    requests (several prefills and decode steps, the expert counters summed
    over them) compiles no program: what a benchmark's warm-up relies on.
    Backend compiles are counted; an eager op may retrace from JAX's cache
    of traces in a long test process, which costs microseconds."""
    jax.clear_caches()  # what other tests compiled does not count
    params = T.init_params(KEY, SMOKE)
    eng = Engine(SMOKE, params, max_len=64, batch_size=2)
    rng = np.random.default_rng(3)
    for n in (3, 20):  # prefill buckets 16 and 32
        eng.serve([Request(prompt=rng.integers(
            0, SMOKE.vocab_size, n).astype(np.int32), max_new_tokens=2)])
    events = []

    def on(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            events.append(event)

    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        eng.serve([Request(prompt=rng.integers(
            0, SMOKE.vocab_size, n).astype(np.int32), max_new_tokens=7)
            for n in (5, 12, 30)])
    finally:
        jax.monitoring.unregister_event_duration_listener(on)
    assert events == []
    assert eng.stats.moe_held["prefill"] > 0
