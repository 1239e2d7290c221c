"""Serving engine tests: slot-based continuous batching, batch invariance
(greedy and sampled), EOS / cache-limit accounting, seeded reproducibility,
wave-baseline parity, recurrent-arch decode, plan-aware batch sizing, and
the paged KV-cache mode (block tables, prefix sharing, backpressure)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import ops
from repro.configs import get_smoke
from repro.models import transformer as T
from repro.plan import CPU_INTERPRET
from repro.serving.engine import Engine, Request, WaveEngine, plan_batch_size

KEY = jax.random.PRNGKey(0)


def _params_and_cfg(arch):
    cfg = dataclasses.replace(get_smoke(arch), compute_dtype="float32")
    return T.init_params(KEY, cfg), cfg


P1 = np.array([3, 1, 4, 1, 5], np.int32)
P2 = np.array([7], np.int32)
P3 = np.array([2, 7, 1], np.int32)


def test_greedy_matches_manual_decode():
    params, cfg = _params_and_cfg("stablelm_1_6b")
    eng = Engine(cfg, params, max_len=32, batch_size=1)
    req = Request(prompt=P1, max_new_tokens=6)
    eng.serve([req])

    # manual greedy via repeated full forwards (no cache)
    toks = list(P1)
    for _ in range(6):
        lg, _, _ = T.forward(params, cfg,
                             tokens=jnp.asarray([toks], jnp.int32))
        toks.append(int(jnp.argmax(lg[0, -1])))
    np.testing.assert_array_equal(req.out_tokens, np.array(toks[len(P1):]))
    assert req.finish_reason == "length"


def test_queue_longer_than_pool_processes_all_requests():
    params, cfg = _params_and_cfg("stablelm_1_6b")
    rng = np.random.default_rng(0)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab_size, size=rng.integers(2, 8)).astype(np.int32),
                    max_new_tokens=4) for _ in range(5)]
    eng = Engine(cfg, params, max_len=32, batch_size=2)  # 5 requests, 2 slots
    eng.serve(reqs)
    for r in reqs:
        assert r.out_tokens is not None and len(r.out_tokens) == 4
        assert r.out_tokens.min() >= 0
        assert r.finish_reason == "length"


@pytest.mark.parametrize("arch", ["xlstm_1_3b", "jamba_1_5_large"])
def test_recurrent_arch_serving(arch):
    """SSM/hybrid archs decode through recurrent state, not a KV window;
    exact-length prefill-into-slot keeps them batch-invariant too."""
    params, cfg = _params_and_cfg(arch)
    solo = Request(prompt=np.array([1, 2, 3], np.int32), max_new_tokens=4)
    Engine(cfg, params, max_len=32, batch_size=1).serve([solo])
    reqs = [Request(prompt=np.array([1, 2, 3], np.int32), max_new_tokens=4),
            Request(prompt=np.array([9, 8], np.int32), max_new_tokens=4)]
    Engine(cfg, params, max_len=32, batch_size=2).serve(reqs)
    for r in reqs:
        assert len(r.out_tokens) == 4
    np.testing.assert_array_equal(solo.out_tokens, reqs[0].out_tokens)


def test_batch_invariance_greedy_mixed_lengths():
    """Regression for the left-pad wave bug: a short prompt decoded in a
    mixed-length batch must match the same prompt decoded alone."""
    params, cfg = _params_and_cfg("stablelm_1_6b")
    solo_long = Request(prompt=P1, max_new_tokens=4)
    solo_short = Request(prompt=P2, max_new_tokens=4)
    Engine(cfg, params, max_len=32, batch_size=1).serve([solo_long])
    Engine(cfg, params, max_len=32, batch_size=1).serve([solo_short])

    for order in ([P1, P2], [P2, P1]):
        pair = [Request(prompt=p, max_new_tokens=4) for p in order]
        Engine(cfg, params, max_len=32, batch_size=2).serve(pair)
        by_len = {len(r.prompt): r for r in pair}
        np.testing.assert_array_equal(solo_long.out_tokens,
                                      by_len[len(P1)].out_tokens)
        np.testing.assert_array_equal(solo_short.out_tokens,
                                      by_len[len(P2)].out_tokens)


def test_batch_invariance_sampled():
    """A sampled request with a pinned rng_seed produces identical tokens
    alone and in any batch composition (per-request sampling streams)."""
    params, cfg = _params_and_cfg("stablelm_1_6b")
    mk = lambda: Request(prompt=P1, max_new_tokens=5, temperature=0.9,
                         rng_seed=42)
    solo = mk()
    Engine(cfg, params, max_len=32, batch_size=1, seed=7).serve([solo])
    batched = [Request(prompt=P2, max_new_tokens=3),
               mk(),
               Request(prompt=P3, max_new_tokens=8, temperature=1.3)]
    Engine(cfg, params, max_len=32, batch_size=3, seed=7).serve(batched)
    np.testing.assert_array_equal(solo.out_tokens, batched[1].out_tokens)


def test_greedy_unaffected_by_sampling_batchmate():
    """A greedy request batched with a temperature>0 request must still
    produce its deterministic greedy output."""
    params, cfg = _params_and_cfg("stablelm_1_6b")
    solo = Request(prompt=P1, max_new_tokens=5, temperature=0.0)
    Engine(cfg, params, max_len=32, batch_size=1).serve([solo])

    mixed = [Request(prompt=P1, max_new_tokens=5, temperature=0.0),
             Request(prompt=P3, max_new_tokens=5, temperature=1.0)]
    Engine(cfg, params, max_len=32, batch_size=2).serve(mixed)
    np.testing.assert_array_equal(solo.out_tokens, mixed[0].out_tokens)


def test_seeded_runs_reproducible_across_batch_compositions():
    """Key consumption depends only on (engine seed, request rng_seed, step)
    — never on which requests share the pool — so a seeded run reproduces
    under a different batch size and queue order."""
    params, cfg = _params_and_cfg("stablelm_1_6b")
    prompts = [P1, P2, P3]

    def serve(batch_size, order):
        reqs = [Request(prompt=prompts[i], max_new_tokens=4, temperature=0.8,
                        rng_seed=i) for i in order]
        Engine(cfg, params, max_len=32, batch_size=batch_size, seed=3).serve(reqs)
        return {r.rng_seed: list(r.out_tokens) for r in reqs}

    a = serve(3, [0, 1, 2])
    b = serve(1, [2, 0, 1])
    assert a == b
    # a different engine seed shifts the sampled streams
    reqs = [Request(prompt=P1, max_new_tokens=4, temperature=0.8, rng_seed=0)]
    Engine(cfg, params, max_len=32, batch_size=1, seed=4).serve(reqs)
    assert list(reqs[0].out_tokens) != a[0]


def test_stop_token_ends_request():
    params, cfg = _params_and_cfg("stablelm_1_6b")
    ref = Request(prompt=P1, max_new_tokens=6)
    Engine(cfg, params, max_len=32, batch_size=1).serve([ref])
    eos = int(ref.out_tokens[2])  # force a stop on the 3rd greedy token

    req = Request(prompt=P1, max_new_tokens=6, stop_tokens=(eos,))
    Engine(cfg, params, max_len=32, batch_size=1).serve([req])
    assert req.finish_reason == "stop"
    np.testing.assert_array_equal(req.out_tokens, ref.out_tokens[:3])


def test_cache_limit_returns_only_real_tokens():
    """Regression for the wave-engine padding bug: when max_len truncates
    decode, out_tokens holds exactly the generated tokens (no zero-pad) and
    they match an untruncated run's prefix."""
    params, cfg = _params_and_cfg("stablelm_1_6b")
    full = Request(prompt=P1, max_new_tokens=12)
    Engine(cfg, params, max_len=32, batch_size=1).serve([full])

    trunc = Request(prompt=P1, max_new_tokens=12)
    Engine(cfg, params, max_len=8, batch_size=1).serve([trunc])
    cap = 8 - len(P1) + 1  # prefill token + writes up to max_len - 1
    assert len(trunc.out_tokens) == cap < 12
    assert trunc.finish_reason == "cache_limit"
    np.testing.assert_array_equal(trunc.out_tokens, full.out_tokens[:cap])


def test_wave_baseline_matches_continuous_outputs():
    """Scheduling must not change tokens: the wave baseline and the slot
    engine agree request-by-request (they differ only in admission timing)."""
    params, cfg = _params_and_cfg("stablelm_1_6b")
    rng = np.random.default_rng(2)
    specs = [(rng.integers(0, cfg.vocab_size, size=n).astype(np.int32), m)
             for n, m in ((5, 6), (2, 2), (3, 4), (6, 3), (1, 5))]
    a = [Request(prompt=p, max_new_tokens=m) for p, m in specs]
    b = [Request(prompt=p, max_new_tokens=m) for p, m in specs]
    Engine(cfg, params, max_len=32, batch_size=2).serve(a)
    WaveEngine(cfg, params, max_len=32, batch_size=2).serve(b)
    for ra, rb in zip(a, b):
        np.testing.assert_array_equal(ra.out_tokens, rb.out_tokens)
        assert ra.finish_reason == rb.finish_reason


def test_prefill_bucket_exactness_and_guard():
    """Masked bucketed prefill (attention archs) must equal exact-length
    prefill token-for-token; recurrent patterns must reject bucketing."""
    params, cfg = _params_and_cfg("stablelm_1_6b")
    exact = Request(prompt=P1, max_new_tokens=5)
    Engine(cfg, params, max_len=32, batch_size=1,
           prefill_bucket=1).serve([exact])
    bucketed = Request(prompt=P1, max_new_tokens=5)
    Engine(cfg, params, max_len=32, batch_size=1,
           prefill_bucket=8).serve([bucketed])
    np.testing.assert_array_equal(exact.out_tokens, bucketed.out_tokens)

    _, hybrid = _params_and_cfg("jamba_1_5_large")
    with pytest.raises(ValueError, match="attention and short-conv"):
        Engine(hybrid, params, max_len=32, batch_size=1, prefill_bucket=8)


def test_prompt_validation():
    params, cfg = _params_and_cfg("stablelm_1_6b")
    eng = Engine(cfg, params, max_len=8, batch_size=1)
    with pytest.raises(ValueError):
        eng.serve([Request(prompt=np.arange(9, dtype=np.int32))])
    with pytest.raises(ValueError):
        eng.serve([Request(prompt=P1, max_new_tokens=0)])
    with pytest.raises(ValueError):
        eng.serve([Request(prompt=P2, rng_seed=2**35)])


def test_plan_batch_size_from_target():
    _, cfg = _params_and_cfg("stablelm_1_6b")
    b = plan_batch_size(cfg, 512, CPU_INTERPRET)
    assert 1 <= b <= 64
    # tighter memory -> fewer slots, never below one
    tiny = dataclasses.replace(CPU_INTERPRET, hbm_words=1e4)
    assert plan_batch_size(cfg, 512, tiny) == 1
    # alignment: pools at/above the sublane multiple are rounded to it
    if b >= CPU_INTERPRET.align_sublane:
        assert b % CPU_INTERPRET.align_sublane == 0


# ---------------------------------------------------------------------------
# Paged KV-cache mode: block tables replace the per-slot contiguous cache.
# ---------------------------------------------------------------------------

def _spec_reqs(rng, n=7, shared_prefix=16):
    """A mixed workload: varied lengths plus two requests sharing a full-
    block prompt prefix, one of them sampled with a pinned seed."""
    reqs = []
    for _ in range(n):
        plen = int(rng.integers(2, 20))
        reqs.append(Request(
            prompt=rng.integers(1, 64, size=plen).astype(np.int32),
            max_new_tokens=int(rng.integers(2, 10))))
    shared = rng.integers(1, 64, size=shared_prefix).astype(np.int32)
    for tail, temp in ((3, 0.0), (5, 0.9)):
        p = np.concatenate(
            [shared, rng.integers(1, 64, size=tail).astype(np.int32)])
        reqs.append(Request(prompt=p, max_new_tokens=6, temperature=temp,
                            rng_seed=11))
    return reqs


def test_paged_matches_contiguous_outputs():
    """The tentpole invariant: switching the KV layout from per-slot
    contiguous to paged blocks changes no tokens — including across shared
    prompt prefixes and a sampled request."""
    params, cfg = _params_and_cfg("stablelm_1_6b")
    a = _spec_reqs(np.random.default_rng(5))
    b = _spec_reqs(np.random.default_rng(5))
    Engine(cfg, params, max_len=64, batch_size=3, paged=False).serve(a)
    eng = Engine(cfg, params, max_len=64, batch_size=3, paged=True)
    assert eng.paged and eng.num_blocks >= 1 + 64 // eng.block_size
    traced = eng._paged_decode._cache_size()
    eng.serve(b)
    # rows of every depth share one decode program: the whole tables
    assert eng._paged_decode._cache_size() <= traced + 1
    for ra, rb in zip(a, b):
        np.testing.assert_array_equal(ra.out_tokens, rb.out_tokens)
        assert ra.finish_reason == rb.finish_reason


def test_paged_is_default_only_for_pure_attention():
    params, cfg = _params_and_cfg("stablelm_1_6b")
    assert Engine(cfg, params, max_len=32, batch_size=1).paged
    hp, hybrid = _params_and_cfg("jamba_1_5_large")
    assert not Engine(hybrid, hp, max_len=32, batch_size=1).paged
    with pytest.raises(ValueError, match="paged KV serves"):
        Engine(hybrid, hp, max_len=32, batch_size=1, paged=True)
    fused = dataclasses.replace(cfg, fused_kv_cache=True)
    with pytest.raises(ValueError, match="fused"):
        Engine(fused, params, max_len=32, batch_size=1, paged=True)


def test_paged_backpressure_completes_all_requests():
    """A pool too small for the full batch admits what fits, re-queues the
    rest, and still produces the exact contiguous-engine outputs."""
    params, cfg = _params_and_cfg("stablelm_1_6b")
    mk = lambda: [Request(prompt=np.full(20, i + 1, np.int32),
                          max_new_tokens=25) for i in range(5)]
    ref = mk()
    Engine(cfg, params, max_len=64, batch_size=4, paged=False).serve(ref)
    # each request needs ceil((20 + 25 - 1) / 16) = 3 blocks; 7 usable
    # blocks hold at most two concurrent requests of the four slots
    got = mk()
    Engine(cfg, params, max_len=64, batch_size=4, paged=True,
           num_blocks=1 + 7).serve(got)
    for ra, rb in zip(ref, got):
        np.testing.assert_array_equal(ra.out_tokens, rb.out_tokens)
        assert rb.finish_reason == "length"


def test_paged_pool_that_can_never_admit_raises():
    params, cfg = _params_and_cfg("stablelm_1_6b")
    eng = Engine(cfg, params, max_len=64, batch_size=2, paged=True,
                 num_blocks=1 + 2)
    with pytest.raises(RuntimeError, match="cannot ever admit"):
        eng.serve([Request(prompt=np.arange(1, 40, dtype=np.int32),
                           max_new_tokens=20)])


def test_paged_decode_dispatches_to_pallas_no_fallback():
    """Regression for the PR-6 acceptance criterion: pooled decode runs the
    pallas attention_decode entry with no capability fallback; an xla
    override serves the same op as requested."""
    params, cfg = _params_and_cfg("stablelm_1_6b")
    req = lambda: [Request(prompt=P1, max_new_tokens=4)]
    with ops.record_dispatch() as log:
        Engine(cfg, params, max_len=48, batch_size=1, paged=True,
               ctx=ops.ExecutionContext(backend="pallas")).serve(req())
    dec = [d for d in log if d.op == "attention_decode"]
    assert dec and all(d.chosen == "pallas" and not d.fell_back for d in dec)
    with ops.record_dispatch() as log:
        Engine(cfg, params, max_len=40, batch_size=1, paged=True,
               ctx=ops.ExecutionContext(backend="xla")).serve(req())
    dec = [d for d in log if d.op == "attention_decode"]
    assert dec and all(d.chosen == "xla" and not d.fell_back for d in dec)


def test_plan_batch_size_block_granularity():
    """Paged sizing rounds the per-request footprint up to whole blocks, so
    a block-size-misaligned max_len plans no more slots than contiguous."""
    _, cfg = _params_and_cfg("stablelm_1_6b")
    b = plan_batch_size(cfg, 24, CPU_INTERPRET, block_size=16)
    assert 1 <= b <= plan_batch_size(cfg, 24, CPU_INTERPRET)


def test_slot_cache_ops_roundtrip():
    """insert_cache_slot / reset_cache_slot splice batch-1 rows in and out
    of a pooled cache (every leaf stacked (repeats, B, ...))."""
    _, cfg = _params_and_cfg("jamba_1_5_large")  # attn + ssm leaves
    pool = T.init_cache(cfg, 3, 8, dtype=jnp.float32)
    row = jax.tree.map(lambda a: jnp.full_like(a[:, :1], 2.0),
                       T.init_cache(cfg, 1, 8, dtype=jnp.float32))
    pool = T.insert_cache_slot(pool, row, 1)
    for leaf in jax.tree.leaves(pool):
        np.testing.assert_array_equal(np.asarray(leaf[:, 1]), 2.0)
        np.testing.assert_array_equal(np.asarray(leaf[:, 0]), 0.0)
        np.testing.assert_array_equal(np.asarray(leaf[:, 2]), 0.0)
    pool = T.reset_cache_slot(pool, 1)
    for leaf in jax.tree.leaves(pool):
        np.testing.assert_array_equal(np.asarray(leaf), 0.0)


def _three_requests():
    return [Request(prompt=np.arange(1, 6, dtype=np.int32), max_new_tokens=4),
            Request(prompt=np.arange(1, 21, dtype=np.int32), max_new_tokens=2),
            Request(prompt=np.arange(1, 4, dtype=np.int32), max_new_tokens=3)]


def test_serve_stats_by_hand():
    """Three slots, blocks and prefill buckets of 16, greedy requests A
    (prompt 5, 4 tokens), B (20, 2) and C (3, 3), all admitted in the first
    round. Prefill: real 5 + 20 + 3 = 28, padded 16 + 32 + 16 = 64. Each
    admission yields the first token; decode rounds then run with cache
    depths (pos) of
      step 1: A 5, B 20, C 3 -> the kernel streams each row's live pages,
              1 + 2 + 1 = 4 of 16 positions, 64; the rows attend to
              6 + 21 + 4 = 31; B has its 2 tokens and finishes;
      step 2: A 6, C 4, free row B at idx 0 -> one page a row, 48
              streamed, 7 + 5 = 12 live; C has its 3 tokens;
      step 3: A 7, free rows at idx 0 -> 48 streamed, 8 live; A has its 4.
    So 3 steps, 3 + 2 + 1 = 6 of 9 row-steps active, kv_read 160, kv_live
    51."""
    params, cfg = _params_and_cfg("stablelm_1_6b")
    eng = Engine(cfg, params, max_len=64, batch_size=3, paged=True)
    assert (eng.block_size, eng.prefill_bucket) == (16, 16)
    eng.serve(_three_requests())
    s = eng.stats
    assert (s.steps, s.row_steps_active, s.row_steps_total) == (3, 6, 9)
    assert (s.kv_read, s.kv_live) == (160, 51)
    assert (s.prefill_real, s.prefill_padded) == (28, 64)
    assert (s.admissions, s.backpressure, s.admit_retries,
            s.numeric_retries, dict(s.failures)) == (3, 0, 0, 0, {})
    eng.serve(_three_requests()[:1])  # the counters cover one call
    assert (eng.stats.steps, eng.stats.admissions) == (3, 1)


def test_request_timestamps_are_ordered():
    params, cfg = _params_and_cfg("stablelm_1_6b")
    reqs = _three_requests()
    Engine(cfg, params, max_len=64, batch_size=2, paged=True).serve(reqs)
    for r in reqs:
        assert 0.0 <= r.t_admit <= r.t_first <= r.t_done
    # C waits for B's slot, so it is admitted after B is done
    assert reqs[2].t_admit >= reqs[1].t_done


def test_serve_spans_land_in_a_profiler_trace(tmp_path):
    """A CPU profile of one tiny job holds the engine's spans: one
    ``serve/admit`` per request (``rid`` its index) with its prefill,
    insert and first-token children, one ``serve/step`` per decode round
    with its decode, sample, read and record children."""
    import glob

    from jax.profiler import ProfileData

    params, cfg = _params_and_cfg("stablelm_1_6b")
    eng = Engine(cfg, params, max_len=64, batch_size=3, paged=True)
    eng.serve(_three_requests())  # compile outside the trace
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        eng.serve(_three_requests())
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    events = [ev for plane in ProfileData.from_file(path).planes
              for line in plane.lines for ev in line.events
              if ev.name.startswith("serve/")]
    count = lambda name: sum(ev.name == name for ev in events)
    for name in ("serve/admit", "serve/prefill", "serve/insert",
                 "serve/first_token"):
        assert count(name) == 3, name
    for name in ("serve/step", "serve/decode", "serve/sample", "serve/read",
                 "serve/record"):
        assert count(name) == eng.stats.steps == 3, name
    rids = sorted(dict(ev.stats)["rid"] for ev in events
                  if ev.name == "serve/admit")
    assert rids == [0, 1, 2]
