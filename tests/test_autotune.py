"""The measured autotune stage (``repro.plan.autotune``): frontier
enumeration stays auditable, the roofline-timed winner is deterministic,
records persist through the PlanCache, and the shared resolution path
reports where every plan came from."""

import dataclasses
import json

import pytest

import jax
import jax.numpy as jnp

from repro import ops
from repro.plan import (AutotunePolicy, ConvSpec, MatmulSpec, Planner,
                        TPU_V5E, TuningRecord, predicted_seconds,
                        resolve_plan, target_fingerprint)
from repro.plan import autotune as at
from repro.plan import planner as planner_mod

# a batch of whole 16-row sublane tiles and lane-wide output channels: the
# kernel pads both, so a narrower layer has a one-candidate frontier
CONV = ConvSpec(N=32, c_I=64, c_O=128, w_O=28, h_O=28, w_F=3, h_F=3)
MM = MatmulSpec(256, 192, 128)
ROOFLINE = AutotunePolicy(timer="roofline")


@pytest.fixture(autouse=True)
def _fresh_cache():
    Planner.cache.clear()
    yield
    Planner.cache.clear()


# ---------------------------------------------------------------------------
# policy + record plumbing
# ---------------------------------------------------------------------------

def test_policy_coerce():
    assert AutotunePolicy.coerce(None) is None
    assert AutotunePolicy.coerce(False) is None
    assert AutotunePolicy.coerce(True) == AutotunePolicy()
    pol = AutotunePolicy(slack=1.1, timer="roofline")
    assert AutotunePolicy.coerce(pol) is pol
    with pytest.raises(TypeError):
        AutotunePolicy.coerce("yes please")


def test_tuning_record_roundtrip():
    ep = Planner(TPU_V5E).autotune(CONV, policy=ROOFLINE)
    (rec,) = at.records()
    back = TuningRecord.from_dict(rec.to_dict())
    assert back == rec
    assert back.fingerprint == target_fingerprint(TPU_V5E)
    assert ep.tiles == rec.tiles and ep.tuned == rec.tuned


def test_tuning_record_rejects_fingerprint_mismatch():
    Planner(TPU_V5E).autotune(CONV, policy=ROOFLINE)
    (rec,) = at.records()
    d = rec.to_dict()
    d["target_fingerprint"] = "0" * 12
    with pytest.raises(ValueError, match="fingerprint"):
        TuningRecord.from_dict(d)
    d2 = rec.to_dict()
    d2["version"] = at.TUNING_FORMAT_VERSION + 1
    with pytest.raises(ValueError, match="newer"):
        TuningRecord.from_dict(d2)


# ---------------------------------------------------------------------------
# frontier: every timed candidate is auditable and fits VMEM
# ---------------------------------------------------------------------------

def _frontier_survivors(spec):
    """Re-run the search's enumerate->slack/cap filter and return the
    candidate plans the audit gate would see."""
    from repro.ops import registry

    op = at._normalize(at.as_op_spec(spec), TPU_V5E)
    base = planner_mod.analytic_plan(op, TPU_V5E)
    op_name, spec_args, spec_kw = at._op_call(op, TPU_V5E)
    ctx = ops.ExecutionContext(target=TPU_V5E, backend="pallas")
    entry = registry.get_backend("pallas").ops[op_name]
    tiles = (at._conv_candidates(op, TPU_V5E, base.tiles)
             if isinstance(op, ConvSpec)
             else at._matmul_candidates(op, TPU_V5E, base.tiles))
    base_words = float(entry.words_fn(ctx, base, *spec_args, **spec_kw))
    cap = max(ROOFLINE.bound_cap * base.lower_bound, base_words)
    out = []
    for t in tiles:
        cand = at._candidate_plan(base, op, t, 0.0)
        w = float(entry.words_fn(ctx, cand, *spec_args, **spec_kw))
        if w <= ROOFLINE.slack * base_words + 1e-9 and w <= cap + 1e-9:
            out.append((entry, ctx, op_name, spec_args, spec_kw,
                        at._candidate_plan(base, op, t, w), w))
    return out


@pytest.mark.parametrize("spec", [CONV, MM], ids=["conv", "matmul"])
def test_frontier_candidates_all_audit_exact(spec):
    from repro.ops.dispatch import DispatchDecision
    from repro.verify import audit

    survivors = _frontier_survivors(spec)
    assert len(survivors) >= 2  # the frontier is non-trivial
    mem = TPU_V5E.memory_model()
    for entry, ctx, op_name, spec_args, spec_kw, cand, w in survivors:
        ap = entry.access_plan_fn(ctx, cand, *spec_args, **spec_kw)
        decision = DispatchDecision(op=op_name, requested="pallas",
                                    chosen="pallas", plan=cand,
                                    measured_words=w, plan_source="explicit")
        res = audit.audit_decision(ap, decision, target=TPU_V5E)
        assert res.ok, (cand.tiles, res)
        assert ap.scratch_words() <= mem.M_eff  # VMEM feasibility


# ---------------------------------------------------------------------------
# the search: determinism, winner never loses to analytic, counter
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec", [CONV, MM], ids=["conv", "matmul"])
def test_roofline_winner_deterministic(spec):
    first = Planner(TPU_V5E).autotune(spec, policy=ROOFLINE)
    (rec1,) = at.records()
    Planner.cache.clear()
    second = Planner(TPU_V5E).autotune(spec, policy=ROOFLINE)
    (rec2,) = at.records()
    assert rec1 == rec2
    assert first.tiles == second.tiles
    assert first.tuned.source == "roofline"
    assert at.search_count() >= 2  # both searches actually ran


def test_winner_never_slower_than_analytic_on_the_model():
    op = at._normalize(at.as_op_spec(CONV), TPU_V5E)
    base = planner_mod.analytic_plan(op, TPU_V5E)
    # priced at the words its launch really moves, as the search prices
    # every candidate (layout passes and lane padding included)
    base_words = next(w for *_, cand, w in _frontier_survivors(CONV)
                      if cand.tiles == base.tiles)
    base_secs = predicted_seconds(base, base_words)
    tuned = Planner(TPU_V5E).autotune(CONV, policy=ROOFLINE)
    assert tuned.tuned.winner_seconds <= base_secs + 1e-12
    assert tuned.tuned.candidates_timed >= 1
    assert tuned.comm_volume == tuned.tuned.winner_words


def test_autotune_memoizes_and_counts_searches():
    n0 = at.search_count()
    p1 = Planner(TPU_V5E).autotune(CONV, policy=ROOFLINE)
    p2 = Planner(TPU_V5E).autotune(CONV, policy=ROOFLINE)
    assert p1 is p2  # record hit materializes the identical cached plan
    assert at.search_count() == n0 + 1


def test_attention_is_unsearchable():
    from repro.plan import AttentionSpec

    spec = AttentionSpec(B=1, H=2, KV=2, Lq=128, Lk=128, hd=64)
    assert not at.supports(spec)
    with pytest.raises(TypeError, match="closed-form"):
        Planner(TPU_V5E).autotune(spec)


# ---------------------------------------------------------------------------
# resolution path: explicit > tuned > analytic, everywhere the same
# ---------------------------------------------------------------------------

def test_resolve_plan_sources():
    p, src = resolve_plan(CONV, TPU_V5E)
    assert src == "analytic" and p.tuned is None
    explicit, src2 = resolve_plan(CONV, TPU_V5E, explicit=p)
    assert explicit is p and src2 == "explicit"
    tuned = Planner(TPU_V5E).autotune(CONV, policy=ROOFLINE)
    p3, src3 = resolve_plan(CONV, TPU_V5E)
    assert src3 == "tuned" and p3 is tuned


def test_resolve_plan_searches_under_policy():
    n0 = at.search_count()
    p, src = resolve_plan(CONV, TPU_V5E, autotune=ROOFLINE)
    assert src == "tuned" and p.tuned is not None
    assert at.search_count() == n0 + 1


def _conv_call():
    x = jax.ShapeDtypeStruct((CONV.N, CONV.c_I, 16, 16), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((CONV.c_O, CONV.c_I, 3, 3), jnp.bfloat16)
    return {"spec_args": (x, w), "spec_kw": {"stride": (1, 1)}}


def test_explain_reports_tuned_vs_analytic():
    ctx = ops.ExecutionContext(target=TPU_V5E, backend="pallas")
    before = ops.explain("conv2d", ctx, **_conv_call())
    assert before.plan_source == "analytic"
    tuning = ops.ExecutionContext(target=TPU_V5E, backend="pallas",
                                  autotune=ROOFLINE)
    dec = ops.explain("conv2d", tuning, **_conv_call())
    assert dec.plan_source == "tuned"
    assert dec.plan.tuned is not None
    assert dec.measured_words == dec.plan.tuned.winner_words
    assert "tuned plan" in dec.why() and "candidates timed" in dec.why()
    # the record now serves every context for the pair, sans policy
    after = ops.explain("conv2d", ctx, **_conv_call())
    assert after.plan_source == "tuned"
    assert after.plan.tiles == dec.plan.tiles


def test_explain_explicit_plan_source():
    ctx = ops.ExecutionContext(target=TPU_V5E, backend="pallas")
    base = ops.explain("conv2d", ctx, **_conv_call())
    again = ops.explain("conv2d", ctx, plan=base.plan, **_conv_call())
    assert again.plan_source == "explicit"
    assert "explicit plan" in again.why()


def test_dispatch_executes_tuned_plan():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (2, 8, 10, 10), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (16, 8, 3, 3), jnp.float32)
    ctx = ops.ExecutionContext(target=TPU_V5E, backend="pallas")
    want = ops.conv2d(x, w, ctx=ctx)
    spec = ConvSpec(N=2, c_I=8, c_O=16, w_O=8, h_O=8, w_F=3, h_F=3,
                    prec=TPU_V5E.precision)
    Planner(TPU_V5E).autotune(spec, policy=ROOFLINE)
    tuning = ops.ExecutionContext(target=TPU_V5E, backend="pallas",
                                  autotune=ROOFLINE)
    got = ops.conv2d(x, w, ctx=tuning)
    import numpy as np
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# persistence: the zero-re-search serving contract
# ---------------------------------------------------------------------------

def test_cache_roundtrip_serves_without_research(tmp_path):
    tuned = Planner(TPU_V5E, autotune=ROOFLINE).plan(CONV)
    assert tuned.tuned is not None
    n0 = at.search_count()
    path = str(tmp_path / "cache.json")
    wrote = Planner.cache.save(path)
    assert wrote >= 2  # at least the tuned plan + its record
    Planner.cache.clear()
    assert Planner.cache.size() == 0 and not at.records()
    Planner.cache.load(path)
    served = Planner(TPU_V5E).plan(CONV)  # no policy: the record serves
    assert served.tuned == tuned.tuned and served.tiles == tuned.tiles
    assert at.search_count() == n0  # zero re-searches
    dump = json.loads(open(path).read())
    assert dump["format"] == planner_mod.PLAN_FORMAT_VERSION
    assert len(dump["tuning"]) == 1


def test_clear_records_keeps_analytic_entries():
    analytic = Planner(TPU_V5E).plan(MM)
    Planner(TPU_V5E).autotune(CONV, policy=ROOFLINE)
    at.clear_records()
    assert not at.records()
    # the matmul's analytic entry survived; the conv re-resolves analytic
    assert Planner(TPU_V5E).plan(MM) is analytic
    assert Planner(TPU_V5E).plan(CONV).tuned is None


# ---------------------------------------------------------------------------
# offline cost model + lint
# ---------------------------------------------------------------------------

def test_offline_model_prices_dma_setup():
    from repro.analysis.roofline import (DMA_SETUP_SECONDS,
                                         alpha_beta_seconds, hbm_seconds)
    assert alpha_beta_seconds(1e6, 0) == hbm_seconds(1e6)
    assert alpha_beta_seconds(1e6, 10) == pytest.approx(
        hbm_seconds(1e6) + 10 * DMA_SETUP_SECONDS)
    ep = Planner(TPU_V5E).plan(CONV)
    assert predicted_seconds(ep) > 0.0


def test_lint_vrf015_flags_legacy_kernel_kwargs(tmp_path):
    from repro.verify.lint import lint_file

    bad = tmp_path / "src" / "serving_thing.py"
    bad.parent.mkdir()
    bad.write_text(
        "from repro.kernels.conv2d import conv2d\n"
        "def f(x, w, tgt):\n"
        "    return conv2d(x, w, target=tgt, tiles=(1, 1, 1, 1, 1))\n")
    (viol,) = lint_file(bad, tmp_path)
    assert viol.code == "VRF015"
    assert "['target', 'tiles']" in viol.message
    ok = tmp_path / "src" / "good_thing.py"
    ok.write_text(
        "from repro import ops\n"
        "def f(x, w, ctx):\n"
        "    return ops.conv2d(x, w, ctx=ctx)\n")
    assert lint_file(ok, tmp_path) == []
    # kernels/ keeps its explicit-plan internals without tripping the rule
    kern = tmp_path / "kernels" / "wrap.py"
    kern.parent.mkdir()
    kern.write_text(
        "from .conv2d import conv2d\n"
        "def g(x, w, p):\n"
        "    return conv2d(x, w, plan=p)\n")
    assert lint_file(kern, tmp_path) == []
