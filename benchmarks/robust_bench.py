"""Robustness benchmark: graceful degradation under a seeded fault campaign.

Two kinds of rows, mirroring ``serving_bench``:

**Degradation-cost sweep (deterministic, gated).** Every runtime demotion
has a *priced* communication cost: when a transient fault quarantines the
pallas conv entry, ``dispatch_call`` re-resolves through its declared
``degrade_to`` chain (im2col), and the decision's ``measured_words`` /
``bound_ratio`` are re-priced for the degraded kernel. The sweep records
that cost for the ResNet-50 shapes (the paper's §5 set) straight from
``ops.explain`` on both backends — the words gap a degraded dispatch
pays — plus one *live* row: a rate-1.0 launch campaign actually
faults an eager conv2d, and the row records the repriced decision the
quarantined dispatcher then reports. All fields are static word counts,
identical on every CI leg.

**Fault campaign (floor-gated).** The serving workload runs twice on the
same engine configuration — fault-free, then under a seeded transient-fault
campaign (default: 5% rate over launch/dma/numeric/oom/pool at every
scheduling site). The gate requires:

  * completion rate >= 0.99 (no aborts: any taxonomy escape fails the run),
  * zero unresolved injections (``FaultCampaign.verify_accounted``),
  * completed requests BIT-IDENTICAL to the fault-free run, failed ones a
    clean prefix of it (retries are idempotent, rebuilds exact),
  * faulted tok/s >= 0.4x the fault-free tok/s on the same leg.

CLI (the CI chaos gate):

    PYTHONPATH=src python -m benchmarks.robust_bench --campaign \\
        --json BENCH_robust.json
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import ops
from repro.configs.resnet50_convs import RESNET50
from repro.plan import CPU_INTERPRET, TPU_V5E
from repro.resilience import faults as fj

PALLAS = ops.ExecutionContext(target=TPU_V5E, backend="pallas")
IM2COL = ops.ExecutionContext(target=TPU_V5E, backend="im2col")

DEFAULT_SPEC = "rate=0.05,seed=0,kinds=launch+dma+numeric+oom+pool"
COMPLETION_FLOOR = 0.99
TOK_S_FLOOR = 0.4  # faulted >= 0.4x clean on the same leg

# serving workload: small enough for the pallas-interpret leg, mixed enough
# to exercise admission, lockstep decode, and finish at distinct depths
MAX_LEN = 64
BATCH = 4
N_REQUESTS = 12
PROMPT_LENS = (4, 9, 14)
MAX_NEWS = (6, 12, 16)


# ---------------------------------------------------------------------------
# Degradation cost: the words a demoted dispatch pays, per ResNet-50 shape
# ---------------------------------------------------------------------------

def degradation_rows(dtype=jnp.bfloat16) -> List[dict]:
    records = []
    for lname, s in RESNET50.items():
        H = (s.h_O - 1) * s.sh + s.h_F  # tight VALID input extent
        W = (s.w_O - 1) * s.sw + s.w_F
        xs = jax.ShapeDtypeStruct((s.N, s.c_I, H, W), dtype)
        ws = jax.ShapeDtypeStruct((s.c_O, s.c_I, s.h_F, s.w_F), dtype)
        kw = {"spec_args": (xs, ws), "spec_kw": {"stride": (s.sh, s.sw)}}
        primary = ops.explain("conv2d", PALLAS, **kw)
        degraded = ops.explain("conv2d", IM2COL, **kw)
        assert primary.chosen == "pallas" and degraded.chosen == "im2col"
        records.append({
            "name": f"degrade/{lname}",
            "primary_words": primary.measured_words,
            "degraded_words": degraded.measured_words,
            "primary_bound_ratio": primary.bound_ratio,
            "degraded_bound_ratio": degraded.bound_ratio,
            "degradation_cost_ratio":
                degraded.measured_words / primary.measured_words,
        })
    return records


def live_degradation_row() -> dict:
    """Actually fault a launch and record the repriced decision.

    A rate-1.0 launch campaign faults the eager pallas conv2d once;
    ``dispatch_call`` quarantines it and serves the call through im2col.
    The row captures what ``ops.explain`` then reports for the same shape:
    ``degraded=True``, the fault name, and measured words / bound ratio
    repriced at the degraded entry. Word counters are static, so the row is
    leg-independent despite executing for real."""
    ctx = ops.ExecutionContext(target=CPU_INTERPRET, backend="pallas")
    x = jnp.ones((2, 8, 12, 12), jnp.float32)
    w = jnp.ones((8, 8, 3, 3), jnp.float32)
    kw = {"spec_args": (jax.ShapeDtypeStruct(x.shape, x.dtype),
                        jax.ShapeDtypeStruct(w.shape, w.dtype)),
          "spec_kw": {"stride": (1, 1), "out_dtype": jnp.float32}}
    ops.clear_quarantine()
    before = ops.explain("conv2d", ctx, **kw)
    camp = fj.FaultCampaign(seed=0, rate=1.0, kinds=("launch",),
                            ops=("conv2d",), max_faults=1)
    with fj.activate(camp):
        y_faulted = ops.conv2d(x, w, ctx=ctx)
    camp.verify_accounted()
    after = ops.explain("conv2d", ctx, **kw)
    assert after.degraded and after.fault == "KernelLaunchError", after
    # the degraded path must still be numerically the same conv
    y_clean = ops.conv2d(x, w, ctx=ops.ExecutionContext(
        target=CPU_INTERPRET, backend="im2col"))
    np.testing.assert_allclose(np.asarray(y_faulted), np.asarray(y_clean),
                               rtol=1e-5, atol=1e-5)
    ops.clear_quarantine()
    return {
        "name": "degrade/live_conv2d",
        "fault": after.fault,
        "primary_words": before.measured_words,
        "degraded_words": after.measured_words,
        "primary_bound_ratio": before.bound_ratio,
        "degraded_bound_ratio": after.bound_ratio,
        "degradation_cost_ratio":
            after.measured_words / before.measured_words,
    }


# ---------------------------------------------------------------------------
# Fault campaign over the serving engine
# ---------------------------------------------------------------------------

def _smoke_cfg():
    from repro.configs import get_smoke
    return dataclasses.replace(get_smoke("qwen2_5_3b"),
                               compute_dtype="float32")


def _workload(cfg) -> List:
    from repro.serving.engine import Request
    rng = np.random.default_rng(0)
    return [Request(
        prompt=rng.integers(0, cfg.vocab_size, size=PROMPT_LENS[i % 3],
                            dtype=np.int64).astype(np.int32),
        max_new_tokens=MAX_NEWS[i % 3], temperature=0.0, rng_seed=i)
        for i in range(N_REQUESTS)]


def _serve(cfg, params, camp: Optional[fj.FaultCampaign]):
    from repro.serving.engine import Engine
    ops.clear_quarantine()  # each run prices its own degradations
    eng = Engine(cfg, params, max_len=MAX_LEN, batch_size=BATCH)
    reqs = _workload(cfg)
    if camp is None:
        t0 = time.perf_counter()
        eng.serve(reqs)
        dt = time.perf_counter() - t0
    else:
        with fj.activate(camp):
            t0 = time.perf_counter()
            eng.serve(reqs)
            dt = time.perf_counter() - t0
    return reqs, dt


def campaign_row(spec: str) -> tuple:
    """(record, problems) for the clean-vs-faulted serving comparison."""
    from repro.models import transformer as T

    cfg = _smoke_cfg()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    # warmup both paths: the faulted warmup (same seed -> same schedule)
    # traces the retry/rebuild-only shapes, so the timed runs compare
    # scheduling cost rather than one-off jit compilations
    _serve(cfg, params, None)
    _serve(cfg, params, fj.campaign_from_spec(spec))
    clean, dt_clean = _serve(cfg, params, None)
    camp = fj.campaign_from_spec(spec)
    faulted, dt_faulted = _serve(cfg, params, camp)
    camp.verify_accounted()  # raises if any handler swallowed a fault

    problems: List[str] = []
    done = [r for r in faulted
            if r.finish_reason not in ("error", "timeout")]
    completion = len(done) / len(faulted)
    if completion < COMPLETION_FLOOR:
        problems.append(f"completion rate {completion:.3f} below "
                        f"{COMPLETION_FLOOR} under {spec!r}")
    mismatched = 0
    for c, f in zip(clean, faulted):
        c_toks = np.asarray(c.out_tokens)
        if f.finish_reason in ("error", "timeout"):
            # a failed request keeps a clean prefix, never invented tokens
            if not np.array_equal(f.out_tokens,
                                  c_toks[:len(f.out_tokens)]):
                mismatched += 1
        elif (f.finish_reason != c.finish_reason
              or not np.array_equal(f.out_tokens, c_toks)):
            mismatched += 1
    if mismatched:
        problems.append(f"{mismatched} request(s) diverged from the "
                        "fault-free run (retries must be idempotent, "
                        "rebuilds exact)")
    toks = lambda rs: sum(len(r.out_tokens) for r in rs  # noqa: E731
                          if r.out_tokens is not None)
    tok_s_clean = toks(clean) / dt_clean
    tok_s_faulted = toks(faulted) / dt_faulted
    if tok_s_faulted < TOK_S_FLOOR * tok_s_clean:
        problems.append(f"faulted tok/s {tok_s_faulted:.1f} below "
                        f"{TOK_S_FLOOR}x clean {tok_s_clean:.1f}")
    if not camp.injections:
        problems.append(f"campaign {spec!r} injected nothing — the gate "
                        "is vacuous (raise rate or workload size)")
    # tok/s fields deliberately avoid _words/_ratio suffixes: compare.py
    # must never gate wall clock; the floors above run in-process instead
    record = {
        "name": "campaign/serving",
        "spec": spec,
        "requests": len(faulted),
        "completion_rate": completion,
        "faults_injected": len(camp.injections),
        "faults_unresolved": len(camp.unresolved()),
        "resolutions": camp.summary()["resolutions"],
        "unaffected_mismatches": mismatched,
        "tok_s_clean": tok_s_clean,
        "tok_s_faulted": tok_s_faulted,
    }
    return record, problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", default="BENCH_robust.json", metavar="PATH",
                    help="write degradation + campaign records to PATH")
    ap.add_argument("--campaign", nargs="?", const=DEFAULT_SPEC, default=None,
                    metavar="SPEC",
                    help="run the serving fault campaign (REPRO_FAULTS-style "
                         f"spec; bare flag = {DEFAULT_SPEC!r})")
    args = ap.parse_args(argv)

    bad: List[str] = []
    records = degradation_rows()
    records.append(live_degradation_row())
    for r in records:
        print(f"{r['name']:22s} primary={r['primary_words']:.3e}w "
              f"degraded={r['degraded_words']:.3e}w "
              f"cost={r['degradation_cost_ratio']:.2f}x")
        if r["degradation_cost_ratio"] <= 1.0:
            bad.append(f"{r['name']}: degradation is free — the fallback "
                       "chain is mispriced or inverted")
    if args.campaign:
        rec, problems = campaign_row(args.campaign)
        bad.extend(problems)
        records.append(rec)
        print(f"{rec['name']:22s} completion={rec['completion_rate']:.3f} "
              f"injected={rec['faults_injected']} "
              f"unresolved={rec['faults_unresolved']} "
              f"tok/s={rec['tok_s_faulted']:.1f} "
              f"(clean {rec['tok_s_clean']:.1f}) "
              f"resolutions={rec['resolutions']}")
    with open(args.json, "w") as f:
        json.dump(records, f, indent=1)
    print(f"wrote {len(records)} records to {args.json}")
    if bad:
        print("FAIL:", file=sys.stderr)
        for b in bad:
            print(f"  {b}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
