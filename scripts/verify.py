"""Static verification sweep: every registered instrumented op, no device.

For each op x shape in the sweep (the five ResNet-50 conv shapes from
``configs/resnet50_convs.py`` on both conv backends — plus their int8
``conv2d_q``/``matmul_q`` quantized forms, whose folded scale vector is an
audited operand of its own — the GEMM / conv1d / grouped-matmul / attention
shapes the tier-1 suite exercises, and the serving decode snapshots from
``benchmarks/serving_bench``), dispatch through
``ops.explain(audit=True)``: the ``repro.verify`` auditor abstractly
interprets the kernel's access plan and the dispatch fails unless the
audited words reproduce ``words_fn`` exactly, fit VMEM, and the DMA
schedule is hazard-free. The run itself is therefore the assertion; rows
are also emitted for the cross-leg byte-identity gate in CI.

    PYTHONPATH=src python scripts/verify.py --json VERIFY.json
    PYTHONPATH=src python scripts/verify.py --mutants   # auditor self-test

Exit codes: 0 clean; 1 audit/lint violations; 3 a seeded mutant escaped.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

_REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(_REPO / "src"))
sys.path.insert(0, str(_REPO))  # benchmarks.* (serving snapshot geometry)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import ops  # noqa: E402
from repro.configs.resnet50_convs import RESNET50  # noqa: E402
from repro.plan import TPU_V5E  # noqa: E402
from repro.verify import install_plan_audit  # noqa: E402
from repro.verify.lint import run_lint  # noqa: E402

PALLAS = ops.ExecutionContext(target=TPU_V5E, backend="pallas")
IM2COL = ops.ExecutionContext(target=TPU_V5E, backend="im2col")


def _row(name: str, decision) -> dict:
    assert decision.audited is not None, f"{name}: dispatch was not audited"
    return {
        "name": name,
        "chosen": decision.chosen,
        "measured_words": decision.measured_words,
        "audited_words": decision.audited,
        "bound_ratio": decision.bound_ratio,
    }


def sweep_convs(dtype=jnp.bfloat16):
    """The conv_bench shape sweep, audited, on both conv backends."""
    rows = []
    for lname, s in RESNET50.items():
        H = (s.h_O - 1) * s.sh + s.h_F
        W = (s.w_O - 1) * s.sw + s.w_F
        xs = jax.ShapeDtypeStruct((s.N, s.c_I, H, W), dtype)
        ws = jax.ShapeDtypeStruct((s.c_O, s.c_I, s.h_F, s.w_F), dtype)
        kw = {"spec_args": (xs, ws), "spec_kw": {"stride": (s.sh, s.sw)},
              "audit": True}
        rows.append(_row(f"conv2d/{lname}/pallas",
                         ops.explain("conv2d", PALLAS, **kw)))
        rows.append(_row(f"conv2d/{lname}/im2col",
                         ops.explain("conv2d", IM2COL, **kw)))
    return rows


def sweep_gemm_conv1d(dtype=jnp.bfloat16):
    rows = []
    for m, k, n in ((512, 384, 256), (2048, 2048, 2048), (23328, 576, 64)):
        a = jax.ShapeDtypeStruct((m, k), dtype)
        b = jax.ShapeDtypeStruct((k, n), dtype)
        rows.append(_row(
            f"matmul/{m}x{k}x{n}",
            ops.explain("matmul", PALLAS, spec_args=(a, b), audit=True)))
    for B, L, D, K in ((2, 33, 130, 4), (4, 256, 512, 4)):
        x = jax.ShapeDtypeStruct((B, L, D), dtype)
        w = jax.ShapeDtypeStruct((K, D), dtype)
        rows.append(_row(
            f"conv1d_causal/B{B}_L{L}_D{D}_K{K}",
            ops.explain("conv1d_causal", PALLAS, spec_args=(x, w),
                        audit=True)))
    # the dropless expert layer's gate/up and down at one chip's 8 experts
    # (LFM2-8B-A1B: 256 decode rows -> 1,280 grouped rows, a 19-token
    # prefill -> 352)
    for m, k, n in ((1280, 2048, 1792), (1280, 1792, 2048),
                    (352, 2048, 1792)):
        x = jax.ShapeDtypeStruct((m, k), dtype)
        w = jax.ShapeDtypeStruct((8, k, n), dtype)
        g = jax.ShapeDtypeStruct((8,), jnp.int32)
        rows.append(_row(
            f"grouped_matmul/{m}x{k}x{n}_G8",
            ops.explain("grouped_matmul", PALLAS, spec_args=(x, w, g),
                        audit=True)))
    return rows


def sweep_quant():
    """Quantized int8 conv2d/matmul dispatches, audited like the bf16 sweep.
    The scale vector is a separately-audited operand here, so these rows
    also pin the one-shot scale-fetch accounting the ``scale_applied_twice``
    mutant perturbs."""
    rows = []
    for lname, s in RESNET50.items():
        H = (s.h_O - 1) * s.sh + s.h_F
        W = (s.w_O - 1) * s.sw + s.w_F
        xs = jax.ShapeDtypeStruct((s.N, s.c_I, H, W), jnp.int8)
        ws = jax.ShapeDtypeStruct((s.c_O, s.c_I, s.h_F, s.w_F), jnp.int8)
        sc = jax.ShapeDtypeStruct((1, s.c_O), jnp.float32)
        rows.append(_row(f"conv2d_q/{lname}/pallas", ops.explain(
            "conv2d_q", PALLAS, dtype="int8", spec_args=(xs, ws, sc),
            spec_kw={"stride": (s.sh, s.sw)}, audit=True)))
    for m, k, n in ((512, 384, 256), (2048, 2048, 2048)):
        a = jax.ShapeDtypeStruct((m, k), jnp.int8)
        b = jax.ShapeDtypeStruct((k, n), jnp.int8)
        sc = jax.ShapeDtypeStruct((1, n), jnp.float32)
        rows.append(_row(f"matmul_q/{m}x{k}x{n}", ops.explain(
            "matmul_q", PALLAS, dtype="int8", spec_args=(a, b, sc),
            audit=True)))
    return rows


def sweep_attention(dtype=jnp.bfloat16):
    """Prefill + contiguous decode + paged decode, mirroring serving_bench."""
    import dataclasses

    from benchmarks.serving_bench import BATCH, BLOCK, MAX_LEN, SNAPSHOTS
    from repro.configs import get_smoke
    from repro.serving import kv

    rows = []
    # prefill-style static attention
    q = jax.ShapeDtypeStruct((2, 8, 128, 64), dtype)
    kvs = jax.ShapeDtypeStruct((2, 8, 128, 64), dtype)
    rows.append(_row("attention/prefill_B2_H8_L128",
                     ops.explain("attention", PALLAS, spec_args=(q, kvs, kvs),
                                 audit=True)))
    cfg = dataclasses.replace(get_smoke("qwen2_5_3b"),
                              compute_dtype="float32")
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    num_blocks = kv.plan_pool_blocks(cfg, MAX_LEN, BATCH, BLOCK)
    for name, B, live in SNAPSHOTS:
        w = -(-live // BLOCK)
        qd = jax.ShapeDtypeStruct((B, H, 1, hd), dtype)
        pool = jax.ShapeDtypeStruct((cfg.repeats, num_blocks, KV, BLOCK, hd),
                                    dtype)
        row = jax.ShapeDtypeStruct((B, KV, hd), dtype)
        rows.append(_row(f"attention_decode/{name}", ops.explain(
            "attention_decode", PALLAS,
            spec_args=(qd, pool, pool,
                       jax.ShapeDtypeStruct((B, w), jnp.int32),
                       jax.ShapeDtypeStruct((B,), jnp.int32),
                       jax.ShapeDtypeStruct((), jnp.int32), row, row),
            audit=True)))
        rows.append(_row(f"attention_contig/{name}", ops.explain(
            "attention", PALLAS,
            needs=ops.attention_needs(q_offset=jnp.arange(B)),
            spec_args=(qd,
                       jax.ShapeDtypeStruct((B, KV, MAX_LEN, hd), dtype),
                       jax.ShapeDtypeStruct((B, KV, MAX_LEN, hd), dtype)),
            spec_kw={"q_offset": jnp.full((B,), live, jnp.int32)},
            audit=True)))
    return rows


def run_mutants() -> int:
    from repro.verify.mutants import run_seeded_mutants

    escaped = 0
    for name, caught, detail in run_seeded_mutants():
        tag = "caught" if caught else "ESCAPED"
        print(f"mutant {name:20s} {tag}: {detail[:100]}")
        escaped += 0 if caught else 1
    return escaped


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--json", help="write audited rows to this path")
    ap.add_argument("--skip-lint", action="store_true")
    ap.add_argument("--mutants", action="store_true",
                    help="run the seeded-mutant self-test and exit")
    args = ap.parse_args(argv)

    if args.mutants:
        escaped = run_mutants()
        if escaped:
            print(f"verify: {escaped} seeded mutant(s) escaped the auditor")
            return 3
        print("verify: all seeded mutants caught")
        return 0

    rc = 0
    if not args.skip_lint:
        found = run_lint()
        for viol in found:
            print(viol)
        if found:
            print(f"verify: {len(found)} lint violation(s)")
            rc = 1

    # every plan built below also passes construction-time validation
    install_plan_audit()

    rows = []
    try:
        rows += sweep_convs()
        rows += sweep_gemm_conv1d()
        rows += sweep_quant()
        rows += sweep_attention()
    except Exception as e:
        print(f"verify: FAILED — {e}")
        return 1

    mismatched = [r for r in rows
                  if abs(r["audited_words"] - r["measured_words"])
                  > 1e-6 * max(r["measured_words"], 1.0)]
    from repro.analysis.roofline import hbm_seconds

    for r in rows:
        print(f"{r['name']:40s} [{r['chosen']:6s}] "
              f"words={r['measured_words']:.6e} "
              f"(~{hbm_seconds(r['measured_words']) * 1e6:.1f}us HBM) "
              f"audited exactly")
    print(f"verify: {len(rows)} dispatches audited, "
          f"{len(mismatched)} mismatched")
    if mismatched:
        rc = 1

    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=1)
        print(f"wrote {args.json} ({len(rows)} rows)")
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
