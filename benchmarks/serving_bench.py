"""Serving benchmark: paged KV-cache decode words against the contiguous
cache's, gated against the paper's attention bound.

**Shape sweep (deterministic, gated).** Decode-state snapshots dispatched
through ``ops.explain`` with ``jax.ShapeDtypeStruct`` specs under an explicit
pallas context, so the records are identical on every CI leg regardless of
``REPRO_BACKEND``. Each snapshot reports the paged ``attention_decode``
kernel's measured HBM words (block-table gather over ``w`` live blocks) next
to the contiguous in-cache decode's words (full ``max_len`` stream) and the
Lq = 1 specialization of Thm 2.1 (``core.bounds.attention_bound``), whose
memory-independent KV-stream term dominates decode. A pool-occupancy row
charges a shared prompt prefix once (refcounted blocks) vs per-request.

CLI (the CI serving gate):

    PYTHONPATH=src python -m benchmarks.serving_bench --json BENCH_serving.json

exits nonzero if paged decode moves >= the contiguous words on any snapshot,
the measured/bound ratio drifts, or prefix sharing stops saving pool words.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro import ops
from repro.plan import TPU_V5E
from repro.serving import kv

PALLAS = ops.ExecutionContext(target=TPU_V5E, backend="pallas")

# four requests share a full-block prompt prefix in the pool-occupancy row
N_SHARED = 4
SHARED_PREFIX = 16
BATCH = 4
# The serving window: paged decode reads w live blocks per step while the
# contiguous engine streams the whole max_len window, so the paged win grows
# with max_len - live_tokens.
MAX_LEN = 512
BLOCK = kv.DEFAULT_BLOCK_SIZE


# ---------------------------------------------------------------------------
# Shape sweep: measured decode words vs the attention bound
# ---------------------------------------------------------------------------

# (name, batch, live tokens per row) decode snapshots under MAX_LEN:
# early decode (1 live block), the bench workload's depth, a deep sequence.
SNAPSHOTS = (
    ("decode/B4_len12", 4, 12),
    ("decode/B4_len50", 4, 50),
    ("decode/B4_len200", 4, 200),
)


def _smoke_cfg():
    from repro.configs import get_smoke
    return dataclasses.replace(get_smoke("qwen2_5_3b"),
                               compute_dtype="float32")


def sweep(dtype=jnp.bfloat16):
    cfg = _smoke_cfg()
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    num_blocks = kv.plan_pool_blocks(cfg, MAX_LEN, BATCH, BLOCK)
    records = []
    for name, B, live in SNAPSHOTS:
        w = -(-live // BLOCK)
        q = jax.ShapeDtypeStruct((B, H, 1, hd), dtype)
        pool = jax.ShapeDtypeStruct((cfg.repeats, num_blocks, KV, BLOCK, hd),
                                    dtype)
        row = jax.ShapeDtypeStruct((B, KV, hd), dtype)
        paged = ops.explain(
            "attention_decode", PALLAS,
            spec_args=(q, pool, pool,
                       jax.ShapeDtypeStruct((B, w), jnp.int32),
                       jax.ShapeDtypeStruct((B,), jnp.int32),
                       jax.ShapeDtypeStruct((), jnp.int32), row, row))
        # the contiguous engine streams the whole max_len cache window each
        # step (per-row offsets, pallas-native since this PR)
        contig = ops.explain(
            "attention", PALLAS,
            needs=ops.attention_needs(q_offset=jnp.arange(B)),
            spec_args=(q,
                       jax.ShapeDtypeStruct((B, KV, MAX_LEN, hd), dtype),
                       jax.ShapeDtypeStruct((B, KV, MAX_LEN, hd), dtype)),
            spec_kw={"q_offset": jnp.full((B,), live, jnp.int32)})
        assert paged.chosen == "pallas" and not paged.fell_back
        assert contig.chosen == "pallas" and not contig.fell_back
        records.append({
            "name": name,
            "live_tokens": live,
            "table_width": w,
            "paged_words": paged.measured_words,
            "contig_words": contig.measured_words,
            "lower_bound": paged.plan.lower_bound,
            "paged_bound_ratio": paged.bound_ratio,
            "paged_over_contig_ratio":
                paged.measured_words / contig.measured_words,
        })
    # pool occupancy: N_SHARED requests sharing a SHARED_PREFIX-token system
    # prompt; refcounted blocks charge the prefix once
    bw = kv.block_words(cfg, BLOCK)
    alloc = kv.BlockAllocator(num_blocks)
    rng = np.random.default_rng(0)
    shared = rng.integers(1, cfg.vocab_size, size=SHARED_PREFIX)
    naive_blocks = 0
    for i in range(N_SHARED):
        toks = list(shared) + list(rng.integers(1, cfg.vocab_size, size=4 + i))
        need = -(-len(toks) // BLOCK)
        naive_blocks += need
        blocks = []
        for key in kv.prefix_chain(toks, BLOCK):
            hit = alloc.lookup(key)
            if hit is not None:
                blocks.append(alloc.ref(hit))
                continue
            b = alloc.alloc()
            alloc.register(b, key)
            blocks.append(b)
        while len(blocks) < need:
            blocks.append(alloc.alloc())
    records.append({
        "name": "pool/shared_prefix",
        "requests": N_SHARED,
        "prefix_tokens": SHARED_PREFIX,
        "shared_pool_words": alloc.used_words(bw),
        "naive_pool_words": naive_blocks * bw,
        "shared_over_naive_ratio":
            alloc.used_words(bw) / (naive_blocks * bw),
    })
    return records


def run(csv_rows: list) -> None:
    for r in sweep():
        if r["name"].startswith("decode/"):
            csv_rows.append((
                f"serving/words/{r['name']}", "0",
                f"paged={r['paged_words']:.3e}w "
                f"({r['paged_bound_ratio']:.2f}x bound) "
                f"contig={r['contig_words']:.3e}w "
                f"paged/contig={r['paged_over_contig_ratio']:.2f}x"))
        else:
            csv_rows.append((
                f"serving/{r['name']}", "0",
                f"shared={r['shared_pool_words']:.3e}w "
                f"naive={r['naive_pool_words']:.3e}w "
                f"({r['shared_over_naive_ratio']:.2f}x)"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", default="BENCH_serving.json", metavar="PATH",
                    help="write the sweep's records to PATH")
    args = ap.parse_args(argv)
    records = sweep()
    bad = []
    for r in records:
        if r["name"].startswith("decode/"):
            print(f"{r['name']:18s} paged={r['paged_words']:.3e}w "
                  f"({r['paged_bound_ratio']:.2f}x bound) "
                  f"contig={r['contig_words']:.3e}w "
                  f"gap={r['paged_over_contig_ratio']:.2f}x")
            if r["paged_words"] >= r["contig_words"]:
                bad.append(f"{r['name']}: paged moves >= contiguous words")
            if r["paged_bound_ratio"] > 1.2:
                bad.append(f"{r['name']}: measured decode words "
                           f"{r['paged_bound_ratio']:.2f}x off the "
                           f"attention bound")
        else:
            print(f"{r['name']:18s} shared={r['shared_pool_words']:.3e}w "
                  f"naive={r['naive_pool_words']:.3e}w")
            if r["shared_pool_words"] >= r["naive_pool_words"]:
                bad.append(f"{r['name']}: prefix sharing saves no pool words")
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
