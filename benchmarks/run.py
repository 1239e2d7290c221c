"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--json out.json] [--only fig4]

Prints ``name,us_per_call,derived`` CSV:
  fig2/*     - paper Fig 2 (single-processor volumes vs bound, mixed precision)
  fig3/*     - paper Fig 3 (parallel volumes vs bound)
  fig4/*     - paper Fig 4 / §5 (LP tiling vs vendor tiling, GEMMINI + TPU)
  plan/*     - unified-planner solve times (repro.plan)
  kernel/*   - Pallas/XLA kernel micro-timings
  conv/*     - measured HBM words: LP-tiled conv vs Im2Col vs Thm 2.1 bound
  autotune/* - measured frontier search: tuned vs analytic plan wall time
  dist/*     - measured inter-device words: halo-exchange conv vs all-gather
               vs the Thm 2.2/2.3 bound (live rows need the 8-device mesh)
  serving/*  - paged vs contiguous decode words, shared-prefix pool words
  roofline/* - §Roofline rows from the dry-run artifacts

``--json`` additionally writes the rows as a machine-readable list of
``{"name", "us_per_call", "derived"}`` objects so successive PRs can diff the
perf trajectory.
"""

from __future__ import annotations

import argparse
import json
import traceback


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also dump rows as JSON to PATH")
    ap.add_argument("--only", default=None,
                    help="comma-separated substring filter on module names "
                         "(e.g. 'fig4' or 'fig4,serving')")
    args = ap.parse_args(argv)

    from repro.launch.devices import use_compile_cache

    use_compile_cache()
    from . import (autotune_bench, conv_bench, dist_bench,
                   fig2_single_processor, fig3_parallel, fig4_gemmini_tiling,
                   kernel_bench, roofline_table, serving_bench)

    only = [s for s in (args.only or "").split(",") if s]
    rows = [("name", "us_per_call", "derived")]
    for mod in (fig2_single_processor, fig3_parallel, fig4_gemmini_tiling,
                kernel_bench, conv_bench, autotune_bench, dist_bench,
                serving_bench, roofline_table):
        if only and not any(s in mod.__name__ for s in only):
            continue
        try:
            mod.run(rows)
        except Exception:
            traceback.print_exc()
            rows.append((f"{mod.__name__}/ERROR", "0", "see stderr"))
    for r in rows:
        print(",".join(str(x) for x in r))
    if args.json:
        header, body = rows[0], rows[1:]
        with open(args.json, "w") as f:
            json.dump([dict(zip(header, (str(x) for x in r))) for r in body],
                      f, indent=1)
        print(f"wrote {len(body)} rows to {args.json}")


if __name__ == "__main__":
    main()
