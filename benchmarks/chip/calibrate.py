"""Readings that the correctness limits are set from, many seeds in one process.

    python3 benchmarks/chip/calibrate.py --workload <cell> --seconds <s> --seeds 1 2 3 ...

For each seed: the cell's set-up, a short window at the cell's own load
(at least one whole job or pass), the program's checked numbers, then the
control's reading of the same numbers: the reference in a precision one step
below the configuration's, put in the program's place at the same size. The
driver's runs never run the control. One JSON line per seed on standard
output. Programs compile once and serve every seed.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from benchmarks.chip import run, spec

    cell = spec.resolve(args.workload, ROOT)
    dev = run.device_or_none(cell, chip=True)
    if dev is None:
        return 2
    run.use_cache()
    kind = cell.kind_module()
    for seed in args.seeds:
        t0 = time.perf_counter()
        system = kind.System(cell, seed, run.say)
        system.setup()
        system.window(args.seconds)
        system.release()
        checks = system.check()
        control = system.control()
        print(json.dumps({
            "workload": cell.name, "seed": seed,
            "program": {n: v for n, v, _ in checks},
            "limit": {n: lim for n, _, lim in checks},
            "control": dict(control),
            "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
