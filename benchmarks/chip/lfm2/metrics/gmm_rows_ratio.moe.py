"""``gmm_rows_ratio.moe``: the shared reader, ``benchmarks/chip/metrics/gmm_rows_ratio.moe.py``."""

import os

from benchmarks.chip import spec

read = spec.load_module(os.path.join(spec.HERE, "metrics",
                                     "gmm_rows_ratio.moe.py")).read
