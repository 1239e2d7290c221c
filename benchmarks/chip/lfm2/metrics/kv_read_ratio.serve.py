"""``kv_read_ratio.serve``: the shared reader, ``benchmarks/chip/metrics/kv_read_ratio.serve.py``."""

import os

from benchmarks.chip import spec

read = spec.load_module(os.path.join(spec.HERE, "metrics",
                                     "kv_read_ratio.serve.py")).read
