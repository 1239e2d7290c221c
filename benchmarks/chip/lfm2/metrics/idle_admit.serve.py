"""``idle_admit.serve``: the shared reader, ``benchmarks/chip/metrics/idle_admit.serve.py``."""

import os

from benchmarks.chip import spec

read = spec.load_module(os.path.join(spec.HERE, "metrics",
                                     "idle_admit.serve.py")).read
