"""``idle.serve``: the shared reader, ``benchmarks/chip/metrics/idle.serve.py``."""

import os

from benchmarks.chip import spec

read = spec.load_module(os.path.join(spec.HERE, "metrics",
                                     "idle.serve.py")).read
