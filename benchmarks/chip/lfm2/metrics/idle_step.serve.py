"""``idle_step.serve``: the shared reader, ``benchmarks/chip/metrics/idle_step.serve.py``."""

import os

from benchmarks.chip import spec

read = spec.load_module(os.path.join(spec.HERE, "metrics",
                                     "idle_step.serve.py")).read
