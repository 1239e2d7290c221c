"""``mfu.serve``: the shared reader, ``benchmarks/chip/metrics/mfu.serve.py``."""

import os

from benchmarks.chip import spec

read = spec.load_module(os.path.join(spec.HERE, "metrics",
                                     "mfu.serve.py")).read
