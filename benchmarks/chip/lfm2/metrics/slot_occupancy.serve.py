"""``slot_occupancy.serve``: the shared reader, ``benchmarks/chip/metrics/slot_occupancy.serve.py``."""

import os

from benchmarks.chip import spec

read = spec.load_module(os.path.join(spec.HERE, "metrics",
                                     "slot_occupancy.serve.py")).read
