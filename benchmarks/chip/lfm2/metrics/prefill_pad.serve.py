"""``prefill_pad.serve``: the shared reader, ``benchmarks/chip/metrics/prefill_pad.serve.py``."""

import os

from benchmarks.chip import spec

read = spec.load_module(os.path.join(spec.HERE, "metrics",
                                     "prefill_pad.serve.py")).read
