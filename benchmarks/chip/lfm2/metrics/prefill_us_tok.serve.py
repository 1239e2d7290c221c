"""``prefill_us_tok.serve``: the shared reader, ``benchmarks/chip/metrics/prefill_us_tok.serve.py``."""

import os

from benchmarks.chip import spec

read = spec.load_module(os.path.join(spec.HERE, "metrics",
                                     "prefill_us_tok.serve.py")).read
