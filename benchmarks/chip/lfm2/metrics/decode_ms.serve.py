"""``decode_ms.serve``: the shared reader, ``benchmarks/chip/metrics/decode_ms.serve.py``."""

import os

from benchmarks.chip import spec

read = spec.load_module(os.path.join(spec.HERE, "metrics",
                                     "decode_ms.serve.py")).read
