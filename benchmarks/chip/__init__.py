"""Chip benchmark: cells of ``BENCHMARK.json`` run on the TPU, one per process.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The harness is driven by data. A cell names a configuration (a JSON file
under ``configs/``), a traffic mix (a JSON file under ``traffic/``) and, in
``BENCHMARK.json``, the metrics it reports. A configuration's ``kind`` names
the module under ``kinds/`` that drives the program for it; each per-layer
metric is a reader under ``metrics/<name>.py``. A later cell, mix or metric
is a new file and a new entry; no existing file changes.

The yardstick lives here and nowhere in the program: traffic generation
(``traffic.py``), the trace reduction (``trace.py``), the chip peaks
(``peaks.py``), the work functions (``work.py``) and the plain references
with their controls (``reference/``). From the program the benchmark takes
only the system under test, its dispatch counter and its kernel names.
"""
