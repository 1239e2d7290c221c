"""Rows the grouped matmul computes over the (token, expert) pairs placed
on the experts held here: the engine's own counters of the traced job
(``ServeStats.moe_rows / moe_held``, prefill and decode together). Each
held expert's rows are padded to the kernel's row tile. A count; it repeats
for a job's routing."""

from benchmarks.chip import engine_reading as er


def read(r):
    stats = er.engine_stats()
    held = sum(getattr(stats, "moe_held", {}).values()) if stats else 0
    if not held:
        return None
    return sum(stats.moe_rows.values()) / held
