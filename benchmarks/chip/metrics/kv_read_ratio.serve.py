"""KV positions the paged decode attention streams over those the live
rows attend to: the engine's own counters of the traced job
(``ServeStats.kv_read / kv_live``). Every row of the pool walks the table
width of the deepest active row, live or dead. A count; it repeats exactly
for a job's lengths."""

from benchmarks.chip import engine_reading as er


def read(r):
    stats = er.engine_stats()
    if stats is None or not stats.kv_live:
        return None
    return stats.kv_read / stats.kv_live
