"""Share of the decode rows that served a request, in %: the engine's own
counters of the traced job, occupied slots summed over the decode rounds
over the pool size summed likewise (``ServeStats.row_steps_active /
row_steps_total``). A count; it repeats exactly for a job's lengths."""

from benchmarks.chip import engine_reading as er


def read(r):
    stats = er.engine_stats()
    if stats is None or not stats.row_steps_total:
        return None
    return 100.0 * stats.row_steps_active / stats.row_steps_total
