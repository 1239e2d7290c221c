"""Share of the prefilled positions that are bucket padding, in %: the
engine's own counters of the traced job, ``(prefill_padded -
prefill_real) / prefill_padded`` (``ServeStats``). A count; it repeats
exactly for a job's lengths."""

from benchmarks.chip import engine_reading as er


def read(r):
    stats = er.engine_stats()
    if stats is None or not stats.prefill_padded:
        return None
    return 100.0 * (stats.prefill_padded - stats.prefill_real) \
        / stats.prefill_padded
