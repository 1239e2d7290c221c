"""Device time of one execution of the engine's decode program, in ms: the
program's module events in the trace (``jit_decode``), summed and divided
by their count."""


def read(r):
    runs = r.trace.module_seconds("jit_decode")
    return None if not runs else 1e3 * sum(runs) / len(runs)
