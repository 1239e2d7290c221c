"""Share of the traced window in which the device was idle while the
engine's host loop was inside a decode round (``serve/step``: table upload
and decode dispatch, sampling dispatch, the read of the step's tokens,
bookkeeping), in %. Device times are first put on the host's clock
(``engine_reading.host_offset_ns``); standard error splits the idle time
over the round's children."""

from benchmarks.chip import engine_reading as er


def read(r):
    return er.idle_share(r, "serve/step", ("serve/decode", "serve/sample",
                                           "serve/read", "serve/record"))
