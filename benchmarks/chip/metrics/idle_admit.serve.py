"""Share of the traced window in which the device was idle while the
engine's host loop was admitting a request (``serve/admit``: prefill
dispatch and the finite check of its logits, the cache insert, the first
token's sampling and read), in %. Device times are first put on the host's
clock (``engine_reading.host_offset_ns``); standard error splits the idle
time over the admission's children."""

from benchmarks.chip import engine_reading as er


def read(r):
    return er.idle_share(r, "serve/admit", ("serve/prefill", "serve/insert",
                                            "serve/first_token"))
