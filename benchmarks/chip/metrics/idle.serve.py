"""Share of the traced window in which no operation ran on the device, in %."""


def read(r):
    w = r.trace.window_s()
    return None if w <= 0 else 100.0 * (1.0 - r.trace.busy_s() / w)
