"""Share of the conv programs' device time spent outside the Pallas custom
calls (the XLA layout passes around the kernel: space-to-depth, pads,
crops, transposes), in %."""


def read(r):
    if "layers" not in r.info:
        return None
    total = r.trace.op_seconds()
    if total <= 0:
        return None
    return 100.0 * r.trace.op_seconds(custom_call=False) / total
