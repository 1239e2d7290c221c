"""Share of the conv2d kernel's roofline reached, in %.

The least time the chip could take for the traced passes (per layer the
larger of FLOPs over peak FLOP/s and compulsory bytes over peak bandwidth,
``work.roofline_s``) over the device time of the Pallas custom calls in the
trace. Says on standard error which bound sets each layer.
"""

from benchmarks.chip import work


def read(r):
    info = r.info
    kernel_s = r.trace.op_seconds(custom_call=True)
    if "layers" not in info or kernel_s <= 0:
        return None
    least = 0.0
    for name, flops, nbytes in info["layers"]:
        t, bound = work.roofline_s(flops, nbytes, r.peaks)
        r.say(f"roofline {name}: {t * 1e3:.4f} ms per call, {bound} bound")
        least += t
    return 100.0 * least * info["passes"] / kernel_s
