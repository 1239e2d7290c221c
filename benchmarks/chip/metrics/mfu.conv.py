"""Whole-pass share of the chip's peak, in %: the FLOPs the traced passes'
convs require over the traced passes' time on the host clock times peak
FLOP/s."""


def read(r):
    info = r.info
    if "layers" not in info or info["elapsed_s"] <= 0:
        return None
    flops = sum(f for _, f, _ in info["layers"]) * info["passes"]
    return 100.0 * flops / (info["elapsed_s"] * r.peaks.flops_bf16)
