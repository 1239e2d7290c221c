"""Share of the MXU work the conv kernels execute that the convs require, in
%: the cell's conv FLOPs (``work.conv_flops``) over the MXU FLOPs of the
chosen kernels' launch geometry, padding included (the program's own
counter, ``DispatchDecision.mxu_flops``, recorded while the cell's programs
were traced in set-up). A count; it repeats exactly. Nothing to read where
a decision does not count them."""


def read(r):
    info = r.info
    flops = [getattr(d, "mxu_flops", None) for d in info.get("decisions", ())]
    if "layers" not in info or not flops or None in flops:
        return None
    return 100.0 * sum(f for _, f, _ in info["layers"]) / sum(flops)
