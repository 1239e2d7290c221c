"""Whole-job share of the chip's peak, in %: the forward FLOPs the traced
job's tokens require (``work.lm_job_flops``: no padding, no recompute) over
the job's time on the host clock times peak FLOP/s."""


def read(r):
    info = r.info
    if "job_flops" not in info or info["job_s"] <= 0:
        return None
    return 100.0 * info["job_flops"] / (info["job_s"] * r.peaks.flops_bf16)
