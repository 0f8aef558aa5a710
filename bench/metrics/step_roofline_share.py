"""Least time of one inner step over its measured wall time, in percent.

The least time is max(ops / peak FLOP/s, bytes / peak bytes/s) over the
chips the cell uses, with ops and bytes the algorithm's necessary work from
``bench/work/<feature_kind>.py``; the wall time per inner step is the window
over (grid calls x N).  Moves ``samples_per_s``."""


def read(ctx):
    peaks, work = ctx.get("peaks"), ctx["work_per_step"]
    if not peaks or not ctx["calls"]:
        return None
    chips = ctx["chips"]
    least = max(work["flops"] / (peaks["flops_per_s"] * chips),
                work["bytes"] / (peaks["hbm_bytes_per_s"] * chips))
    per_step = ctx["window_s"] / (ctx["calls"] * ctx["num_iterations"])
    share = 100.0 * least / per_step
    return share if share > 0 else None
