"""Share of the measured window in which no op ran on the device, from the
profiler trace (``bench/trace_reduce.py``); on several chips, the largest
over the chips.  Moves ``samples_per_s``."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not trace["idle_share"]:
        return None
    return 100.0 * max(trace["idle_share"].values())
