"""Host time of one grid call inside ``run_sweep``, in milliseconds: the
median length of the program's ``sweep.run`` spans (``repro.spans``) that
start inside the measured window, one per call.  It covers planning,
dispatch up to the return of the unfinished arrays, and finalizing; the
device's work after that is not in it.  Nothing to read where the program
records no such spans, or where their count is not the window's call
count.  Moves ``samples_per_s``."""

import statistics


def read(ctx):
    try:
        from repro import spans
    except ImportError:
        return None
    lo, hi = ctx["window_bounds"]
    runs = [s for s in spans.recorded()
            if s.name == "sweep.run" and lo <= s.start <= hi]
    if not runs or len(runs) != ctx["calls"]:
        return None
    return 1e3 * statistics.median([s.end - s.start for s in runs])
