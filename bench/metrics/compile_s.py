"""Seconds of compilation during set-up: tracing, lowering and backend
compilation or persistent-cache load, as ``jax.monitoring`` reports them.
Moves ``setup_s``."""


def read(ctx):
    lo, hi = ctx["setup_bounds"]
    return sum(dur for _, start, dur in ctx["compile_events"]
               if lo <= start <= hi)
