"""Backend compilations (persistent-cache loads included) that started
inside the measured window, counted by ``jax.monitoring``; should be 0.
Moves ``samples_per_s``."""


def read(ctx):
    lo, hi = ctx["window_bounds"]
    return float(sum(1 for name, start, _ in ctx["compile_events"]
                     if name == ctx["backend_compile_event"]
                     and lo <= start <= hi))
