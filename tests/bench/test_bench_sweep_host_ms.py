"""``sweep_host_ms``: the median host time of the window's ``sweep.run``
spans, or nothing to read where the program records none, or where their
count differs from the window's calls."""

import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchtiny import BENCH

import cells

METRICS = os.path.join(BENCH, "metrics")


def _read(bounds, calls):
    return cells.read_metric(METRICS, "sweep_host_ms",
                             {"window_bounds": bounds, "calls": calls})


def _window(calls: int) -> tuple[float, float]:
    """Bounds of a window of ``calls`` tiny grid calls, each blocked on."""
    from repro.envs import GarnetMDP, as_param_sampler
    from repro.experiments import SweepSpec, run_sweep
    env = GarnetMDP(num_states=8, num_actions=2, branching=2, seed=0)
    v = np.asarray(env.cost_vector(), np.float32)
    spec = SweepSpec(modes=("practical",), lambdas=(0.1, 1.0), seeds=(0,),
                     rhos=(0.99,), eps=0.5, num_iterations=4, num_agents=4,
                     trace="summary")
    sampler = as_param_sampler(env, v, num_agents=4, num_samples=8)
    args = (spec, sampler, jnp.zeros(8))
    jax.block_until_ready(run_sweep(*args))          # compile outside
    lo = time.perf_counter()
    for _ in range(calls):
        jax.block_until_ready(run_sweep(*args))
    return lo, time.perf_counter()


def test_reads_the_median_length_of_the_window_run_spans():
    from repro import spans
    bounds = _window(3)
    runs = [s for s in spans.recorded() if s.name == "sweep.run"
            and bounds[0] <= s.start <= bounds[1]]
    assert len(runs) == 3
    value = _read(bounds, 3)
    assert value > 0
    assert value == 1e3 * statistics.median(s.end - s.start for s in runs)


def test_nothing_to_read_where_the_count_is_not_the_call_count():
    bounds = _window(2)
    assert _read(bounds, 2) > 0
    assert _read(bounds, 3) is None


def test_nothing_to_read_without_spans_in_the_window():
    _window(1)
    before = time.perf_counter() - 3600.0
    assert _read((before, before + 1.0), 1) is None


def test_nothing_to_read_where_the_program_has_no_spans(monkeypatch):
    """A program without ``repro.spans`` (a checkout older than the
    module) gives nothing to read, and no error."""
    import repro
    bounds = _window(1)
    monkeypatch.delattr(repro, "spans")
    monkeypatch.setitem(sys.modules, "repro.spans", None)
    assert _read(bounds, 1) is None
