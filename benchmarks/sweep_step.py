"""Sweep-step microbenchmark: reference vs fused vs megastep step backends.

Stages, swept over (m, T, n):

* ``gain_family`` — the per-step gain-family evaluation
  (``gain_dispatch.mode_gains``), the exact stage the fused backend
  rewrites.  For ``gain_backend="reference"`` this compares three
  independent vmapped jnp passes against the shared-projection family; for
  ``"pallas"`` it compares the m-per-agent vmapped kernel dispatches
  against ONE batched-agent ``gain_family_stats`` call (the call-count
  reduction is the headline: off-TPU the kernels run interpreted, so the
  ratio directly measures dispatch count, which is also what the TPU grid
  sees).  ``step_backend="megastep"`` is not a separate row here — for
  gain-only callers it takes the fused path by construction.
* ``full_step`` — the whole gated-SGD inner step (sampling + gradients +
  gains + trigger + server update) via an N-iteration ``gated_sgd_core``
  scan on a synthetic linear problem, reported per step.  The megastep
  column is the tentpole: gains + trigger + gated update leave as ONE
  kernel (agent block MEGASTEP_BLOCK_M=32 vs the family kernel's 8, so it
  also runs a quarter of the grid programs), closing the Amdahl gap the
  fused rows leave open.
* ``sweep_step`` — R runs vmapped through the full step (the sweep
  engine's hot loop), reported per run-step.  On the pallas path the
  megastep rows ride the kernel's native run-grid axis (custom_vmap):
  R x m agents in one program per step instead of a kernel dispatch per
  run.

Rows carry ``speedup_vs_reference`` (reference time / this time, same stage
and gain backend).  The committed non-smoke JSON
(experiments/bench/sweep_step.json) is the perf baseline later PRs gate
against.  The gate that must hold: megastep > fused > 1x at every m >= 32
shape on the PALLAS gain backend full step — that is the path the fusion
exists for.  The pure-XLA rows are informational: XLA already fuses the
jnp reference inside one jitted program, so those ratios hover around 1
and swing ±20-30% with this container's 2-core timing noise.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import gain_dispatch
from repro.core.algorithm1 import gated_sgd_core

EPS = 0.5

# (m, T, n) grid: m is the axis the batched-agent kernel tiles; T, n move
# the arithmetic intensity of the projection.
GAIN_SHAPES = [(8, 64, 32), (32, 64, 32), (128, 64, 32), (32, 256, 64),
               (128, 256, 64)]
# the interpreted per-agent kernel pays ~m dispatches per call, so the
# pallas pair is measured at moderate m to keep the suite seconds-scale
PALLAS_SHAPES = [(32, 64, 32), (128, 64, 32)]
STEP_SHAPES = [(32, 64, 32), (128, 64, 32)]
SWEEP_RUNS = 4
SWEEP_SHAPES = [(32, 64, 32)]
SMOKE_GAIN_SHAPES = [(8, 16, 8), (32, 16, 8)]
SMOKE_PALLAS_SHAPES = [(8, 16, 8)]
SMOKE_STEP_SHAPES = [(8, 16, 8)]
SMOKE_SWEEP_SHAPES = [(8, 16, 8)]

STEP_BACKENDS = ("reference", "fused", "megastep")


def _median_time(fn, *args, reps: int = 20, trials: int = 7):
    """Median-of-trials wall time (us) — the 2-core container is noisy."""
    out = fn(*args)
    jax.block_until_ready(out)
    ts = []
    for _ in range(trials):
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(*args)
        jax.block_until_ready(out)
        ts.append((time.perf_counter() - t0) / reps * 1e6)
    return float(np.median(ts))


def _inputs(m: int, T: int, n: int):
    rng = np.random.default_rng(0)
    grads = jnp.asarray(rng.normal(size=(m, n)).astype(np.float32))
    phi = jnp.asarray(rng.normal(size=(m, T, n)).astype(np.float32))
    gj = jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
    pm = jnp.asarray(rng.normal(size=(n, n)).astype(np.float32))
    return grads, phi, gj, (pm + pm.T) / 2


def _bench_gain_family(m, T, n, gain_backend, step_backend, reps, trials):
    grads, phi, gj, pm = _inputs(m, T, n)
    fn = jax.jit(lambda mid, g, p: gain_dispatch.mode_gains(
        mid, g, p, EPS, gj, pm, backend=gain_backend,
        step_backend=step_backend))
    return _median_time(fn, 1, grads, phi, reps=reps, trials=trials)


def _make_sample_all(T, n, w_true):
    def sample_all(rngs):
        def one(r):
            kf, kn = jax.random.split(r)
            phi = jax.random.normal(kf, (T, n))
            targets = phi @ w_true + 0.1 * jax.random.normal(kn, (T,))
            return phi, targets
        return jax.vmap(one)(rngs)
    return sample_all


def _core_runner(m, T, n, gain_backend, step_backend, num_iterations):
    rng = np.random.default_rng(1)
    w_true = jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
    sample_all = _make_sample_all(T, n, w_true)
    thresholds = jnp.full((num_iterations,), 1e-3, jnp.float32)

    def run(key, mode_id=gain_dispatch.MODE_PRACTICAL):
        return gated_sgd_core(
            key, jnp.zeros((n,)), mode_id, thresholds,
            0.5, sample_all, EPS, m, trace="summary",
            gain_backend=gain_backend, step_backend=step_backend)
    return run


def _bench_full_step(m, T, n, gain_backend, step_backend, num_iterations,
                     reps, trials):
    """One gated-SGD inner run on a synthetic linear problem, us per step."""
    fn = jax.jit(_core_runner(m, T, n, gain_backend, step_backend,
                              num_iterations))
    us_total = _median_time(fn, jax.random.key(0), reps=reps, trials=trials)
    return us_total / num_iterations


def _bench_sweep_step(m, T, n, gain_backend, step_backend, num_iterations,
                      runs, reps, trials):
    """R runs vmapped through the full step, us per (run, step).

    The mode id rides in as per-run DATA (like the sweep engine feeds it),
    which is also what keeps the reference path's optimization_barrier out
    of the vmapped program.
    """
    run = _core_runner(m, T, n, gain_backend, step_backend, num_iterations)
    fn = jax.jit(lambda keys, mids: jax.vmap(run)(keys, mids))
    keys = jax.random.split(jax.random.key(0), runs)
    mids = jnp.full((runs,), gain_dispatch.MODE_PRACTICAL, jnp.int32)
    us_total = _median_time(fn, keys, mids, reps=reps, trials=trials)
    return us_total / (num_iterations * runs)


def run(smoke: bool = False) -> list[dict]:
    reps, trials = (3, 3) if smoke else (20, 7)
    gain_shapes = SMOKE_GAIN_SHAPES if smoke else GAIN_SHAPES
    pallas_shapes = SMOKE_PALLAS_SHAPES if smoke else PALLAS_SHAPES
    step_shapes = SMOKE_STEP_SHAPES if smoke else STEP_SHAPES
    sweep_shapes = SMOKE_SWEEP_SHAPES if smoke else SWEEP_SHAPES
    num_iterations = 5 if smoke else 30
    step_reps = max(reps // 4, 2)
    rows = []

    for backend, shapes in (("reference", gain_shapes),
                            ("pallas", pallas_shapes)):
        for (m, T, n) in shapes:
            ref = _bench_gain_family(m, T, n, backend, "reference",
                                     reps, trials)
            fus = _bench_gain_family(m, T, n, backend, "fused", reps, trials)
            for sb, us in (("reference", ref), ("fused", fus)):
                rows.append(dict(
                    bench="sweep_step", stage="gain_family", m=m, T=T, n=n,
                    gain_backend=backend, step_backend=sb, us_per_call=us,
                    speedup_vs_reference=ref / us))

    for backend in ("reference", "pallas"):
        for (m, T, n) in step_shapes:
            times = {sb: _bench_full_step(m, T, n, backend, sb,
                                          num_iterations, step_reps, trials)
                     for sb in STEP_BACKENDS}
            for sb in STEP_BACKENDS:
                rows.append(dict(
                    bench="sweep_step", stage="full_step", m=m, T=T, n=n,
                    gain_backend=backend, step_backend=sb,
                    us_per_call=times[sb],
                    speedup_vs_reference=times["reference"] / times[sb]))

    for backend in ("reference", "pallas"):
        for (m, T, n) in sweep_shapes:
            times = {sb: _bench_sweep_step(m, T, n, backend, sb,
                                           num_iterations, SWEEP_RUNS,
                                           step_reps, trials)
                     for sb in STEP_BACKENDS}
            for sb in STEP_BACKENDS:
                rows.append(dict(
                    bench="sweep_step", stage="sweep_step", m=m, T=T, n=n,
                    runs=SWEEP_RUNS, gain_backend=backend, step_backend=sb,
                    us_per_call=times[sb],
                    speedup_vs_reference=times["reference"] / times[sb]))
    return rows
