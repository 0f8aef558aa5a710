"""Chip smoke test: the sweep engine's main path on a TPU.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # only the run-axis-sharded sweep

One process does everything, so it alone holds the chip.  Phases on one
chip, all at fleet scale (GARNET MDP with 128 states, so n = 128 tabular
features; m = 1024 agents; T = 256 samples per agent per step; 16 runs =
2 trigger modes x 4 lambdas x 2 seeds):

1. sweep   — ``run_sweep`` three times, with the step paths set on the
             ``SweepSpec``: reference/reference (pure XLA), pallas/fused
             (the family kernel) and pallas/megastep (the whole-step
             kernel).  All results must be finite; both kernel paths must
             agree with the reference path within ``COMM_ATOL`` /
             ``J_RTOL``.  Compile (set-up) and steady-state seconds are
             printed for each path.
2. store   — the reference result goes into a ``SweepStore`` under the
             output directory; ``best_lambda_batch`` answered through
             ``StoreRegistry.table`` must equal ``best_lambda`` on the
             entry's own ``tradeoff_curve``.
3. resume  — the same spec through ``run_sweep_resumable`` in two
             checkpointed segments must agree with ``run_sweep``.

``--chips 4`` runs the same grid with the run axis sharded over
``make_sweep_mesh(4)``, asserts the output lives on 4 devices, and
compares it with the unsharded run on device 0; nothing else runs.

Data comes from seeds; the script writes only under ``--out``.  It exits
non-zero, printing no result line, when JAX finds no TPU.  The last line
of standard output is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core.algorithm1 import ParamSampler  # noqa: E402
from repro.envs.garnet import GarnetMDP  # noqa: E402
from repro.experiments import (  # noqa: E402
    StoreRegistry,
    SweepSpec,
    SweepStore,
    best_lambda,
    finalize_sweep,
    plan_sweep,
    run_sweep,
    run_sweep_resumable,
    store_result,
    tradeoff_curve,
)
from repro.experiments.sweep import exec_plan  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_sweep_mesh  # noqa: E402

# Fleet-scale workload: one step's features are m*T*n*4 B = 134 MB per run,
# 2.1 GB for the 16 vmapped runs.
SIZES = dict(num_states=128, num_agents=1024, num_samples=256,
             num_iterations=150)
MODES = ("theoretical", "practical")
# Spans comm rates from ~0.8 down to ~0.01 (theoretical trigger).
LAMBDAS = (1e-3, 1e-2, 1e-1, 1.0)
SEEDS = (0, 1)
# Tabular features under uniform visits give Phi = I/S: eps = S/64
# contracts the expected error by 1 - 2 eps/S = 0.969 per step; rho sits
# above the Assumption-3 floor 0.969^2.
EPS_PER_STATE = 1.0 / 64.0
RHO = 0.99
BUDGETS = (0.05, 0.2, 0.5, 0.9)

# (gain_backend, step_backend) of the three step paths.
PATHS = (("reference", "reference"), ("pallas", "fused"),
         ("pallas", "megastep"))

# Agreement between step paths.  On a TPU, XLA's default precision feeds
# f32 matmuls to the MXU as bf16, while the megastep kernel forms the gated
# update in f32; and a gain within ~1e-5 of its threshold can flip one
# transmit decision.  At lambda = 1 only 2-8 of the 1024 agents transmit
# per step, so a few flips there moved J by 7.2% on a v5e (comm rates by
# 5e-5); with both paths at "highest" precision they agreed to 6e-4.
# COMM_ATOL bounds the comm rate of a run (a fraction of 153,600
# decisions) and J_RTOL its final objective.  Both stay far below what
# separates neighbouring lambdas of the grid (comm rates ~0.25 apart, J a
# factor ~10), so a wrong kernel still fails.
COMM_ATOL = 0.02
J_RTOL = 0.2


def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def make_workload(num_states: int, num_agents: int, num_samples: int):
    """GARNET instance 0 with a homogeneous fleet; w0 = 0."""
    env = GarnetMDP(num_states=num_states)
    w0 = jnp.zeros(num_states)
    sampler = ParamSampler(fn=env.sampler_fn(num_samples),
                           params=env.agent_params(w0, num_agents))
    return sampler, w0, env.vfa_problem(np.zeros(num_states))


def make_spec(num_states: int, num_agents: int, num_iterations: int,
              gain_backend: str, step_backend: str,
              chunk_size=None) -> SweepSpec:
    return SweepSpec(
        modes=MODES, lambdas=LAMBDAS, seeds=SEEDS, rhos=(RHO,),
        eps=EPS_PER_STATE * num_states, num_iterations=num_iterations,
        num_agents=num_agents, trace="summary", gain_backend=gain_backend,
        step_backend=step_backend, chunk_size=chunk_size)


def _timed(fn):
    """(result, first-call seconds, steady-state seconds), each call
    ended by ``block_until_ready`` on every output leaf."""
    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    t1 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, t1 - t0, time.perf_counter() - t1


def _finite(result) -> bool:
    return all(bool(np.all(np.isfinite(np.asarray(x))))
               for x in jax.tree.leaves(result.trace))


def compare(got, want) -> dict:
    """Largest per-run |d comm_rate| and relative |d J| of two results."""
    gc, wc = np.asarray(got.comm_rate), np.asarray(want.comm_rate)
    gj, wj = np.asarray(got.j_final), np.asarray(want.j_final)
    return {"comm_max_abs": float(np.max(np.abs(gc - wc))),
            "j_max_rel": float(np.max(np.abs(gj - wj) / np.abs(wj)))}


def agrees(diff: dict) -> bool:
    return diff["comm_max_abs"] <= COMM_ATOL and diff["j_max_rel"] <= J_RTOL


def phase_sweep(sizes: dict, device: str, log=print) -> tuple[dict, list]:
    """Every step path once at full size; returns (results, failures)."""
    sampler, w0, problem = make_workload(
        sizes["num_states"], sizes["num_agents"], sizes["num_samples"])
    results, failures = {}, []
    for gb, sb in PATHS:
        spec = make_spec(sizes["num_states"], sizes["num_agents"],
                         sizes["num_iterations"], gb, sb)
        res, first_s, steady_s = _timed(
            lambda: run_sweep(spec, sampler, w0, problem=problem))
        results[(gb, sb)] = res
        log(f"sweep {gb}/{sb} on {device}: first_call_s={first_s:.3f} "
            f"(compile, set-up) steady_s={steady_s:.3f} "
            f"runs={res.comm_rate.size} "
            f"comm_mean={float(np.mean(res.comm_rate)):.6f} "
            f"j_mean={float(np.mean(res.j_final)):.6f}")
        if not _finite(res):
            failures.append(f"sweep {gb}/{sb}: non-finite output")
    ref = results[PATHS[0]]
    for path in PATHS[1:]:
        diff = compare(results[path], ref)
        log(f"agreement {path[0]}/{path[1]} vs reference: "
            f"comm_max_abs={diff['comm_max_abs']:.3e} (tol {COMM_ATOL}) "
            f"j_max_rel={diff['j_max_rel']:.3e} (tol {J_RTOL})")
        if not agrees(diff):
            failures.append(f"sweep {path}: disagrees with reference {diff}")
    return results, failures


def phase_store(result, sizes: dict, out_dir: str, log=print) -> list:
    """Persist, then query through the registry and check the answer
    against the scalar query on the entry's own tradeoff curve."""
    spec = make_spec(sizes["num_states"], sizes["num_agents"],
                     sizes["num_iterations"], *PATHS[0])
    store = SweepStore(os.path.join(out_dir, "store"))
    h = store_result(store, spec, result)
    entry = store.get(h, verify=True)
    failures = []
    if not np.array_equal(entry.arrays["trace/comm_rate"],
                          np.asarray(result.comm_rate)):
        failures.append("store: comm_rate did not round-trip")
    table = StoreRegistry(store.root).table(h)
    for mode in MODES:
        got = table.best_lambda_batch(BUDGETS, mode=mode)
        curve = tradeoff_curve(entry, mode=mode)
        want = [best_lambda(curve, b) for b in BUDGETS]
        log(f"store {mode}: best_lambda per budget "
            + " ".join(f"{b}->{r['lam']:.4g}" for b, r in zip(BUDGETS, got)))
        if got != want:
            failures.append(f"store {mode}: registry {got} != curve {want}")
    return failures


def phase_resume(reference, sizes: dict, out_dir: str, log=print) -> list:
    """The reference spec in two checkpointed segments vs ``run_sweep``."""
    sampler, w0, problem = make_workload(
        sizes["num_states"], sizes["num_agents"], sizes["num_samples"])
    runs = int(reference.comm_rate.size)
    spec = make_spec(sizes["num_states"], sizes["num_agents"],
                     sizes["num_iterations"], *PATHS[0],
                     chunk_size=runs // 2)
    segments = []
    t0 = time.perf_counter()
    res = run_sweep_resumable(
        spec, sampler, w0, problem=problem,
        store_dir=os.path.join(out_dir, "chunks"),
        on_chunk=lambda i, total, restored: segments.append(i))
    jax.block_until_ready(res.comm_rate)
    diff = compare(res, reference)
    log(f"resume: segments={len(segments)} wall_s="
        f"{time.perf_counter() - t0:.3f} "
        f"comm_max_abs={diff['comm_max_abs']:.3e} "
        f"j_max_rel={diff['j_max_rel']:.3e}")
    failures = []
    if len(segments) < 2:
        failures.append(f"resume: {len(segments)} segment(s), want >= 2")
    if not _finite(res) or not agrees(diff):
        failures.append(f"resume: disagrees with run_sweep {diff}")
    return failures


def phase_sharded(sizes: dict, num_devices: int, device: str,
                  log=print) -> list:
    """The grid sharded over ``num_devices`` vs unsharded on device 0."""
    sampler, w0, problem = make_workload(
        sizes["num_states"], sizes["num_agents"], sizes["num_samples"])
    mesh = make_sweep_mesh(num_devices)
    failures = []
    for gb, sb in (PATHS[0], PATHS[2]):
        spec = make_spec(sizes["num_states"], sizes["num_agents"],
                         sizes["num_iterations"], gb, sb)
        plan = plan_sweep(spec, sampler, w0, problem, mesh=mesh)
        flat, first_s, steady_s = _timed(lambda: exec_plan(plan))
        placed = len(flat.comm_rate.sharding.device_set)
        sharded = finalize_sweep(plan, flat)
        t0 = time.perf_counter()
        single = jax.block_until_ready(
            run_sweep(spec, sampler, w0, problem=problem))
        single_s = time.perf_counter() - t0
        diff = compare(sharded, single)
        log(f"sharded {gb}/{sb} on {num_devices}x {device}: "
            f"devices_holding_output={placed} first_call_s={first_s:.3f} "
            f"steady_s={steady_s:.3f}; one device: first_call_s="
            f"{single_s:.3f}; comm_max_abs={diff['comm_max_abs']:.3e} "
            f"j_max_rel={diff['j_max_rel']:.3e}")
        if placed != num_devices:
            failures.append(f"sharded {gb}/{sb}: output on {placed} "
                            f"devices, want {num_devices}")
        if not _finite(sharded) or not agrees(diff):
            failures.append(f"sharded {gb}/{sb}: disagrees with one "
                            f"device {diff}")
    return failures


def result_line(info: dict) -> str:
    """The contracted last line of standard output."""
    return json.dumps({"ok": True, "device": {
        "platform": info["platform"], "kind": info["kind"],
        "count": info["count"]}})


def _memory_stats() -> dict:
    stats = jax.devices()[0].memory_stats() or {}
    return {k: stats[k] for k in ("peak_bytes_in_use", "bytes_in_use",
                                  "largest_alloc_size", "bytes_limit")
            if k in stats}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the run-axis-sharded sweep")
    ap.add_argument("--out", default=os.path.join(REPO, "chiprun_out",
                                                  "chip_smoke"),
                    help="directory for the store and checkpoints "
                         "(emptied first)")
    args = ap.parse_args(argv)

    info = device_info()
    print(f"device: platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}", flush=True)
    if info["platform"] != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform is "
              f"{info['platform']!r}); nothing was run", file=sys.stderr)
        return 2
    if info["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX sees {info['count']}", file=sys.stderr)
        return 2
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    shutil.rmtree(args.out, ignore_errors=True)
    os.makedirs(args.out)

    def log(msg):
        print(msg, flush=True)

    t0 = time.perf_counter()
    if args.chips == 4:
        failures = phase_sharded(SIZES, 4, info["kind"], log)
    else:
        results, failures = phase_sweep(SIZES, info["kind"], log)
        reference = results[PATHS[0]]
        failures += phase_store(reference, SIZES, args.out, log)
        failures += phase_resume(reference, SIZES, args.out, log)
    log(f"memory_stats={_memory_stats()} wall_s="
        f"{time.perf_counter() - t0:.3f}")
    if failures:
        for f in failures:
            print(f"FAILED: {f}", file=sys.stderr)
        return 1
    print(result_line(info), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
