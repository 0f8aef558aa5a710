"""Sweep-throughput frontier: grid size x device count, plus env-family scale.

Two suites on the device-sharded, memory-streaming engine, both run in
this process on the devices JAX sees — one process per chip, since a
child process cannot reach a chip its parent already holds:

* ``device_frontier`` — the same flattened grid executed over meshes of
  d = 1, 2, 4, 8 devices (``launch.mesh.make_sweep_mesh(d)``, a prefix of
  the real devices; counts above ``jax.device_count()`` are skipped):
  runs/s with the run axis shard_map'd over the mesh.
* ``env_family`` — >= 64 random garnet MDP instances as the engine's
  ``env_sets`` grid axis, sharded over every device: one jitted call
  sweeps the whole family (per-instance exact terms included).

Timings separate compile (first call) from steady-state execution, and
every row names the platform and device kind it ran on.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.algorithm1 import ParamSampler
from repro.envs import GridWorld, family_sampler_fn, garnet_env_family
from repro.experiments import SweepSpec, run_sweep
from repro.launch.mesh import make_sweep_mesh

DEVICE_COUNTS = (1, 2, 4, 8)


def _timed_sweep(run_fn, grid_runs: int):
    t0 = time.perf_counter()
    jax.block_until_ready(run_fn().comm_rate)        # compile + first exec
    t1 = time.perf_counter()
    res = run_fn()
    jax.block_until_ready(res.comm_rate)             # steady state
    t2 = time.perf_counter()
    return res, dict(grid_runs=grid_runs,
                     first_call_s=t1 - t0, exec_s=t2 - t1,
                     runs_per_s=grid_runs / (t2 - t1),
                     us_per_call=(t2 - t1) * 1e6 / grid_runs)


def _device_fields(devices: int) -> dict:
    dev = jax.devices()[0]
    return dict(devices=devices, platform=dev.platform,
                device_kind=dev.device_kind)


def _frontier_row(devices: int, cfg: dict) -> dict:
    gw = GridWorld()
    prob = gw.vfa_problem(np.zeros(gw.num_states))
    w0 = jnp.zeros(gw.num_states)
    spec = SweepSpec(
        modes=("theoretical", "practical", "random", "never"),
        lambdas=tuple(np.logspace(-4, -1, cfg["lambdas"])),
        seeds=tuple(range(cfg["seeds"])),
        rhos=(prob.min_rho(0.5) * 1.0001,), eps=0.5,
        num_iterations=cfg["iters"], num_agents=cfg["agents"],
        trace="summary")
    sampler = ParamSampler(fn=gw.sampler_fn(10),
                           params=gw.agent_params(w0, cfg["agents"]))
    mesh = make_sweep_mesh(devices)
    runs = int(np.prod(spec.grid_shape))
    _, t = _timed_sweep(lambda: run_sweep(spec, sampler, w0, problem=prob,
                                          mesh=mesh), runs)
    t.update(bench="sweep_scaling", suite="device_frontier",
             iters=cfg["iters"], agents=cfg["agents"],
             **_device_fields(devices))
    return t


def _family_row(cfg: dict) -> dict:
    envs, fam = garnet_env_family(cfg["env_instances"], num_states=20)
    w0 = jnp.zeros(20)
    spec = SweepSpec(
        modes=("theoretical", "practical"), lambdas=(1e-3,),
        seeds=tuple(range(cfg["seeds"])), rhos=(0.999,), eps=0.4,
        num_iterations=cfg["iters"], num_agents=cfg["agents"],
        trace="summary")
    sampler = ParamSampler(fn=family_sampler_fn(10),
                           params=envs[0].agent_params(w0, cfg["agents"]))
    mesh = make_sweep_mesh()
    runs = cfg["env_instances"] * int(np.prod(spec.grid_shape))
    res, t = _timed_sweep(lambda: run_sweep(spec, sampler, w0, env_sets=fam,
                                            mesh=mesh), runs)
    jf = np.asarray(res.j_final)
    env_ax = res.axes.index("env_set")
    non_env = tuple(i for i in range(jf.ndim) if i != env_ax)
    t.update(bench="sweep_scaling", suite="env_family",
             env_instances=cfg["env_instances"],
             jitted_calls=1, axes=list(res.axes),
             J_final_mean=float(jf.mean()),
             J_final_spread=float(np.std(jf.mean(axis=non_env))),
             comm_rate_mean=float(np.mean(np.asarray(res.comm_rate))),
             **_device_fields(jax.device_count()))
    return t


def run(smoke: bool = False) -> list[dict]:
    if smoke:
        counts, grid = (1, 2), dict(lambdas=2, seeds=2, iters=25, agents=2)
        family = dict(env_instances=8, seeds=1, iters=20, agents=2)
    else:
        counts, grid = DEVICE_COUNTS, dict(lambdas=4, seeds=4, iters=200,
                                           agents=4)
        family = dict(env_instances=64, seeds=2, iters=150, agents=4)
    t0 = time.perf_counter()
    rows = [_frontier_row(d, grid) for d in counts
            if d <= jax.device_count()]
    rows.append(_family_row(family))
    base = rows[0]["runs_per_s"]
    for r in rows:
        if r["suite"] == "device_frontier":
            r["speedup_vs_1dev"] = r["runs_per_s"] / base
    rows[0]["sweep_wall_s"] = time.perf_counter() - t0
    return rows
