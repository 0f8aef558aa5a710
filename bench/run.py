"""Run one benchmark cell on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix, found
by name under ``bench/`` (``bench/cells.py``).  One run:

1. checks the device: no TPU, or fewer chips than the cell asks for, exits
   2 with no result line;
2. turns on JAX's persistent compilation cache at a fixed path in the
   checkout (``$JAX_COMPILATION_CACHE_DIR`` where that is set);
3. builds the configuration's environment and the sweep grid from the
   configuration and traffic files and the seed;
4. enters the configuration's matmul precision;
5. warms up with one grid call of exactly the timed shapes;
6. measures: back-to-back ``run_sweep`` calls, each ended by
   ``block_until_ready``, until one ends after ``--seconds``; with
   ``--trace 1`` the profiler records that window;
7. holds every answer of every call against the plain reference
   (``bench/compare.py``), after the window and after device memory is read;
8. prints the result as the last line of standard output, and each number
   compared beside its limit as the last lines of standard error.

The timed call is ``repro.experiments.run_sweep``, the call users make; the
spec names no step backend, so the program picks its own path.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import cells  # noqa: E402
import compare  # noqa: E402
import trace_reduce  # noqa: E402

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
COMPILE_EVENTS = (BACKEND_COMPILE_EVENT,
                  "/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration")
# the reference's stream is its own: the seed, tagged "RE"
REFERENCE_TAG = 0x5245


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def grid_seeds(seed: int, count: int) -> tuple[int, ...]:
    """``count`` seeds of the program's grid, below 2**31, from ``--seed``."""
    import numpy as np
    state = np.random.SeedSequence(seed).generate_state(count)
    return tuple(int(s) & 0x7FFFFFFF for s in state)


def reference_key(seed: int):
    import jax
    import numpy as np
    state = np.random.SeedSequence([seed, REFERENCE_TAG]).generate_state(1)
    return jax.random.key(int(state[0]) & 0x7FFFFFFF)


def enable_compile_cache(root: str) -> str:
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileEvents:
    """(event, start, seconds) of every compile phase, on the host clock."""

    def __init__(self):
        from jax import monitoring
        self.events = []
        self._monitoring = monitoring
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            now = time.perf_counter()
            self.events.append((event, now - duration, duration))

    def close(self):
        self._monitoring.unregister_event_duration_listener(self._on)


def build(res: dict, seed: int):
    """The timed call's arguments: ``(spec, sampler, w0, problem, mesh)``."""
    from repro.core.channel import ChannelSpec
    from repro.experiments import SweepSpec
    from repro.launch.mesh import make_sweep_mesh

    cfg, traffic = res["config"], res["traffic"]
    sampler, w0, problem = cells.load_module(
        res["program_env"], "bench_program_env").build(cfg)
    chan = traffic["channel"]
    spec = SweepSpec(
        modes=tuple(cfg["modes"]), lambdas=tuple(cfg["lambdas"]),
        seeds=grid_seeds(seed, traffic["seeds_per_call"]),
        rhos=(cfg["rho"],), eps=cfg["eps"],
        num_iterations=cfg["num_iterations"], num_agents=cfg["num_agents"],
        trace="summary",
        channel_sets=None if chan is None else (ChannelSpec(**chan),))
    mesh = (make_sweep_mesh(traffic["mesh_devices"])
            if traffic["mesh_devices"] else None)
    return spec, sampler, w0, problem, mesh


def answers(result, spec) -> list:
    """Every run of one grid result as ``((mode, lam), arrays)``."""
    import numpy as np
    axes = result.axes
    t = result.trace
    leaves = {"final_weights": result.final_weights,
              "comm_rate": result.comm_rate, "j_final": result.j_final,
              "tx_counts": t.tx_counts,
              "delivered_counts": t.delivered_counts,
              "delivered_rate": t.delivered_rate}
    leaves = {k: (None if v is None else np.asarray(v))
              for k, v in leaves.items()}
    grid = leaves["comm_rate"].shape
    out = []
    for idx in np.ndindex(grid):
        key = (spec.modes[idx[axes.index("mode")]],
               spec.lambdas[idx[axes.index("lam")]])
        out.append((key, {k: (None if v is None else v[idx])
                          for k, v in leaves.items()}))
    return out


def memory_peak(grid_args, devices) -> tuple[int, dict]:
    """Peak bytes on the fullest chip: the larger of the runtime's peak
    counter and the compiled grid program's memory analysis.

    ``grid_args`` are ``run_sweep``'s arguments at a fixed seed, so the
    analysis compiles one program that the persistent cache keeps.
    """
    import jax
    from repro.experiments.sweep import exec_plan, plan_sweep
    stats = [d.memory_stats() or {} for d in devices]
    runtime = max(s.get("peak_bytes_in_use", 0) for s in stats)
    spec, sampler, w0, problem, mesh = grid_args
    plan = plan_sweep(spec, sampler, w0, problem, mesh=mesh)
    mem = jax.jit(lambda: exec_plan(plan)).lower().compile().memory_analysis()
    program = (mem.argument_size_in_bytes + mem.output_size_in_bytes
               + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes
               - mem.alias_size_in_bytes)
    detail = {"runtime_peak_bytes_in_use": runtime,
              "program_memory_analysis_bytes": program,
              "program_temp_bytes": mem.temp_size_in_bytes}
    return max(runtime, program), detail


def reference_env(res: dict):
    """The configuration's plain reference environment."""
    return cells.load_module(res["reference_env"],
                             "bench_reference_env").make(res["config"])


def simulate_reference(res: dict, env, keys, key, precision="highest",
                       **overrides) -> dict:
    """The plain reference's run of every ``(mode, lam)`` of ``keys``,
    as ``{(mode, lam): arrays}``; ``overrides`` replace configuration
    sizes (the control's planted faults use this)."""
    cfg = res["config"]
    algo = cells.load_module(os.path.join(res["bench"], "reference",
                                          "algorithm1.py"),
                             "bench_reference_algorithm1")
    args = dict(eps=cfg["eps"], rho=cfg["rho"],
                num_iterations=cfg["num_iterations"],
                num_agents=cfg["num_agents"], num_samples=cfg["num_samples"],
                channel=res["traffic"]["channel"])
    args.update(overrides)
    sim = algo.simulate(env, keys, key=key, precision=precision, **args)
    return {k: {f: (None if v is None else v[i]) for f, v in sim.items()}
            for i, k in enumerate(keys)}


def run_cell(root: str, name: str, seed: int, seconds: float, trace: bool,
             require_chip: bool = True, bench: str = BENCH,
             trace_dir: str | None = None) -> tuple[int, dict | None]:
    """One run; returns ``(exit code, result or None)``."""
    if not os.path.isdir(os.path.join(root, "src", "repro")):
        log(f"bench: no system under test at {root}/src/repro")
        return 2, None
    res = cells.resolve(root, name, bench=bench)
    cfg, traffic, cell = res["config"], res["traffic"], res["cell"]
    if res["limits"] is None:
        log(f"bench: {name} has no bench/limits/{name}.json")
        return 2, None

    import jax
    devices = jax.devices()
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    log(f"bench: {name} on {info['count']}x {info['kind']} "
        f"({info['platform']})")
    if require_chip and info["platform"] != "tpu":
        log(f"bench: no TPU (JAX platform is {info['platform']!r}); "
            f"nothing was run")
        return 2, None
    if info["count"] < cell["chips"]:
        log(f"bench: the cell needs {cell['chips']} chips, JAX sees "
            f"{info['count']}")
        return 2, None
    peaks = cells.peaks(bench, info["kind"]) if require_chip else None
    log(f"bench: compile cache {enable_compile_cache(root)}")
    compiles = CompileEvents()

    sys.path.insert(0, os.path.join(root, "src"))
    from repro.experiments import run_sweep

    spec, sampler, w0, problem, mesh = build(res, seed)
    used = devices[:max(1, traffic["mesh_devices"])]

    def call(spec=spec):
        return run_sweep(spec, sampler, w0, problem=problem, mesh=mesh)

    def precision():
        return jax.default_matmul_precision(cfg["matmul_precision"])

    with precision():
        jax.block_until_ready(call())
    setup_end = time.perf_counter()
    setup_s = setup_end - T_START

    tmp = None
    if trace:
        tmp = trace_dir or tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(tmp)
    results, ends = [], []
    with precision(), jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        w_start = time.perf_counter()
        while True:
            with jax.profiler.TraceAnnotation("bench.call"):
                out = call()
            with jax.profiler.TraceAnnotation("bench.block"):
                jax.block_until_ready(out)
            results.append(out)
            ends.append(time.perf_counter())
            if ends[-1] - w_start >= seconds:
                break
        w_end = ends[-1]
    if trace:
        jax.profiler.stop_trace()
    compiles.close()
    window_s = w_end - w_start
    calls = len(results)
    runs = int(results[-1].comm_rate.size)
    samples = (calls * runs * cfg["num_iterations"] * cfg["num_agents"]
               * cfg["num_samples"])

    placed = len(results[-1].final_weights.sharding.device_set)
    got = [a for r in results for a in answers(r, spec)]
    del results, out
    # memory of the grid program at a fixed seed: one cached compile
    with precision():
        peak, mem_detail = memory_peak(build(res, 0), used)
    log(f"bench: memory {mem_detail}")

    keys = [(mode, lam) for mode in spec.modes for lam in spec.lambdas]
    t_ref = time.perf_counter()
    env = reference_env(res)
    reference = simulate_reference(res, env, keys, reference_key(seed))
    log(f"bench: reference {time.perf_counter() - t_ref:.3f} s")
    verdict = compare.compare(
        env, got, reference, res["limits"],
        devices_missing=(len(used) - placed if traffic["mesh_devices"]
                         else None))

    device = dict(info, memory_peak_bytes=int(peak))
    ctx = {"trace": None, "peaks": peaks, "chips": len(used),
           "calls": calls, "window_s": window_s,
           "num_iterations": cfg["num_iterations"],
           "window_bounds": (w_start, w_end),
           "setup_bounds": (T_START, setup_end),
           "compile_events": compiles.events,
           "backend_compile_event": BACKEND_COMPILE_EVENT,
           "work_per_step": cells.load_module(res["work"], "bench_work")
           .per_step([k[0] for k, _ in got[:runs]], cfg["num_agents"],
                     cfg["num_samples"], cfg["features"])}
    breakdown = None
    if trace:
        reduced = trace_reduce.reduce(trace_reduce.load(tmp))
        if trace_dir is None:
            shutil.rmtree(tmp, ignore_errors=True)
        ctx["trace"] = reduced
        busy = list(reduced["busy_s"].values())
        device["busy_s"] = sum(busy) / len(busy) if busy else 0.0
        device["window_s"] = reduced["window_s"]
        breakdown = {"device_ops": reduced["device_ops"],
                     "idle_gaps": reduced["idle_gaps"]}

    if trace:
        metrics = {}
        for m in res["per_layer"]:
            value = cells.read_metric(res["metrics_dir"], m["name"], ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e = {"samples_per_s": samples / window_s, "setup_s": setup_s}
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in res["end_to_end"]}
    log(f"bench: {calls} calls of {runs} runs in {window_s:.3f} s "
        f"({', '.join(f'{b - a:.3f}' for a, b in zip([w_start] + ends, ends))}"
        f" s each), set-up {setup_s:.3f} s")
    result = {"correct": verdict["correct"], "attempted": calls * runs,
              "failed": verdict["failed"], "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = verdict["checks"]
    for cname, c in verdict["checks"].items():
        log(f"check {cname}: {c['value']!r} (limit {c['limit']!r})")
    return 0, result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the raw profile here (default: a temporary "
                         "directory, removed)")
    args = ap.parse_args(argv)
    code, result = run_cell(ROOT, args.workload, args.seed, args.seconds,
                            bool(args.trace), trace_dir=args.trace_dir)
    if result is not None:
        print(json.dumps(result), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
