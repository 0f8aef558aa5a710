"""Readings that the limits of ``bench/limits/<cell>.json`` are set from.

    python bench/control.py --workload <cell> --seeds 1-12 --control-seeds 101-103 \\
        [--controls program:high,program:default] \\
        [--faults frozen,half_batch,altered] --out readings.json

In one process, for the cell's configuration and traffic:

* program: on each of ``--seeds``, one timed grid call exactly as
  ``bench/run.py`` makes it, each answer held against the reference
  (``bench/compare.py``); the largest of each number over the seeds is its
  lower reading;
* controls: on each of ``--control-seeds``, each of ``--controls`` held
  against the reference at "highest"; the smallest over the seeds is its
  upper reading.  ``program:<p>`` is that same grid call with JAX's
  default matmul precision ``<p>`` in place of the configuration's
  "highest": "high" (three bfloat16 passes) is the step below, "default"
  (one pass) the chip's own default.  ``reference:<p>`` puts the reference,
  with its contractions written out at ``<p>``, in the program's place: it
  computes alike on every backend, so a CPU test can run it;
* faults, planted in the reference put in the program's place:
  ``frozen``     a step that returns its state unchanged (no gradient
                 reaches the update);
  ``half_batch`` half of each agent's samples left out, the mean taken over
                 the rest;
  ``altered``    answers altered where they are produced: each mode's
                 first and last lambda swap their reported J and comm rate
                 (the weights stay).

Writes every per-seed reading to ``--out`` and prints the lower and upper
reading of each number.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import cells  # noqa: E402
import compare  # noqa: E402
import run  # noqa: E402

CONTROL_TAG = 0x4354   # "CT": the control's stream differs from both others
FAULTS = ("frozen", "half_batch", "altered")


class Frozen:
    """The reference environment with every gradient zero: the server step
    returns the weights unchanged."""

    def __init__(self, env):
        self._env = env

    def __getattr__(self, name):
        return getattr(self._env, name)

    def tdot(self, feat, r, ein):
        import jax.numpy as jnp
        return jnp.zeros(r.shape[:1] + (self._env.n,), r.dtype)


def seed_list(text: str) -> list[int]:
    """"1-12" or "1,5,9" -> list of ints."""
    if "-" in text and "," not in text:
        lo, hi = map(int, text.split("-"))
        return list(range(lo, hi + 1))
    return [int(s) for s in text.split(",") if s]


def _key(seed: int, tag: int):
    import jax
    import numpy as np
    state = np.random.SeedSequence([seed, tag]).generate_state(1)
    return jax.random.key(int(state[0]) & 0x7FFFFFFF)


def _worst(env, got, reference) -> dict:
    """Largest of each number over the answers ``got``."""
    worst = {}
    for key, ans in got:
        for name, v in compare.run_numbers(env, ans, reference[key]).items():
            worst[name] = max(worst.get(name, v), v)
    return worst


def readings(root: str, name: str, seeds, control_seeds, faults=(),
             controls=("program:high", "program:default"),
             bench: str = BENCH, log=print) -> dict:
    """Per-seed readings of the program, the control and each fault."""
    import jax
    from repro.experiments import run_sweep

    res = cells.resolve(root, name, bench=bench)
    cfg = res["config"]
    env = run.reference_env(res)
    keys = [(mode, lam) for mode in cfg["modes"] for lam in cfg["lambdas"]]

    def simulate(key, precision="highest", env=env, **overrides):
        return list(run.simulate_reference(res, env, keys, key, precision,
                                           **overrides).items())

    def reference(seed):
        return dict(simulate(run.reference_key(seed)))

    def program(seed, precision):
        spec, sampler, w0, problem, mesh = run.build(res, seed)
        with jax.default_matmul_precision(precision):
            return run.answers(jax.block_until_ready(run_sweep(
                spec, sampler, w0, problem=problem, mesh=mesh)), spec)

    out = {"program": [], "controls": {c: [] for c in controls},
           "faults": {f: [] for f in faults}}
    for seed in seeds:
        got = program(seed, cfg["matmul_precision"])
        out["program"].append(dict(_worst(env, got, reference(seed)),
                                   seed=seed))
        log(f"program seed {seed}: {out['program'][-1]}")
    for seed in control_seeds:
        ref = reference(seed)
        for control in controls:
            kind, precision = control.split(":")
            if kind == "program":
                got = program(seed, precision)
            elif kind == "reference":
                got = simulate(_key(seed, CONTROL_TAG), precision=precision)
            else:
                raise ValueError(f"unknown control {control!r}")
            out["controls"][control].append(dict(_worst(env, got, ref),
                                                 seed=seed))
            log(f"control {control} seed {seed}: "
                f"{out['controls'][control][-1]}")
        for fault in faults:
            if fault == "frozen":
                got = simulate(_key(seed, CONTROL_TAG), env=Frozen(env))
            elif fault == "half_batch":
                got = simulate(_key(seed, CONTROL_TAG),
                               num_samples=cfg["num_samples"] // 2)
            elif fault == "altered":
                got = simulate(_key(seed, CONTROL_TAG))
                per_mode = len(cfg["lambdas"])
                for a in range(0, len(got), per_mode):
                    b = a + per_mode - 1
                    (ka, ra), (kb, rb) = got[a], got[b]
                    swap = ("j_final", "comm_rate")
                    got[a] = (ka, dict(ra, **{f: rb[f] for f in swap}))
                    got[b] = (kb, dict(rb, **{f: ra[f] for f in swap}))
            else:
                raise ValueError(f"unknown fault {fault!r}")
            out["faults"][fault].append(dict(_worst(env, got, ref),
                                             seed=seed))
            log(f"fault {fault} seed {seed}: {out['faults'][fault][-1]}")
    return out


def summary(out: dict) -> dict:
    """Lower reading (max over the program's seeds) and upper readings
    (min over each control's and each fault's seeds) of each number."""
    names = [k for k in out["program"][0] if k != "seed"]
    lo = {n: max(r[n] for r in out["program"]) for n in names}
    up = {}
    for what, rows in {**out["controls"], **out["faults"]}.items():
        if rows:
            up[what] = {n: min(r[n] for r in rows) for n in names}
    return {"lower": lo, "upper": up}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", required=True)
    ap.add_argument("--controls", default="program:high,program:default")
    ap.add_argument("--faults", default="")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: no TPU; nothing was run", file=sys.stderr)
        return 2
    print(f"compile cache {run.enable_compile_cache(run.ROOT)}")
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    out = readings(run.ROOT, args.workload, seed_list(args.seeds),
                   seed_list(args.control_seeds),
                   [f for f in args.faults.split(",") if f],
                   [c for c in args.controls.split(",") if c])
    out["summary"] = summary(out)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out["summary"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
