"""Benchmark harness: one bench per paper figure/claim + the beyond-paper
comm-savings and kernel/roofline suites.

Prints ``name,us_per_call,derived`` CSV per row (the repo convention) and
writes full JSON to experiments/bench/.

  PYTHONPATH=src python -m benchmarks.run                   # everything
  PYTHONPATH=src python -m benchmarks.run --only fig2       # one suite
  PYTHONPATH=src python -m benchmarks.run --only kernels,sweep_step  # several
  PYTHONPATH=src python -m benchmarks.run --smoke           # seconds-scale CI

``--smoke`` shrinks every suite's grid to seconds-scale (tiny grids, few
iterations) so the whole benchmark set runs inside CI; smoke results are
NOT written to experiments/bench/ (they would overwrite the real numbers).
``--out-dir DIR`` redirects the JSON elsewhere and writes even under
``--smoke`` — that is how the CI bench-regression gate captures a fresh
smoke run to validate against the committed schemas
(``benchmarks.check_bench``).

Store-backed figure regeneration (DESIGN.md §9):

  --store ROOT       figure suites (fig2/fig3/theorem1/comm_savings/
                     heterogeneity) persist their sweeps to this
                     ``SweepStore`` via ``sweep_or_load`` — a warm re-run
                     loads instead of re-sweeping
  --from-store ROOT  skip the device entirely: regenerate every figure
                     artifact the store backs through the jax-free report
                     pipeline (``benchmarks.report_regen``)
"""

from __future__ import annotations

import argparse
import sys
import time

from benchmarks import (
    agents_scaling,
    chaos,
    comm_savings,
    degraded_edge,
    fig2_grid_tradeoff,
    fig3_continuous,
    heterogeneity,
    kernels_bench,
    report_regen,
    resume_query,
    roofline,
    serve_load,
    sweep_scaling,
    sweep_step,
    td_speedup,
    theorem1_bound,
)
from benchmarks.common import save_rows
from repro.launch.compile_cache import enable_compile_cache

SUITES = {
    "fig2": fig2_grid_tradeoff,
    "fig3": fig3_continuous,
    "theorem1": theorem1_bound,
    "agents_scaling": agents_scaling,
    "sweep_scaling": sweep_scaling,
    "sweep_step": sweep_step,
    "comm_savings": comm_savings,
    "resume_query": resume_query,
    "serve_load": serve_load,
    "heterogeneity": heterogeneity,
    "degraded_edge": degraded_edge,
    "td_speedup": td_speedup,
    "report_regen": report_regen,
    "kernels": kernels_bench,
    "roofline": roofline,
    "chaos": chaos,
}

# suites that accept store= (persist results / reuse cached columns)
STORE_AWARE = {"fig2", "fig3", "theorem1", "comm_savings", "heterogeneity",
               "degraded_edge", "td_speedup", "report_regen"}


def resolve_suites(only):
    """Validate a ``--only`` value into a list of suite names.

    ``None`` means every suite.  Names are comma-separated; surrounding
    whitespace is tolerated.  An unknown name — or a value with no names
    at all, like ``--only ""`` (which previously fell through and silently
    ran EVERYTHING) — raises ``ValueError`` naming the offender and the
    valid choices.
    """
    if only is None:
        return list(SUITES)
    names = [n.strip() for n in only.split(",") if n.strip()]
    if not names:
        raise ValueError("--only given but named no suite "
                         f"(choose from {', '.join(SUITES)})")
    for name in names:
        if name not in SUITES:
            raise ValueError(f"unknown suite {name!r} "
                             f"(choose from {', '.join(SUITES)})")
    return names


def _derived(row: dict) -> str:
    for key in ("J_final", "rhs_bound", "overhead_pct", "savings_pct",
                "speedup_vs_reference", "speedup_warm_vs_cold",
                "speedup_vs_m1",
                "throughput_rps", "gflop_per_call", "dominant",
                "byte_deterministic", "artifacts"):
        if key in row:
            return f"{key}={row[key]}"
    return ""


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, metavar="SUITE[,SUITE...]",
                    help="run one or more comma-separated suites: "
                         + ",".join(SUITES))
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale grids for CI; skips JSON output "
                         "(unless --out-dir is given)")
    ap.add_argument("--out-dir", default=None, metavar="DIR", dest="out_dir",
                    help="write per-suite JSON here instead of "
                         "experiments/bench/; also enables JSON under "
                         "--smoke (the bench-regression gate's input)")
    ap.add_argument("--store", default=None, metavar="ROOT",
                    help="SweepStore root: figure suites persist/reuse "
                         "their sweeps there (sweep_or_load)")
    ap.add_argument("--from-store", default=None, metavar="ROOT",
                    dest="from_store",
                    help="regenerate figure artifacts from this SweepStore "
                         "via the jax-free report pipeline; no device work")
    args = ap.parse_args()
    try:
        only = None if args.only is None else resolve_suites(args.only)
    except ValueError as e:
        ap.error(str(e))
    if args.from_store:
        if only not in (None, ["report_regen"]):
            ap.error("--from-store regenerates through the report pipeline; "
                     "combine it only with --only report_regen")
        names = ["report_regen"]
    else:
        names = only if only else list(SUITES)

    enable_compile_cache()
    print("name,us_per_call,derived")
    failures = 0
    for name in names:
        t0 = time.time()
        kwargs = {}
        if name in STORE_AWARE and (args.store or args.from_store):
            kwargs["store"] = args.from_store or args.store
        try:
            rows = SUITES[name].run(smoke=args.smoke, **kwargs)
        except Exception as e:  # keep the harness going; report at the end
            print(f"{name},ERROR,{type(e).__name__}:{e}", flush=True)
            failures += 1
            continue
        if args.out_dir:
            save_rows(name, rows, out_dir=args.out_dir)
        elif not args.smoke:
            save_rows(name, rows)
        for row in rows:
            # subprocess suites report crashes as error rows rather than
            # raising — surface them and fail the run (the CI smoke gate
            # must go red when a suite never actually executed)
            if isinstance(row.get("error"), str):
                print(f"{row.get('bench', name)},ERROR,{row['error'][:200]}",
                      flush=True)
                failures += 1
                continue
            label = row.get("bench", name)
            sub = [str(row[k]) for k in ("regime", "fleet_class", "channel",
                                         "mode", "site", "kind",
                                         "query", "panel", "lam", "arch",
                                         "shape", "mesh", "suite", "devices",
                                         "env_instances", "stage", "m",
                                         "concurrency", "step_backend",
                                         "gain_backend")
                   if k in row]
            full = label + ("[" + "/".join(sub) + "]" if sub else "")
            print(f"{full},{row.get('us_per_call', 0):.1f},{_derived(row)}",
                  flush=True)
        if name == "roofline":
            print("\n" + roofline.format_table(rows) + "\n", file=sys.stderr)
        print(f"# {name} done in {time.time() - t0:.1f}s", file=sys.stderr)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
