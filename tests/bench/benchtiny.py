"""A copy of the benchmark at sizes a CPU test run holds.

``tiny_checkout(tmp_path)`` copies ``bench/`` and ``BENCHMARK.json`` into
``tmp_path``, links the system under test beside them, and cuts every
configuration to a few agents, samples and steps; the limits are those of
``TINY_LIMITS``, read at these sizes on the CPU (PERF.md).
"""

from __future__ import annotations

import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
BENCH = os.path.join(REPO, "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

TINY = {
    "garnet-s128-m1024": dict(num_states=16, features=16, num_agents=64,
                              num_samples=32, num_iterations=40, eps=2.0),
    "linsys-m1024": dict(num_agents=32, num_samples=16, num_iterations=40),
}
# Readings at these sizes on the CPU, program over seeds 1-6, the faults
# and the written-out controls at their least over three seeds.  GARNET
# (clean; lossy): j_eval 1.5e-3; 1.6e-3, j_sim 1.3; 1.8, comm 0.013; 0.053,
# delivered 0.050; faults: j_sim 6.0 and more (state unchanged, next state
# left where it was), comm 0.41 and more (half of the batch, answers
# altered); control j_eval 0.48 (one pass) and 8.8e-3 (three).  §V:
# j_eval 1.1e-4, j_sim 1.05, comm 0.030; faults: comm 0.10 and more
# (answers altered, state unchanged, next state left where it was).
TINY_LIMITS = {"j_eval": 0.05, "j_sim": 4.0, "comm": 0.15, "delivered": 0.15,
               "delivered_over_sent": 0.0, "devices_missing": 0.0}
TINY_LIMITS_BY_CONFIG = {"linsys-m1024": dict(TINY_LIMITS, j_eval=1e-2,
                                              j_sim=2.5, comm=0.06)}


def tiny_checkout(tmp_path) -> str:
    root = str(tmp_path)
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    os.symlink(os.path.join(REPO, "src"), os.path.join(root, "src"))
    for name, sizes in TINY.items():
        path = os.path.join(root, "bench", "configs", f"{name}.json")
        with open(path) as f:
            cfg = json.load(f)
        cfg.update(sizes)
        with open(path, "w") as f:
            json.dump(cfg, f)
    manifest = json.load(open(os.path.join(root, "BENCHMARK.json")))
    for cell in manifest["workloads"]:
        limits = TINY_LIMITS_BY_CONFIG.get(cell["config"], TINY_LIMITS)
        with open(os.path.join(root, "bench", "limits",
                               f"{cell['name']}.json"), "w") as f:
            json.dump({"limits": limits}, f)
    return root
