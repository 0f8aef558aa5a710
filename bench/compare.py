"""The comparison that decides ``correct``.

Every run of every timed grid call is an answer: for one (trigger mode,
lambda, seed) it gives the learned weights, the comm rate (eq. 7), the
per-agent transmit counts and J(w_N).  The plain reference
(``bench/reference/``) simulates each (mode, lambda) of the grid with its
own random stream, and each answer is held against it:

``j_eval``   |J_program(w) - J(w)| / J(w): the program's own objective of its
             final weights against the reference's exact J of the same
             weights in float64.  Deterministic given w; J(w) sits near the
             noise floor, far below the terms it is summed from, so it is
             what matmul precision moves (the control fails here).
``j_sim``    |ln J(w_program) - ln J(w_reference)|: the true objective the
             program's run reached against the reference run's.  Covers the
             sampler, the gradients, the gains, the trigger and the update.
``comm``     |comm_program - comm_reference|: the trigger's decisions.
``delivered`` (lossy channel) |delivered rate program - reference|.
``delivered_over_sent`` (lossy channel) max over agents of deliveries minus
             transmissions; a channel can only lose: exact, limit 0.
``devices_missing`` (sharded traffic) devices of the mesh that hold no part
             of the output: exact, limit 0.

The two random streams differ, so ``j_sim``, ``comm`` and ``delivered``
compare two draws of one distribution; their limits are set from that
spread (PERF.md), and a later change that draws the same distribution from
another stream reads the same.  A number that is not finite fails.
"""

from __future__ import annotations

import math

import numpy as np

def run_numbers(env, got: dict, ref: dict) -> dict:
    """Per-answer numbers of one program run ``got`` against the reference
    run ``ref`` of the same (mode, lambda)."""
    j_true = env.objective64(got["final_weights"])
    j_ref = env.objective64(ref["final_weights"])
    out = {
        "j_eval": abs(float(got["j_final"]) - j_true) / j_true,
        "j_sim": abs(math.log(j_true) - math.log(j_ref)),
        "comm": abs(float(got["comm_rate"]) - float(ref["comm_rate"])),
    }
    if got.get("delivered_counts") is not None:
        out["delivered"] = abs(float(got["delivered_rate"])
                               - float(ref["delivered_rate"]))
        out["delivered_over_sent"] = float(np.max(
            got["delivered_counts"] - got["tx_counts"]))
    return {k: (v if math.isfinite(v) else math.inf) for k, v in out.items()}


def compare(env, answers, reference: dict, limits: dict,
            devices_missing: int | None = None) -> dict:
    """Hold every answer against the reference run of its (mode, lambda).

    ``answers`` is a list of ``(key, run)``: ``key`` is the (mode, lambda)
    pair and ``run`` the program's per-run arrays; ``reference`` maps each
    key to the reference run.  Returns ``{"correct", "failed", "checks"}``
    where ``checks`` gives each number's worst value beside its limit.
    """
    worst: dict = {}
    failed = 0
    for key, run in answers:
        nums = run_numbers(env, run, reference[key])
        bad = False
        for name, value in nums.items():
            worst[name] = max(worst.get(name, -math.inf), value)
            bad |= not value <= limits[name]
        failed += bad
    if devices_missing is not None:
        worst["devices_missing"] = float(devices_missing)
    checks = {name: {"value": worst[name], "limit": limits[name]}
              for name in worst}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    return {"correct": correct and failed == 0, "failed": failed,
            "checks": checks}
