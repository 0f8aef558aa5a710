"""Beyond-paper: gated gradient aggregation on a real (reduced) model —
expected cross-agent bytes saved vs lambda (DESIGN.md §4 accounting).

Runs the federated train step in this process on the devices JAX sees
(the federation axis has one agent per device) at several lambda values
and reports the measured comm rate and the implied DCN bytes per step.
The lambda grid is scaled to the LM's gradient magnitudes (||g||^2 ~ tens
at init; the paper's grid-MDP lambdas are 4 orders smaller because its J
is O(1)).  Model build, mesh setup and parameter init are paid once for
the whole lambda sweep.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

LAMBDAS = (0.0, 1.0, 30.0, 300.0)


def _sweep(num_steps: int, lambdas) -> list[dict]:
    from repro.configs import get_config
    from repro.core.fed_sgd import FedConfig, FedStats, tree_bytes
    from repro.data.synthetic_lm import SyntheticLMConfig, make_lm_batch
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import build_train_step
    from repro.models import build_model
    from repro.optim import sgd

    cfg = get_config("mamba2-370m").reduced()
    model = build_model(cfg)
    mesh = make_host_mesh(1)
    opt = sgd(0.1)
    params0 = model.init(jax.random.key(0))
    lmc = SyntheticLMConfig(vocab_size=cfg.vocab_size, seq_len=128,
                            global_batch=8)
    dev = jax.devices()[0]
    recs = []
    for lam in lambdas:
        t0 = time.perf_counter()
        fed = FedConfig(eps=0.1, lam=lam, rho=0.995, horizon=30,
                        estimator="hvp")
        bundle = build_train_step(model, cfg, mesh, opt,
                                  fed_cfg=fed if lam > 0 else None)
        # fresh buffers per lambda: the jitted step donates params, and
        # device_put aliases when the sharding already matches
        params = jax.device_put(
            jax.tree.map(jnp.copy, params0),
            jax.tree.map(lambda s: NamedSharding(mesh, s), bundle.pspecs))
        state = opt.init(params)
        fs = FedStats.init(bundle.num_agents)
        losses = []
        for step in range(num_steps):
            batch = make_lm_batch(lmc, jax.random.key(1), step)
            params, state, fs, m = bundle.step(params, state, fs, batch)
            losses.append(float(m["loss"]))
        gbytes = tree_bytes(params)
        comm = float(m["comm_rate"])
        recs.append({
            "lam": lam, "agents": bundle.num_agents, "comm_rate": comm,
            "grad_bytes": gbytes,
            "bytes_per_step_full": gbytes * bundle.num_agents,
            "bytes_per_step_gated": gbytes * bundle.num_agents * comm,
            "loss_first": losses[0], "loss_last": losses[-1],
            "platform": dev.platform, "device_kind": dev.device_kind,
            "lam_wall_s": time.perf_counter() - t0,
        })
    return recs


def run(smoke: bool = False, store=None) -> list[dict]:
    steps, lambdas = (4, (0.0, 30.0)) if smoke else (30, LAMBDAS)
    t0 = time.perf_counter()
    recs = _sweep(steps, lambdas)
    rows = []
    for rec in recs:
        wall_s = rec.pop("lam_wall_s")
        rows.append(dict(rec, bench="comm_savings",
                         savings_pct=100.0 * (1.0 - rec["comm_rate"]),
                         us_per_call=wall_s * 1e6 / steps))
    rows[0]["sweep_wall_s"] = time.perf_counter() - t0
    if store is not None:
        _persist(store, lambdas, steps, recs)
    return rows


def _persist(store, lambdas, steps, recs) -> None:
    """One dict-spec ``SweepStore`` entry (axes: just λ) so the jax-free
    report pipeline (DESIGN.md §9) can regenerate the savings table and
    chart from a cold store.  Skipped when the entry already exists —
    measured LM losses are not covered by the append-only byte-identity
    guarantee the sweep-engine entries enjoy."""
    from repro.experiments.store import SweepStore
    if not isinstance(store, SweepStore):
        store = SweepStore(store)
    spec = {"figure": "comm_savings", "model": "mamba2-370m-reduced",
            "lambdas": [float(l) for l in lambdas], "num_steps": steps,
            "agents": recs[0]["agents"]}
    if store.has(spec):
        return
    arrays = {k: np.asarray([rec[k] for rec in recs], np.float64)
              for k in ("comm_rate", "bytes_per_step_full",
                        "bytes_per_step_gated", "loss_first", "loss_last")}
    store.put(spec, arrays, axes=("lam",),
              extra={"figure": "comm_savings",
                     "grad_bytes": recs[0]["grad_bytes"],
                     "agents": recs[0]["agents"]})
