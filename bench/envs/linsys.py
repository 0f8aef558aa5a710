"""The system's own §V linear-system environment, built as a user builds it."""

from __future__ import annotations

import numpy as np

COST_WEIGHTS = (1.0, 1.0, 0.0, 0.0, 0.0, 0.0)   # |x|^2 in [x1^2, x2^2, ...]


def build(cfg: dict):
    """(sampler, w0, problem) for ``run_sweep`` from the configuration.

    ``v_current: "cost"`` evaluates the Bellman update of V_current = |x|^2,
    the first iterate from V = 0, so a sample's target |x|^2 + gamma |x_+|^2
    needs the successor x_+ = A x + w."""
    import jax.numpy as jnp
    from repro.core.algorithm1 import ParamSampler
    from repro.envs import LinearSystem

    env = LinearSystem(a_matrix=tuple(map(tuple, cfg["a_matrix"])),
                       noise_var=cfg["noise_var"], gamma=cfg["gamma"])
    if cfg["v_current"] != "cost":
        raise ValueError(f"unknown v_current {cfg['v_current']!r}")
    v = np.asarray(COST_WEIGHTS, np.float32)
    w0 = jnp.asarray(cfg["w0"], jnp.float32)
    sampler = ParamSampler(fn=env.sampler_fn(cfg["num_samples"]),
                           params=env.agent_params(v, cfg["num_agents"]))
    return sampler, w0, env.vfa_problem(v, grid=cfg["population_grid"])
