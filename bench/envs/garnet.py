"""The system's own GARNET environment, built as a user builds it."""

from __future__ import annotations

import numpy as np


def build(cfg: dict):
    """(sampler, w0, problem) for ``run_sweep`` from the configuration.

    ``v_current: "cost"`` evaluates the Bellman update of V_current = c, the
    first iterate from V = 0, so a sample's target c(x) + gamma c(x') needs
    the next state x' drawn from P(x, a)."""
    import jax.numpy as jnp
    from repro.core.algorithm1 import ParamSampler
    from repro.envs.garnet import GarnetMDP

    env = GarnetMDP(num_states=cfg["num_states"],
                    num_actions=cfg["num_actions"],
                    branching=cfg["branching"], seed=cfg["instance"],
                    gamma=cfg["gamma"])
    if cfg["v_current"] != "cost":
        raise ValueError(f"unknown v_current {cfg['v_current']!r}")
    v = np.asarray(env.cost_vector(), np.float32)
    w0 = jnp.asarray(cfg["w0"], jnp.float32)
    sampler = ParamSampler(fn=env.sampler_fn(cfg["num_samples"]),
                           params=env.agent_params(v, cfg["num_agents"]))
    return sampler, w0, env.vfa_problem(v)
