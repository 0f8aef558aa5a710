"""Common environment protocol for the batched sweep engine (DESIGN.md §5).

Every env exposes the same three capabilities the experiment stack needs:

* ``vfa_problem(v)``  — the exact population problem (3) for one Bellman
  update at ``V_current = v`` (used for the theoretical trigger, J, w*).
* ``sampler_fn(num_samples)`` — ONE jax-pure function
  ``(agent_params, rng) -> (phi_t (T, n), targets_t (T,))`` shared by every
  agent.  All heterogeneity lives in the parameters, never in the code, so a
  fleet is a single ``vmap`` and an experiment grid a single jitted program.
* ``agent_params(v, num_agents, ...)`` — stacked per-agent parameter pytree
  (leading axis m).  Envs expose env-specific knobs (visit distribution,
  target noise, ...) to build heterogeneous fleets; ``stack_agent_params``
  combines arbitrary per-agent rows.

``as_param_sampler`` bundles the two into the ``ParamSampler`` that
``run_gated_sgd`` / ``run_sweep`` consume.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from repro.core import vfa as vfa_lib
from repro.core.algorithm1 import ParamSampler, ProblemTerms

Array = jax.Array


class EnvFamily(NamedTuple):
    """A stacked family of environments — the sweep engine's env grid axis.

    ``params`` is a pytree whose leaves carry a leading instance axis
    (E, ...) — for tabular envs ``{"P": (E, S, A, S), "c": (E, S),
    "gamma": (E,)}`` — consumed by a THREE-argument sampler
    ``fn(env_params, agent_params, rng)`` (``family_sampler_fn``).
    ``terms`` optionally stacks the exact ``ProblemTerms`` per instance
    (leaves (E, ...)), enabling the theoretical trigger and per-env J
    summaries inside one jitted sweep.  Passed to ``run_sweep(env_sets=...)``
    it becomes the outermost grid axis.
    """

    params: object
    terms: Optional[ProblemTerms] = None

    @property
    def num_instances(self) -> int:
        return int(jax.tree.leaves(self.params)[0].shape[0])


@runtime_checkable
class Env(Protocol):
    """Structural protocol — GridWorld, GarnetMDP and LinearSystem satisfy it."""

    def vfa_problem(self, v_current) -> vfa_lib.VFAProblem: ...

    def sampler_fn(self, num_samples: int): ...

    def agent_params(self, v_current, num_agents: int): ...


def stack_agent_params(*rows) -> object:
    """Stack per-agent parameter pytrees (each leaf gains a leading m axis).

    Rows must share a treedef; use an env's single-agent param builders to
    make them, e.g. ``stack_agent_params(good, junk)`` for Fig 2's
    heterogeneous regime.
    """
    return jax.tree.map(lambda *leaves: jax.numpy.stack(leaves), *rows)


def stack_env_fleets(fleets) -> object:
    """Stack one agent fleet PER ENV INSTANCE into the zipped fleet axis.

    ``fleets`` is a sequence of E per-env agent-param pytrees (each with
    leaves (m, ...), e.g. from ``stack_agent_params``); the result's leaves
    are (E, m, ...) — the ``fleet_sets=`` input of ``run_sweep``, gathered
    by the *same* env index as ``env_sets`` inside the jit (zip semantics:
    no extra grid axis).  All fleets must share a treedef and a fleet size
    m (rectangular across the family; vary composition, not cardinality).
    """
    fleets = list(fleets)
    if not fleets:
        raise ValueError("need at least one per-env fleet to stack")
    return jax.tree.map(lambda *leaves: jax.numpy.stack(leaves), *fleets)


def as_param_sampler(env: Env, v_current, num_agents: int,
                     num_samples: int, **agent_kwargs) -> ParamSampler:
    """The env's default homogeneous fleet as a ParamSampler."""
    return ParamSampler(
        fn=env.sampler_fn(num_samples),
        params=env.agent_params(v_current, num_agents, **agent_kwargs),
    )


def family_sampler_fn(num_samples: int):
    """Tabular sampling with the ENV as data: one fn for a whole MDP family.

    ``fn(env_params, agent_params, rng) -> (phi_t (T, S), targets_t (T,))``
    mirrors ``TabularSamplerMixin.sampler_fn`` step for step, but reads the
    transition tensor / cost vector / discount from ``env_params`` instead
    of closing over one instance — so an env family is a grid axis of the
    sweep engine, not a retrace.  Built once per sample count; all
    instances must share (S, A).
    """

    def fn(env_params, params, rng):
        # each stage under a named scope, which names its ops in the
        # compiled program and the device trace
        P, c = env_params["P"], env_params["c"]          # (S, A, S), (S,)
        S, A = P.shape[0], P.shape[1]
        r_x, r_a, r_n, r_t = jax.random.split(rng, 4)
        with jax.named_scope("state_draw"):
            x = jax.random.categorical(r_x, params["visit_logits"],
                                       shape=(num_samples,))
        with jax.named_scope("action_draw"):
            a = jax.random.randint(r_a, (num_samples,), 0, A)
        with jax.named_scope("next_state"):
            x_next = jax.random.categorical(r_n, jnp.log(P[x, a] + 1e-30),
                                            axis=-1)
        with jax.named_scope("target"):
            targets = (c[x] + env_params["gamma"] * params["v"][x_next]
                       + params["noise_scale"]
                       * jax.random.normal(r_t, (num_samples,)))
        with jax.named_scope("features"):
            return jax.nn.one_hot(x, S), targets

    return fn


def family_problem_terms(env_params, v_current: Array) -> ProblemTerms:
    """Exact ``ProblemTerms`` of ONE env-params row at ``V_current`` —
    jax-traceable, so a family stacks via ``jax.vmap`` (uniform policy,
    uniform d, tabular phi: Phi = I/S, b = targets/S)."""
    P_pi = env_params["P"].mean(axis=1)          # uniform policy
    targets = env_params["c"] + env_params["gamma"] * (P_pi @ v_current)
    S = env_params["c"].shape[0]
    return ProblemTerms(
        phi_matrix=jnp.eye(S) / S,
        bvec=targets / S,
        c0=jnp.sum(targets**2) / S,
    )


def stack_env_family(envs, v_current, with_terms: bool = True) -> EnvFamily:
    """Stack tabular env instances into the sweep engine's env grid axis.

    All instances must share (S, A) so the stacked leaves are rectangular;
    heterogeneity across the family lives entirely in the transition /
    cost / discount *values*.  ``with_terms`` also stacks the exact
    ``ProblemTerms`` at ``v_current`` (theoretical trigger, J summaries).
    """
    rows = [e.env_params() for e in envs]
    params = {
        "P": jnp.stack([r["P"] for r in rows]),
        "c": jnp.stack([r["c"] for r in rows]),
        "gamma": jnp.asarray([r["gamma"] for r in rows], jnp.float32),
    }
    terms = None
    if with_terms:
        v = jnp.asarray(v_current, jnp.float32)
        terms = jax.vmap(lambda ep: family_problem_terms(ep, v))(params)
    return EnvFamily(params=params, terms=terms)


class TabularSamplerMixin:
    """Shared parameterized sampling for finite-state envs (tabular phi).

    Host classes provide ``transition_matrix()``, ``cost_vector()``,
    ``num_states``, ``num_actions`` and ``gamma``.  Per-agent parameters:

      * ``v``            — (S,) weights of V_current (tabular phi => V table).
      * ``visit_logits`` — (S,) log-weights of the agent's local state-visit
                           distribution d_i (zeros == the paper's uniform d).
      * ``noise_scale``  — additive N(0, scale^2) target noise, modeling a
                           low-quality / high-noise edge agent.

    Heterogeneity is therefore pure data, so a fleet vmaps and a sweep jits
    once (DESIGN.md §2).
    """

    def env_params(self) -> dict:
        """This instance as the data pytree ``family_sampler_fn`` consumes."""
        return {
            "P": jnp.asarray(self.transition_matrix(), jnp.float32),
            "c": jnp.asarray(self.cost_vector(), jnp.float32),
            "gamma": self.gamma,
        }

    def sampler_fn(self, num_samples: int):
        """(params, rng) -> (phi_t (T, S), targets_t (T,)), jax-pure.

        Delegates to ``family_sampler_fn`` with this instance's env params
        closed over — one arithmetic definition serves both the single-env
        and the env-family sweep paths (parity by construction, not by
        keeping two copies in sync).
        """
        env = self.env_params()
        fam = family_sampler_fn(num_samples)

        def fn(params, rng):
            return fam(env, params, rng)

        return fn

    def agent_param_row(self, v_current: Array,
                        visit_logits: Optional[Array] = None,
                        noise_scale: float = 0.0) -> dict:
        """One agent's sampler parameters (un-stacked)."""
        S = self.num_states
        return {
            "v": jnp.asarray(v_current, jnp.float32),
            "visit_logits": (jnp.zeros((S,), jnp.float32)
                             if visit_logits is None
                             else jnp.asarray(visit_logits, jnp.float32)),
            "noise_scale": jnp.float32(noise_scale),
        }

    def agent_params(self, v_current: Array, num_agents: int,
                     visit_logits: Optional[Array] = None,
                     noise_scale: float = 0.0) -> dict:
        """Homogeneous fleet: the same row stacked m times."""
        row = self.agent_param_row(v_current, visit_logits, noise_scale)
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x, (num_agents,) + x.shape), row)

    def problem_terms(self, v_current: Array) -> ProblemTerms:
        """Exact ``ProblemTerms`` for V_current, jax-traceable (scan-able VI).

        Tabular phi = e_s under uniform d gives Phi = I/S, b = targets/S;
        delegates to ``family_problem_terms`` (one definition for the
        single-env and env-family paths).
        """
        return family_problem_terms(self.env_params(), v_current)
