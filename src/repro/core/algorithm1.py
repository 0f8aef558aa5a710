"""Algorithm 1: Distributed Approximate Value Iteration (paper §II-B, §III-IV).

The inner loop (lines 5-9, the part Theorem 1 analyzes) runs N gated-SGD
iterations for a *fixed* ``V_current``; the outer loop (lines 11-12) replaces
``V_current`` with the fitted approximation and repeats — projected value
iteration [Bertsekas Vol. II Ch. 6].

Everything is pure JAX and, since the batched-sweep refactor (DESIGN.md §2),
*branchless*: the trigger mode is trace-time data (an integer id selected
with masks, not a Python ``if``), thresholds and the random-transmit
probability are arrays, and heterogeneous agents are a single parameterized
sampler vmapped over stacked per-agent parameters.  One compiled program
therefore serves every (mode, lambda, rho, seed) combination, which is what
lets ``repro.experiments.run_sweep`` execute an entire experiment grid as a
single jitted call.

Layers:
  * ``gated_sgd_core``   — the branchless inner loop on raw arrays.
  * ``run_gated_sgd``    — the faithful-reproduction API (config object,
                           legacy closure samplers still accepted).
  * ``run_value_iteration`` / ``run_value_iteration_scan`` — the outer loop
                           (lines 11-12), as a Python loop over closure
                           factories or as a ``lax.scan`` over a
                           jax-traceable parameter builder.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp

from repro.core import channel as channel_lib
from repro.core import gain_dispatch
from repro.core import server as server_lib
from repro.core import vfa as vfa_lib
from repro.core.trigger import TriggerConfig, should_transmit

Array = jax.Array

# sampler(rng) -> (phi_t, targets_t): one agent's T fresh local samples with
# Bellman targets already evaluated under the fixed V_current.  The legacy
# closure form; heterogeneous fleets should use ParamSampler instead.
Sampler = Callable[[Array], tuple[Array, Array]]

MODES = gain_dispatch.MODES
MODE_IDS = {name: i for i, name in enumerate(MODES)}

# fold_in tag deriving a run's sampler-state init key from its run key
# ("TD" in ASCII).  Shared by every caller that initializes stateful
# sampler chains (repro.core.td.run_td, the sweep engine's markov path),
# so per-run and in-sweep trajectories stay bitwise identical.  Never a
# wider jax.random.split — widening a split changes every derived key.
SAMPLER_STATE_FOLD = 0x5444


class ParamSampler(NamedTuple):
    """A single sampling *function* plus stacked per-agent parameters.

    ``fn(params_i, rng) -> (phi_t (T, n), targets_t (T,))`` draws one agent's
    local batch; ``params`` is a pytree whose leaves carry a leading agent
    axis (m, ...).  Heterogeneous agents (differing local distributions /
    noise — where informativeness gating earns its keep) are then just
    different rows of ``params``, and the whole fleet is one ``vmap`` —
    replacing the per-closure Python loop the seed repo used.  Envs build
    these via ``Env.sampler_fn`` / ``Env.agent_params`` (repro.envs.base).
    """

    fn: Callable[[object, Array], tuple[Array, Array]]
    params: object

    @property
    def num_agents(self) -> int:
        leaves = jax.tree.leaves(self.params)
        if not leaves:
            raise ValueError(
                "ParamSampler.params is empty (e.g. None): such samplers "
                "only carry the fn for run_sweep(param_sets=...) and cannot "
                "be used where a concrete fleet is required")
        return int(leaves[0].shape[0])


class InnerTrace(NamedTuple):
    """Per-iteration trace of one inner run (leading axis = N iterations).

    ``alphas`` / ``comm_rate`` are the trigger's *attempted* transmissions
    (eq. 7 accounting, channel or not); ``delivered`` is the channel-masked
    subset that actually reached the server — populated only when the run
    carries a lossy ``channel`` (``None`` otherwise, like the optional
    ``SummaryTrace`` streams).
    """

    weights: Array      # (N+1, n) w_0..w_N
    alphas: Array       # (N, m) transmit decisions
    gains: Array        # (N, m) evaluated gains
    comm_rate: Array    # scalar: (1/N) sum_k mean_i alpha_k^i   (eq. 7)
    delivered: Optional[Array] = None   # (N, m) alpha * channel keep mask


class TraceSpec(NamedTuple):
    """What the *streaming* inner loop materializes (DESIGN.md §2).

    The full trace stacks ``(N+1, n)`` weights per run, which caps sweep
    grids at a single device's HBM once N or the grid is large.  A
    ``TraceSpec`` instead selects O(1)-memory running summaries (always
    carried: final weights, comm rate, per-agent transmit counts and gain
    statistics; ``trace="summary"`` is exactly this default spec) plus,
    optionally, opt-in per-iteration *scalar* streams:

    * ``j_trajectory`` — exact ``J(w_k)`` per iteration via ``ProblemTerms``
      ((N,) scalars instead of (N+1, n) weights; emitted only when ``terms``
      are available — ``None`` otherwise, like ``j_final``).
    * ``alphas`` / ``gains`` — the (N, m) decision/gain stacks, for callers
      that need per-iteration communication detail but not weights.

    Hashable (a NamedTuple of bools), so it rides through ``jax.jit``
    static arguments — the sweep engine passes it via ``SweepSpec.trace``.
    """

    j_trajectory: bool = False
    alphas: bool = False
    gains: bool = False


class SummaryTrace(NamedTuple):
    """Streaming counterpart of ``InnerTrace``: running summaries only.

    Peak live memory is independent of ``num_iterations`` (modulo the
    optional scalar streams selected by ``TraceSpec``) — the property the
    sharded sweep engine relies on for big-N grids; verified by
    tests/test_sweep_sharded.py via ``memory_analysis()``.
    """

    final_weights: Array          # (n,) w_N
    comm_rate: Array              # scalar, eq. 7
    tx_counts: Array              # (m,) per-agent total transmissions
    gain_mean: Array              # (m,) mean evaluated gain per agent
    gain_min: Array               # (m,)
    gain_max: Array               # (m,)
    j_final: Optional[Array]      # scalar exact J(w_N), when terms given
    j_trajectory: Optional[Array]  # (N,) exact J(w_k), TraceSpec.j_trajectory
    alphas: Optional[Array]       # (N, m) when TraceSpec.alphas
    gains: Optional[Array]        # (N, m) when TraceSpec.gains
    # channel accounting (None on the perfect-channel/default path):
    # tx_counts/comm_rate above stay the *attempted* rates; these are the
    # delivered subset after the channel's Bernoulli keep mask.
    delivered_counts: Optional[Array] = None   # (m,) per-agent deliveries
    delivered_rate: Optional[Array] = None     # scalar delivered comm rate


FULL_TRACE = "full"
# "summary" is the strictly-O(1) policy (running summaries only); per-
# iteration scalar streams (J trajectory, alpha/gain stacks) are opt-in
# via an explicit TraceSpec so nobody pays O(N) buffers unknowingly.
SUMMARY_TRACE = TraceSpec()


def resolve_trace(trace) -> Union[str, TraceSpec]:
    """Normalize the trace policy: 'full' | 'summary' | TraceSpec."""
    if trace == "full":
        return "full"
    if trace == "summary":
        return SUMMARY_TRACE
    if isinstance(trace, TraceSpec):
        return trace
    raise ValueError(
        f"trace must be 'full', 'summary' or a TraceSpec, got {trace!r}")


class ProblemTerms(NamedTuple):
    """The exact problem reduced to sufficient statistics (jit-friendly).

    J(w) = w^T Phi w - 2 b^T w + c0  with  Phi = E_d phi phi^T,
    b = E_d[target * phi], c0 = E_d[target^2];  grad J = 2 (Phi w - b).
    ``VFAProblem`` is a plain dataclass (not a pytree), so the branchless
    core carries these three arrays instead.
    """

    phi_matrix: Array   # (n, n)
    bvec: Array         # (n,)
    c0: Array           # scalar

    @classmethod
    def from_problem(cls, problem: vfa_lib.VFAProblem) -> "ProblemTerms":
        phi = problem.second_moment()
        b = jnp.einsum("s,si->i", problem.d_weights * problem.targets,
                       problem.phi_matrix)
        c0 = jnp.sum(problem.d_weights * problem.targets**2)
        return cls(phi_matrix=phi, bvec=b, c0=c0)

    def grad(self, w: Array) -> Array:
        return 2.0 * (self.phi_matrix @ w - self.bvec)

    def objective(self, w: Array) -> Array:
        return w @ (self.phi_matrix @ w) - 2.0 * (self.bvec @ w) + self.c0


@dataclasses.dataclass(frozen=True)
class GatedSGDConfig:
    trigger: TriggerConfig
    eps: float
    num_agents: int
    mode: str = "practical"
    random_tx_prob: float = 0.5   # for mode == "random" (paper's Fig 2 baseline)
    # 'reference' | 'pallas'; None reads REPRO_GAIN_BACKEND at trace time
    gain_backend: Optional[str] = None
    # 'reference' | 'fused' (shared-projection gain family) | 'megastep'
    # (whole-inner-step fusion, DESIGN.md §3); None reads
    # REPRO_STEP_BACKEND at trace time
    step_backend: Optional[str] = None

    def __post_init__(self):
        if self.mode not in MODES:
            raise ValueError(f"mode must be one of {MODES}, got {self.mode!r}")
        if (self.gain_backend is not None
                and self.gain_backend not in gain_dispatch.BACKENDS):
            raise ValueError(
                f"gain_backend must be one of {gain_dispatch.BACKENDS}, "
                f"got {self.gain_backend!r}")
        if (self.step_backend is not None
                and self.step_backend not in gain_dispatch.STEP_BACKENDS):
            raise ValueError(
                f"step_backend must be one of {gain_dispatch.STEP_BACKENDS}, "
                f"got {self.step_backend!r}")


# ---------------------------------------------------------------------------
# Branchless core.
# ---------------------------------------------------------------------------

SampleAll = Callable[[Array], tuple[Array, Array]]   # (m,) rngs -> (m,T,n),(m,T)
# Stateful (Markovian) form, selected by passing sampler_state= to the core:
# (state, w, (m,) rngs) -> (state', (m,T,n), (m,T)).  ``state`` is an
# arbitrary pytree with per-agent leading axes (e.g. the (m,) chain-state
# indices of a federated TD(0) run, repro.core.td); it threads through the
# scan carry exactly like the channel rings — capacities/shapes static,
# contents traced.  The sampler sees the weights the *agent* sees (``w``,
# or ``w_{k-s}`` on the lossy-channel path), which is what lets TD(0)
# bootstrap its targets from the live local model.
StatefulSampleAll = Callable[[object, Array, Array],
                             tuple[object, Array, Array]]


def gated_sgd_core(
    rng: Array,
    w0: Array,
    mode_id: Union[Array, int],
    thresholds: Array,
    tx_prob: Union[Array, float],
    sample_all: Union[SampleAll, StatefulSampleAll],
    eps: float,
    num_agents: int,
    terms: Optional[ProblemTerms] = None,
    gain_backend: Optional[str] = None,
    trace: Union[str, TraceSpec] = "full",
    step_backend: Optional[str] = None,
    channel: Optional[channel_lib.ChannelInputs] = None,
    channel_caps: Optional[tuple[int, int]] = None,
    sampler_state: Optional[object] = None,
) -> Union[InnerTrace, SummaryTrace]:
    """Branchless inner loop of Algorithm 1 (lines 5-9).

    ``mode_id``, ``thresholds`` (N,) and ``tx_prob`` are *data*: the same
    compiled program evaluates every trigger mode, so the function can be
    vmapped over an experiment grid.  Per step it samples all agents'
    batches, evaluates the full gain family through ``gain_dispatch`` and
    mask-selects the configured one (eq. 13 / 15 / Remark 4), applies the
    trigger (eq. 9 — or the random/always/never baselines), and performs the
    server update (eq. 6).  ``step_backend="fused"`` evaluates the family
    from one shared projection pass (DESIGN.md §3); ``"megastep"`` fuses
    the *entire* post-gradient step — gains, trigger, gated update — into
    one ``gain_dispatch.megastep`` dispatch (a single VMEM-resident kernel
    with ``gain_backend="pallas"``); ``"reference"`` (default) is the
    bitwise-pinned original.

    ``trace`` selects what the scan materializes: ``"full"`` (default)
    stacks the per-iteration ``InnerTrace`` exactly as the bit-compat
    contract requires; ``"summary"`` / a ``TraceSpec`` streams O(1)-memory
    running summaries (``SummaryTrace``) so memory is independent of N —
    the policy the device-sharded sweep engine uses for big grids.

    ``channel`` (with its static ring capacities ``channel_caps``; see
    ``repro.core.channel``) switches to the lossy-edge variant: trigger
    decisions stay the attempted transmissions, the server aggregates only
    the delivered subset (Bernoulli keep mask, optional d-step delay ring),
    and agents compute against s-step-stale weights.  ``channel=None``
    (default) executes this exact function body — the perfect-channel
    program is byte-for-byte the pre-channel one.

    ``sampler_state`` (default ``None``) switches the sampler contract to
    the stateful ``StatefulSampleAll`` form: ``sample_all(state, w, rngs)
    -> (state', phi_b, targets_b)``, with the state pytree threaded through
    the scan carry.  This is the Markovian-sampling hook (DESIGN.md §11):
    a federated TD(0) agent carries its current chain state and bootstraps
    targets from the weights it locally observes.  ``None`` is an empty
    pytree in the carry, so every pre-existing stateless program — and
    every committed spec hash — stays byte-identical.
    """
    N = thresholds.shape[0]
    phi_matrix = terms.phi_matrix if terms is not None else None
    trace = resolve_trace(trace)
    # Resolved once at trace time (same contract as the per-call resolution
    # inside gain_dispatch: flipping the env var mid-process must not reuse
    # already-jitted callables).
    step_backend_r = gain_dispatch._resolve_step(step_backend)

    if channel is not None:
        # Static dispatch: the perfect-channel path below stays untouched
        # (same RNG schedule, same ops — the bitwise-invariance contract).
        if channel_caps is None:
            raise ValueError(
                "channel= needs the static ring capacities channel_caps="
                "(delay_cap, stale_cap); build both via "
                "repro.core.channel.channel_inputs(spec, num_agents)")
        if step_backend_r == "megastep" and channel_caps[0] > 1:
            raise NotImplementedError(
                "step_backend='megastep' fuses the server update into the "
                "per-step kernel, which cannot express a transmission delay "
                "(delivered updates must land d steps later); use the "
                "reference or fused step backend for channels with delay > 0")
        return _channel_core(
            rng, w0, mode_id, thresholds, tx_prob, sample_all, eps,
            num_agents, terms, gain_backend, trace, step_backend,
            step_backend_r, channel, channel_caps, sampler_state)

    stateful = sampler_state is not None

    def step_body(w, st, k, rng_k):
        """One gated-SGD step: (w, st, k, rng_k) -> (w_next, st', ...).

        Shared verbatim by the full and summary scans so both trace
        policies execute identical per-step arithmetic.  ``st`` is the
        sampler-state pytree (``None`` — an empty carry — on the
        stateless/i.i.d. path).  Each stage runs under a named scope
        (``keys``, ``sample``, ``grad``, ``gains``, ``trigger``,
        ``update``), which names its ops in the compiled program and the
        device trace and changes no op.
        """
        with jax.named_scope("keys"):
            rngs = jax.random.split(rng_k, num_agents + 1)
        with jax.named_scope("sample"):
            if stateful:
                st, phi_b, targets_b = sample_all(st, w, rngs[:-1])
            else:
                phi_b, targets_b = sample_all(rngs[:-1])
        with jax.named_scope("grad"):
            grads = jax.vmap(vfa_lib.stochastic_gradient,
                             in_axes=(None, 0, 0))(w, phi_b, targets_b)
        with jax.named_scope("gains"):
            grad_j = terms.grad(w) if terms is not None else None
        if step_backend_r == "megastep":
            # the whole rest of the step — gains, trigger, gated update —
            # is one dispatch; rngs[-1] feeds the same bernoulli draw as
            # the reference path so RNG streams match bitwise
            with jax.named_scope("trigger"):
                alpha_rand = jax.random.bernoulli(
                    rngs[-1], tx_prob, (num_agents,)).astype(jnp.float32)
            with jax.named_scope("gains"):
                w_next, alphas, gains = gain_dispatch.megastep(
                    mode_id, w, grads, phi_b, eps, thresholds[k],
                    alpha_rand, grad_j, phi_matrix, backend=gain_backend)
            return w_next, st, alphas, gains
        with jax.named_scope("gains"):
            gains = gain_dispatch.mode_gains(
                mode_id, grads, phi_b, eps, grad_j, phi_matrix,
                backend=gain_backend, step_backend=step_backend)
        with jax.named_scope("trigger"):
            alpha_gate = should_transmit(gains, thresholds[k])
            alpha_rand = jax.random.bernoulli(
                rngs[-1], tx_prob, (num_agents,)).astype(jnp.float32)
            alphas = jnp.where(
                mode_id == gain_dispatch.MODE_ALWAYS, jnp.ones(num_agents),
                jnp.where(mode_id == gain_dispatch.MODE_NEVER,
                          jnp.zeros(num_agents),
                          jnp.where(mode_id == gain_dispatch.MODE_RANDOM,
                                    alpha_rand, alpha_gate)))
            # Barrier so XLA cannot constant-fold alphas when mode_id is
            # static (always-mode all-ones would otherwise fuse differently
            # than the traced-mode program, breaking per-run <-> sweep
            # bit-compatibility).  Only needed — and only legal, the
            # primitive has no batching rule — when mode_id is concrete;
            # traced mode_id keeps alphas runtime.
            if not isinstance(mode_id, jax.core.Tracer):
                alphas = jax.lax.optimization_barrier(alphas)
        with jax.named_scope("update"):
            w_next = server_lib.server_update(w, grads, alphas, eps)
        return w_next, st, alphas, gains

    rngs = jax.random.split(rng, N)

    if trace == "full":
        @jax.named_scope("step")
        def step(carry, inp):
            w, st = carry
            k, rng_k = inp
            w_next, st, alphas, gains = step_body(w, st, k, rng_k)
            return (w_next, st), (w_next, alphas, gains)

        (w_final, _), (ws, alphas, gains) = jax.lax.scan(
            step, (w0, sampler_state), (jnp.arange(N), rngs))
        del w_final
        weights = jnp.concatenate([w0[None], ws], axis=0)
        comm_rate = jnp.mean(alphas)
        return InnerTrace(weights=weights, alphas=alphas, gains=gains,
                          comm_rate=comm_rate)

    @jax.named_scope("step")
    def step_summary(carry, inp):
        w, st, tx_counts, gain_sum, gain_min, gain_max = carry
        k, rng_k = inp
        w_next, st, alphas, gains = step_body(w, st, k, rng_k)
        with jax.named_scope("summary"):
            carry = (w_next, st,
                     tx_counts + alphas,
                     gain_sum + gains,
                     jnp.minimum(gain_min, gains),
                     jnp.maximum(gain_max, gains))
            ys = (terms.objective(w_next)
                  if trace.j_trajectory and terms is not None else None,
                  alphas if trace.alphas else None,
                  gains if trace.gains else None)
        return carry, ys

    m = num_agents
    init = (w0, sampler_state, jnp.zeros((m,)), jnp.zeros((m,)),
            jnp.full((m,), jnp.inf), jnp.full((m,), -jnp.inf))
    (w_final, _, tx_counts, gain_sum, gain_min, gain_max), ys = jax.lax.scan(
        step_summary, init, (jnp.arange(N), rngs))
    j_traj, alphas_s, gains_s = ys
    return SummaryTrace(
        final_weights=w_final,
        comm_rate=jnp.sum(tx_counts) / (N * m),
        tx_counts=tx_counts,
        gain_mean=gain_sum / N,
        gain_min=gain_min,
        gain_max=gain_max,
        j_final=terms.objective(w_final) if terms is not None else None,
        j_trajectory=j_traj,
        alphas=alphas_s,
        gains=gains_s,
    )


def _channel_core(
    rng: Array,
    w0: Array,
    mode_id: Union[Array, int],
    thresholds: Array,
    tx_prob: Union[Array, float],
    sample_all: SampleAll,
    eps: float,
    num_agents: int,
    terms: Optional[ProblemTerms],
    gain_backend: Optional[str],
    trace: Union[str, TraceSpec],
    step_backend: Optional[str],
    step_backend_r: str,
    channel: channel_lib.ChannelInputs,
    channel_caps: tuple[int, int],
    sampler_state: Optional[object] = None,
) -> Union[InnerTrace, SummaryTrace]:
    """Lossy-edge variant of the branchless inner loop (DESIGN.md §10).

    Same per-step trigger arithmetic as ``gated_sgd_core``'s body, wrapped
    in the channel semantics:

    * **staleness** — a ring of the last ``stale_cap`` server weights; the
      agent's whole local computation (stochastic gradients, gains, exact
      grad for the theoretical trigger) reads ``w_{k-s}`` (clamped to
      ``w_0`` while k < s), while the server update still applies to the
      current ``w``.
    * **drop** — ``delivered = alphas * Bernoulli(1 - drop_prob)``; the
      keep mask draws from ``fold_in(rng_k, 1)`` so the agent/trigger key
      schedule is exactly the perfect-channel one (a clean
      ``ChannelSpec()`` reproduces the ``channel=None`` trajectory).
    * **delay** — delivered aggregates enter a ``delay_cap`` pending ring
      (sum + count per slot) and are applied ``d`` steps later with the
      server's masked-mean arithmetic (eq. 6); zeros-init means nothing
      arrives before step d, and the run's last d sends never land.

    The ring capacities are static, the per-run ``delay``/``staleness``/
    ``drop_prob`` are traced — one compiled program serves an entire
    ``channel_sets`` grid axis.
    """
    N = thresholds.shape[0]
    phi_matrix = terms.phi_matrix if terms is not None else None
    delay_cap, stale_cap = channel_caps
    m = num_agents
    stateful = sampler_state is not None

    def step_body(w, st, stale_buf, pend_sum, pend_cnt, k, rng_k):
        # named scopes as in gated_sgd_core's step, plus ``channel``: the
        # drop draw, the stale-weights ring and the delay ring
        with jax.named_scope("keys"):
            rngs = jax.random.split(rng_k, num_agents + 1)
        with jax.named_scope("channel"):
            keep = jax.random.bernoulli(
                jax.random.fold_in(rng_k, 1), 1.0 - channel.drop_prob,
                (num_agents,)).astype(jnp.float32)
            w_stale = jnp.take(stale_buf,
                               (k - channel.staleness) % stale_cap, axis=0)
        with jax.named_scope("sample"):
            if stateful:
                # the stateful sampler sees what the *agent* sees: the s-
                # step-stale weights drive the TD bootstrap, matching the
                # gains/grads
                st, phi_b, targets_b = sample_all(st, w_stale, rngs[:-1])
            else:
                phi_b, targets_b = sample_all(rngs[:-1])
        with jax.named_scope("grad"):
            grads = jax.vmap(vfa_lib.stochastic_gradient,
                             in_axes=(None, 0, 0))(w_stale, phi_b, targets_b)
        with jax.named_scope("gains"):
            grad_j = terms.grad(w_stale) if terms is not None else None
        if step_backend_r == "megastep":
            # delay_cap == 1 here (checked at dispatch): the kernel's fused
            # update IS the immediate arrival; the deliver mask rides into
            # the kernel as one extra multiply after the threshold compare
            with jax.named_scope("trigger"):
                alpha_rand = jax.random.bernoulli(
                    rngs[-1], tx_prob, (num_agents,)).astype(jnp.float32)
            with jax.named_scope("gains"):
                w_next, alphas, gains = gain_dispatch.megastep(
                    mode_id, w, grads, phi_b, eps, thresholds[k],
                    alpha_rand, grad_j, phi_matrix, backend=gain_backend,
                    deliver=keep)
            with jax.named_scope("channel"):
                delivered = alphas * keep
        else:
            with jax.named_scope("gains"):
                gains = gain_dispatch.mode_gains(
                    mode_id, grads, phi_b, eps, grad_j, phi_matrix,
                    backend=gain_backend, step_backend=step_backend)
            with jax.named_scope("trigger"):
                alpha_gate = should_transmit(gains, thresholds[k])
                alpha_rand = jax.random.bernoulli(
                    rngs[-1], tx_prob, (num_agents,)).astype(jnp.float32)
                alphas = jnp.where(
                    mode_id == gain_dispatch.MODE_ALWAYS,
                    jnp.ones(num_agents),
                    jnp.where(mode_id == gain_dispatch.MODE_NEVER,
                              jnp.zeros(num_agents),
                              jnp.where(mode_id == gain_dispatch.MODE_RANDOM,
                                        alpha_rand, alpha_gate)))
                if not isinstance(mode_id, jax.core.Tracer):
                    alphas = jax.lax.optimization_barrier(alphas)
            with jax.named_scope("channel"):
                delivered = alphas * keep
                pend_sum = jax.lax.dynamic_update_index_in_dim(
                    pend_sum, jnp.einsum("m,mn->n", delivered, grads),
                    k % delay_cap, 0)
                pend_cnt = jax.lax.dynamic_update_index_in_dim(
                    pend_cnt, jnp.sum(delivered), k % delay_cap, 0)
                slot = (k - channel.delay) % delay_cap
                arrived = jnp.take(pend_sum, slot, axis=0)
                arrived_cnt = jnp.take(pend_cnt, slot, axis=0)
            with jax.named_scope("update"):
                w_next = w - eps * (arrived / jnp.maximum(arrived_cnt, 1.0))
        with jax.named_scope("channel"):
            stale_buf = jax.lax.dynamic_update_index_in_dim(
                stale_buf, w_next, (k + 1) % stale_cap, 0)
        return (w_next, st, stale_buf, pend_sum, pend_cnt,
                alphas, gains, delivered)

    rngs = jax.random.split(rng, N)
    init_rings = (jnp.broadcast_to(w0, (stale_cap,) + w0.shape),
                  jnp.zeros((delay_cap,) + w0.shape),
                  jnp.zeros((delay_cap,)))

    if trace == "full":
        @jax.named_scope("step")
        def step(carry, inp):
            k, rng_k = inp
            (w_next, st, stale_buf, ps, pc,
             alphas, gains, delivered) = step_body(*carry, k, rng_k)
            return (w_next, st, stale_buf, ps, pc), (w_next, alphas, gains,
                                                     delivered)

        (w_final, *_), (ws, alphas, gains, delivered) = jax.lax.scan(
            step, (w0, sampler_state) + init_rings, (jnp.arange(N), rngs))
        del w_final
        weights = jnp.concatenate([w0[None], ws], axis=0)
        return InnerTrace(weights=weights, alphas=alphas, gains=gains,
                          comm_rate=jnp.mean(alphas), delivered=delivered)

    @jax.named_scope("step")
    def step_summary(carry, inp):
        (w, st, stale_buf, ps, pc, tx_counts, dl_counts,
         gain_sum, gain_min, gain_max) = carry
        k, rng_k = inp
        (w_next, st, stale_buf, ps, pc,
         alphas, gains, delivered) = step_body(w, st, stale_buf, ps, pc,
                                               k, rng_k)
        with jax.named_scope("summary"):
            carry = (w_next, st, stale_buf, ps, pc,
                     tx_counts + alphas,
                     dl_counts + delivered,
                     gain_sum + gains,
                     jnp.minimum(gain_min, gains),
                     jnp.maximum(gain_max, gains))
            ys = (terms.objective(w_next)
                  if trace.j_trajectory and terms is not None else None,
                  alphas if trace.alphas else None,
                  gains if trace.gains else None)
        return carry, ys

    init = (w0, sampler_state) + init_rings + (
        jnp.zeros((m,)), jnp.zeros((m,)), jnp.zeros((m,)),
        jnp.full((m,), jnp.inf), jnp.full((m,), -jnp.inf))
    carry, ys = jax.lax.scan(step_summary, init, (jnp.arange(N), rngs))
    (w_final, _, _, _, _, tx_counts, dl_counts,
     gain_sum, gain_min, gain_max) = carry
    j_traj, alphas_s, gains_s = ys
    return SummaryTrace(
        final_weights=w_final,
        comm_rate=jnp.sum(tx_counts) / (N * m),
        tx_counts=tx_counts,
        gain_mean=gain_sum / N,
        gain_min=gain_min,
        gain_max=gain_max,
        j_final=terms.objective(w_final) if terms is not None else None,
        j_trajectory=j_traj,
        alphas=alphas_s,
        gains=gains_s,
        delivered_counts=dl_counts,
        delivered_rate=jnp.sum(dl_counts) / (N * m),
    )


def make_sample_all(
    sampler: Union[Sampler, tuple, list, ParamSampler], num_agents: int
) -> SampleAll:
    """Adapt any accepted sampler form to the core's batched interface.

    * ``ParamSampler``      -> one vmap over stacked per-agent params.
    * single closure        -> homogeneous fleet, vmap over rngs.
    * tuple/list of closures-> legacy heterogeneous form; identical closures
      collapse to the vmap path, genuinely distinct ones are stacked with a
      Python loop (kept only for back-compat — prefer ParamSampler).
    """
    if isinstance(sampler, ParamSampler):
        if sampler.num_agents != num_agents:
            raise ValueError(
                f"ParamSampler carries {sampler.num_agents} agents, "
                f"config says {num_agents}")
        return lambda rngs: jax.vmap(sampler.fn)(sampler.params, rngs)
    if isinstance(sampler, (tuple, list)):
        if len(sampler) != num_agents:
            raise ValueError("need one sampler per agent")
        if all(s is sampler[0] for s in sampler):
            return lambda rngs: jax.vmap(sampler[0])(rngs)

        def stacked(rngs):
            outs = [s(rngs[i]) for i, s in enumerate(sampler)]
            return (jnp.stack([p for p, _ in outs]),
                    jnp.stack([t for _, t in outs]))
        return stacked
    return lambda rngs: jax.vmap(sampler)(rngs)


# ---------------------------------------------------------------------------
# Faithful-reproduction API.
# ---------------------------------------------------------------------------


def run_gated_sgd(
    rng: Array,
    w0: Array,
    sampler: Union[Sampler, tuple, list, ParamSampler],
    cfg: GatedSGDConfig,
    problem: Optional[vfa_lib.VFAProblem] = None,
    trace: Union[str, TraceSpec] = "full",
) -> Union[InnerTrace, SummaryTrace]:
    """One inner run of Algorithm 1 (lines 5-9) for N iterations, m agents.

    ``problem`` (exact J / Phi) is required for mode == "theoretical" only.
    Thin wrapper over ``gated_sgd_core`` — the sweep engine vmaps the same
    core, so per-run and batched results agree (bit-compatibly on the
    ``batching="map"`` path; see tests/test_sweep.py).  The full-trace
    default is part of that contract; pass ``trace="summary"`` for the
    O(1)-memory streaming summaries.
    """
    if cfg.mode == "theoretical" and problem is None:
        raise ValueError("theoretical mode needs the exact VFAProblem")
    terms = ProblemTerms.from_problem(problem) if problem is not None else None
    return gated_sgd_core(
        rng, w0,
        mode_id=MODE_IDS[cfg.mode],
        thresholds=cfg.trigger.schedule(),
        tx_prob=cfg.random_tx_prob,
        sample_all=make_sample_all(sampler, cfg.num_agents),
        eps=cfg.eps,
        num_agents=cfg.num_agents,
        terms=terms,
        gain_backend=cfg.gain_backend,
        trace=trace,
        step_backend=cfg.step_backend,
    )


run_gated_sgd_jit = functools.partial(jax.jit, static_argnames=("sampler", "cfg"))(
    run_gated_sgd
)


def performance_metric(trace: InnerTrace, lam: float, problem: vfa_lib.VFAProblem) -> Array:
    """The paper's criterion (8): lam * comm_rate + J(w_N) (single realization)."""
    return lam * trace.comm_rate + problem.objective(trace.weights[-1])


# ---------------------------------------------------------------------------
# Outer loop (Algorithm 1 in full): repeat inner fits, replacing V_current.
# ---------------------------------------------------------------------------

# make_sampler(v_weights) builds the per-agent sampler whose Bellman targets
# use V_current(x) = v_weights . phi(x)   (tabular == indicator features).
MakeSampler = Callable[[Array], Sampler]

# make_params(v_weights) -> stacked per-agent sampler params for the outer
# state V_current; must be jax-traceable so the outer loop can lax.scan.
MakeParams = Callable[[Array], object]


def run_value_iteration(
    rng: Array,
    w0: Array,
    make_sampler: MakeSampler,
    cfg: GatedSGDConfig,
    num_outer: int,
    problem_for_v: Optional[Callable[[Array], vfa_lib.VFAProblem]] = None,
) -> tuple[Array, list[InnerTrace]]:
    """Full Algorithm 1 with closure factories: ``num_outer`` Bellman updates.

    Returns the final weights and every inner trace (for comm accounting).
    Kept for back-compat with non-traceable sampler factories; the scan form
    below compiles the whole outer loop into one program.
    """
    traces: list[InnerTrace] = []
    v_weights = w0
    for outer in range(num_outer):
        rng, sub = jax.random.split(rng)
        sampler = make_sampler(v_weights)
        problem = problem_for_v(v_weights) if problem_for_v is not None else None
        trace = run_gated_sgd(sub, v_weights, sampler, cfg, problem=problem)
        v_weights = trace.weights[-1]   # line 11-12: V_current <- V_updated
        traces.append(trace)
    return v_weights, traces


def run_value_iteration_scan(
    rng: Array,
    w0: Array,
    sampler_fn: Callable[[object, Array], tuple[Array, Array]],
    make_params: MakeParams,
    cfg: GatedSGDConfig,
    num_outer: int,
    terms_for_v: Optional[Callable[[Array], ProblemTerms]] = None,
) -> tuple[Array, InnerTrace]:
    """Full Algorithm 1 as one ``lax.scan`` over the outer Bellman updates.

    ``make_params(v_weights)`` rebuilds the stacked per-agent sampler
    parameters from the current V (jax-traceable — e.g.
    ``env.agent_params``); ``terms_for_v`` optionally rebuilds the exact
    problem terms (needed for the theoretical trigger).  Returns the final
    weights and the stacked inner traces (leading axis = outer iteration).
    """
    if cfg.mode == "theoretical" and terms_for_v is None:
        raise ValueError("theoretical mode needs terms_for_v")
    thresholds = cfg.trigger.schedule()
    mode_id = MODE_IDS[cfg.mode]

    def outer(carry, rng_o):
        v_weights = carry
        params = make_params(v_weights)
        terms = terms_for_v(v_weights) if terms_for_v is not None else None
        trace = gated_sgd_core(
            rng_o, v_weights, mode_id, thresholds, cfg.random_tx_prob,
            lambda rngs: jax.vmap(sampler_fn)(params, rngs),
            cfg.eps, cfg.num_agents, terms=terms,
            gain_backend=cfg.gain_backend,
            step_backend=cfg.step_backend)
        return trace.weights[-1], trace

    rngs = jax.random.split(rng, num_outer)
    v_final, traces = jax.lax.scan(outer, w0, rngs)
    return v_final, traces
