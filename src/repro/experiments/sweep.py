"""The device-sharded, memory-streaming sweep engine (DESIGN.md §2).

The paper's headline artifacts — Fig. 2/3 tradeoff curves and the Theorem 1
validation — are grids over (trigger mode x lambda x rho x seed), which the
seed repo executed as hundreds of sequential ``run_gated_sgd`` calls.
Because the refactored Algorithm 1 core is branchless — mode id, thresholds
and the random-transmit probability are all *data* — an entire grid is just
the same compiled program evaluated at many points.  ``run_sweep``:

  1. flattens the requested grid (optional env-family axis x optional
     agent-parameter-set axis x modes x lambdas x rhos x seeds) into
     per-run arrays — an optional *zipped* per-env fleet stack
     (``fleet_sets=``) rides the env axis instead of adding one,
  2. executes ONE jitted call — ``vmap`` (default, fastest), ``lax.map``
     (sequential; bit-identical to per-run execution, used by the parity
     tests), or chunked map-over-vmap (``SweepSpec.chunk_size``) for grids
     larger than memory — over the shared ``gated_sgd_core``,
  3. optionally shards the flattened run axis over a device mesh
     (``mesh=``, see ``repro.launch.mesh.make_sweep_mesh``) with padding to
     a multiple of the device count,
  4. reshapes everything back to the grid and attaches exact-objective
     summaries plus a grid-axes descriptor (``SweepResult.axes``).

Memory scaling: ``SweepSpec.trace`` selects the full per-iteration
``InnerTrace`` (default, the bit-compat contract) or the O(1)-memory
streaming ``SummaryTrace`` (``"summary"`` / a ``TraceSpec``) whose peak
live memory is independent of ``num_iterations`` — the policy big-N /
big-grid sweeps should use.

Seeds map to keys exactly as the per-run convention (``jax.random.key(s)``),
so a sweep cell and the corresponding single run see identical randomness.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import warnings
from typing import NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from repro import spans
from repro.core import channel as channel_lib
from repro.core import gain_dispatch
from repro.core import vfa as vfa_lib
from repro.core.algorithm1 import (
    MODE_IDS,
    MODES,
    SAMPLER_STATE_FOLD,
    InnerTrace,
    ParamSampler,
    ProblemTerms,
    SummaryTrace,
    TraceSpec,
    gated_sgd_core,
    resolve_trace,
)
from repro.core.trigger import TriggerConfig

Array = jax.Array

# The grid axes every sweep carries, slowest-varying last-4; env-family and
# agent-param-set axes prepend when requested.  SweepResult.axes reports the
# actual per-result tuple so downstream row builders never guess from ndim.
BASE_AXES = ("mode", "lam", "rho", "seed")


@dataclasses.dataclass(frozen=True)
class SweepSpec:
    """One experiment grid: modes x lambdas x rhos x seeds (all trace-time data).

    ``random_tx_prob`` may be a scalar or anything broadcastable to the grid
    shape — e.g. Fig 2's rate-matched random baseline passes the measured
    per-(regime, lambda) theoretical rates.  ``batching="map"`` trades the
    vmap wall-clock win for bit-identical-to-per-run numerics;
    ``chunk_size`` (vmap only) streams the grid through ``lax.map`` in
    vmapped chunks of that size, bounding live memory for grids larger than
    a device.  ``trace`` selects full per-iteration traces or O(1)-memory
    streaming summaries (see ``repro.core.algorithm1.TraceSpec``).
    """

    modes: tuple[str, ...]
    lambdas: tuple[float, ...]
    seeds: tuple[int, ...]
    rhos: tuple[float, ...]
    eps: float
    num_iterations: int
    num_agents: int
    include_horizon_norm: bool = True
    random_tx_prob: Union[float, np.ndarray] = 0.5
    # 'reference' | 'pallas'; None resolves REPRO_GAIN_BACKEND at trace time
    gain_backend: Optional[str] = None
    # 'reference' | 'fused' shared-projection step | 'megastep' whole-step
    # fusion (DESIGN.md §3); None resolves REPRO_STEP_BACKEND at trace time
    step_backend: Optional[str] = None
    batching: str = "vmap"          # 'vmap' | 'map'
    trace: Union[str, TraceSpec] = "full"   # 'full' | 'summary' | TraceSpec
    chunk_size: Optional[int] = None
    # Lossy-edge channel axis (repro.core.channel): a tuple of ChannelSpec
    # rows adds a leading "channel" grid axis — every row of the grid runs
    # under each channel (drop probability / delay / staleness as traced
    # data; the ring capacities covering the whole set are jit statics).
    # None (default) is the perfect channel: the pre-channel program runs
    # byte-for-byte and the field is dropped from the store's spec payload,
    # so committed hashes never move.
    channel_sets: Optional[tuple] = None
    # Sampling regime (DESIGN.md §11): "iid" (default) draws every batch
    # fresh from the agents' visit distributions — the stateless sampler
    # contract.  "markov" threads per-agent sampler state (e.g. TD(0)
    # chain positions) through the inner scan via the core's
    # ``sampler_state=`` hook; the sampler fn then takes
    # ``(env, params, w, state, rng)`` (family form) or
    # ``(params, w, state, rng)`` and ``run_sweep`` needs a
    # ``state_init_fn``.  The default is dropped from the store's spec
    # payload, so pre-existing committed hashes never move.
    sampling: str = "iid"
    # Experiment label, part of the spec (and store) identity.  Sweeps whose
    # difference lives in *inputs* the spec cannot see — e.g. two fleet
    # compositions over the same grid (heterogeneity studies) — must carry
    # distinct tags so their SweepStore entries do not collide on one hash.
    tag: Optional[str] = None

    def __post_init__(self):
        for m in self.modes:
            if m not in MODES:
                raise ValueError(f"unknown mode {m!r}, must be one of {MODES}")
        if self.batching not in ("vmap", "map"):
            raise ValueError(f"batching must be 'vmap' or 'map', got {self.batching!r}")
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
        resolve_trace(self.trace)   # validates
        if self.channel_sets is not None:
            if not self.channel_sets:
                raise ValueError(
                    "channel_sets must be a non-empty tuple of ChannelSpec "
                    "rows (or None for the perfect channel)")
            coerced = tuple(channel_lib.validate_channel(c, self.num_agents)
                            for c in self.channel_sets)
            object.__setattr__(self, "channel_sets", coerced)
            if (self.step_backend == "megastep"
                    and max(c.delay for c in coerced) > 0):
                raise ValueError(
                    "step_backend='megastep' fuses the server update into "
                    "the per-step kernel and cannot express a channel delay "
                    "> 0; use the reference or fused step backend")
        if self.sampling not in ("iid", "markov"):
            raise ValueError(
                f"sampling must be 'iid' or 'markov', got {self.sampling!r}")
        if self.chunk_size is not None:
            if self.batching != "vmap":
                raise ValueError("chunk_size only applies to batching='vmap' "
                                 "(lax.map is already sequential)")
            if self.chunk_size < 1:
                raise ValueError(f"chunk_size must be >= 1, got {self.chunk_size}")

    @property
    def grid_shape(self) -> tuple[int, int, int, int]:
        return (len(self.modes), len(self.lambdas), len(self.rhos), len(self.seeds))

    def thresholds(self) -> np.ndarray:
        """(L, R, N) threshold schedules — lambda and rho are pure data."""
        out = np.empty(
            (len(self.lambdas), len(self.rhos), self.num_iterations), np.float32)
        for i, lam in enumerate(self.lambdas):
            for j, rho in enumerate(self.rhos):
                out[i, j] = np.asarray(TriggerConfig(
                    lam=lam, rho=rho, num_iterations=self.num_iterations,
                    include_horizon_norm=self.include_horizon_norm).schedule())
        return out


class SweepResult(NamedTuple):
    """Stacked traces + summaries; ``axes`` names the leading grid axes.

    ``trace`` is an ``InnerTrace`` (full) or ``SummaryTrace`` (streaming),
    each leaf carrying the grid shape as its leading axes — e.g.
    ``axes == ("env_set", "mode", "lam", "rho", "seed")`` for an env-family
    sweep.  Downstream consumers (``tradeoff_rows``) index by axis *name*,
    never by ndim, so new leading axes cannot silently mislabel rows.
    """

    trace: Union[InnerTrace, SummaryTrace]
    comm_rate: Array           # (*grid,) eq. 7 per run
    j_final: Optional[Array]   # (*grid,) exact J(w_N), when a problem was given
    axes: tuple[str, ...] = BASE_AXES

    @property
    def final_weights(self) -> Array:
        if isinstance(self.trace, SummaryTrace):
            return self.trace.final_weights
        return self.trace.weights[..., -1, :]


class _RunInputs(NamedTuple):
    """Per-run leaves of the flattened grid (leading axis = padded runs).

    Grid-axis selections are carried as *indices* into the replicated
    param-set / env-family stacks, gathered per run inside the jitted
    program — the host never materializes a per-run copy of the (possibly
    large) environment tensors.
    """

    keys: Array                 # (G,) typed PRNG keys
    mode_ids: Array             # (G,)
    thresholds: Array           # (G, N)
    tx_probs: Array             # (G,)
    set_idx: Optional[Array]    # (G,) index into the param-set stack, or None
    env_idx: Optional[Array]    # (G,) index into the env-family stack, or None
    chan_idx: Optional[Array] = None   # (G,) index into the channel stack


_EXEC_STATICS = ("sampler_fn", "eps", "num_agents", "gain_backend",
                 "step_backend", "batching", "share_params", "fleet_by_env",
                 "per_run_terms", "trace", "chunk_size", "channel_caps",
                 "sampling", "state_init_fn", "mesh")


def _sweep_exec_impl(per_run, w0, shared_params, param_stack, env_stack,
                     env_terms, shared_terms, channel_stack, *, sampler_fn,
                     eps, num_agents, gain_backend, step_backend, batching,
                     share_params, fleet_by_env, per_run_terms, trace,
                     chunk_size, channel_caps, sampling, state_init_fn, mesh):
    def block(per_run, w0, shared_params, param_stack, env_stack, env_terms,
              shared_terms, channel_stack):
        """Execute a (shard-local) block of runs; leading axis = runs."""

        def one(run: _RunInputs):
            # fleet_by_env: the param stack is ZIPPED with the env axis —
            # the same env index gathers both the MDP and its fleet, so a
            # per-env fleet never becomes a cross-product grid axis.
            params = (shared_params if share_params else
                      jax.tree.map(lambda x: x[run.env_idx], param_stack)
                      if fleet_by_env else
                      jax.tree.map(lambda x: x[run.set_idx], param_stack))
            terms = (jax.tree.map(lambda x: x[run.env_idx], env_terms)
                     if per_run_terms else shared_terms)
            chan = (jax.tree.map(lambda x: x[run.chan_idx], channel_stack)
                    if channel_stack is not None else None)
            markov = sampling == "markov"
            if env_stack is not None:
                env = jax.tree.map(lambda x: x[run.env_idx], env_stack)
                if markov:
                    sample_all = lambda st, w, rngs: jax.vmap(
                        sampler_fn, in_axes=(None, 0, None, 0, 0))(
                            env, params, w, st, rngs)
                else:
                    sample_all = lambda rngs: jax.vmap(
                        sampler_fn, in_axes=(None, 0, 0))(env, params, rngs)
            elif markov:
                sample_all = lambda st, w, rngs: jax.vmap(
                    sampler_fn, in_axes=(0, None, 0, 0))(params, w, st, rngs)
            else:
                sample_all = lambda rngs: jax.vmap(sampler_fn)(params, rngs)
            # per-run chain-state init from the run key's fold_in-derived
            # stream — inside the jit, so resumed/segmented executions
            # rebuild the identical state (the same derivation run_td uses;
            # per-run <-> sweep stays bitwise on the map path)
            state = (state_init_fn(params, jax.random.fold_in(
                run.keys, SAMPLER_STATE_FOLD)) if markov else None)
            return gated_sgd_core(
                run.keys, w0, run.mode_ids, run.thresholds, run.tx_probs,
                sample_all, eps, num_agents, terms=terms,
                gain_backend=gain_backend, trace=trace,
                step_backend=step_backend, channel=chan,
                channel_caps=channel_caps, sampler_state=state)

        if batching == "map":
            return jax.lax.map(one, per_run)
        if chunk_size is not None:
            K = per_run.thresholds.shape[0]
            chunked = jax.tree.map(
                lambda x: x.reshape((K // chunk_size, chunk_size) + x.shape[1:]),
                per_run)
            out = jax.lax.map(lambda ch: jax.vmap(one)(ch), chunked)
            return jax.tree.map(
                lambda x: x.reshape((K,) + x.shape[2:]), out)
        return jax.vmap(one)(per_run)

    if mesh is None:
        return block(per_run, w0, shared_params, param_stack, env_stack,
                     env_terms, shared_terms, channel_stack)
    axis = mesh.axis_names[0]
    # check_vma=False: every output is sharded over the run axis (nothing
    # replicated), so the varying-axes check has nothing to verify here,
    # and under it pallas_call would need a vma on each kernel out_shape —
    # the kernels stay mesh-agnostic instead.  Mesh-vs-single parity is
    # asserted directly by tests/test_sweep_sharded.
    sharded = jax.shard_map(
        block, mesh=mesh,
        in_specs=(PartitionSpec(axis),) + (PartitionSpec(),) * 7,
        out_specs=PartitionSpec(axis), check_vma=False)
    return sharded(per_run, w0, shared_params, param_stack, env_stack,
                   env_terms, shared_terms, channel_stack)


_sweep_exec = functools.partial(jax.jit, static_argnames=_EXEC_STATICS)(
    _sweep_exec_impl)

# Segment-loop variant: the sliced per-run inputs are created inside
# ``exec_plan_segment`` and never read again, so XLA may reuse their buffers
# for the outputs (input-output aliasing; verified structurally through
# ``launch.hlo_analysis.donated_aliases`` by tests/test_runtime_resume.py).
# Donation cannot change results — crash-resume stays bitwise identical.
_sweep_exec_donated = functools.partial(
    jax.jit, static_argnames=_EXEC_STATICS, donate_argnums=(0,))(
    _sweep_exec_impl)


class SweepPlan(NamedTuple):
    """The fully-materialized execution plan of one grid (DESIGN.md §8).

    ``plan_sweep`` turns (spec, sampler, stacks) into per-run input arrays
    plus the replicated parameter/env stacks; ``exec_plan`` runs the whole
    padded run axis in one jitted call (what ``run_sweep`` does), while
    ``exec_plan_segment`` runs a half-open ``[start, stop)`` slice of it —
    the chunk-boundary hook the resumable runtime
    (``repro.experiments.runtime``) checkpoints between.  Both paths feed
    ``finalize_sweep``, which trims the padding, restores the grid shape
    and attaches the exact-objective summaries, so a segmented execution is
    assembled by exactly the same code as an uninterrupted one.
    """

    spec: SweepSpec
    per_run: _RunInputs          # padded to ``padded_runs`` rows
    w0: Array
    shared_params: object        # sampler params when no param_sets axis
    param_stack: object          # stacked param sets, or None
    env_stack: object            # stacked env-family params, or None
    env_terms: object            # stacked per-env ProblemTerms, or None
    shared_terms: object         # grid-shared ProblemTerms, or None
    sampler_fn: object
    mesh: object
    gs: tuple[int, ...]          # grid shape ([E,] [P,] M, L, R, S)
    axes: tuple[str, ...]
    num_runs: int                # G: real grid cells
    padded_runs: int             # Gp: multiple of device count x chunk size
    env_indices: Optional[np.ndarray]   # (G,) env index per run, unpadded
    fleet_by_env: bool = False   # param_stack is zipped with the env axis
    channel_stack: object = None  # stacked ChannelInputs (C, ...), or None
    channel_caps: object = None   # static (delay_cap, stale_cap), or None
    # sampler-state initializer for spec.sampling="markov": a *stable*
    # (module-level) jax-pure fn (agent_params, rng) -> state pytree with
    # per-agent leading axes — it rides through jit as a static, so a fresh
    # lambda per call would defeat the compile cache.  None on iid sweeps.
    state_init_fn: object = None

    @property
    def num_devices(self) -> int:
        return (int(np.prod(self.mesh.devices.shape))
                if self.mesh is not None else 1)

    @property
    def segment_runs(self) -> int:
        """Runs per checkpointable segment: chunk_size per device (the
        whole padded axis when the spec does not chunk)."""
        if self.spec.chunk_size is None:
            return self.padded_runs
        return self.spec.chunk_size * self.num_devices

    def segments(self) -> list[tuple[int, int]]:
        """Half-open ``[start, stop)`` run ranges; padding guarantees the
        padded axis divides evenly into segments."""
        s = self.segment_runs
        return [(a, a + s) for a in range(0, self.padded_runs, s)]


def plan_sweep(
    spec: SweepSpec,
    sampler: ParamSampler,
    w0: Array,
    problem: Optional[Union[vfa_lib.VFAProblem, ProblemTerms]] = None,
    *,
    param_sets: Optional[object] = None,
    env_sets: Optional[object] = None,
    fleet_sets: Optional[object] = None,
    mesh=None,
    state_init_fn=None,
) -> SweepPlan:
    """Flatten the requested grid into a ``SweepPlan`` (see ``run_sweep``
    for the argument semantics)."""
    if spec.sampling == "markov" and state_init_fn is None:
        raise ValueError(
            "sampling='markov' threads per-agent sampler state through the "
            "inner scan and needs state_init_fn=(agent_params, rng) -> "
            "state (e.g. repro.core.td.td_init_states)")
    if spec.sampling == "iid" and state_init_fn is not None:
        raise ValueError(
            "state_init_fn was given but spec.sampling is 'iid' — the "
            "stateless sampler contract has no state to initialize; set "
            "SweepSpec(sampling='markov') for stateful (Markovian) sweeps")
    terms = (problem if isinstance(problem, ProblemTerms)
             else ProblemTerms.from_problem(problem) if problem is not None
             else None)
    env_terms = getattr(env_sets, "terms", None) if env_sets is not None else None
    if "theoretical" in spec.modes and terms is None and env_terms is None:
        raise ValueError("theoretical mode needs the exact problem "
                         "(problem= or env_sets with terms)")
    if fleet_sets is not None:
        if env_sets is None:
            raise ValueError("fleet_sets zips one agent fleet per env "
                             "instance — it requires env_sets")
        if param_sets is not None:
            raise ValueError(
                "fleet_sets and param_sets cannot combine: the fleet stack "
                "is already selected by the env index (zip semantics); use "
                "one env family per param regime instead")

    M, L, R, S = spec.grid_shape
    share_params = param_sets is None
    gs: tuple[int, ...] = ()
    axes: tuple[str, ...] = ()
    if env_sets is not None:
        E = int(jax.tree.leaves(env_sets.params)[0].shape[0])
        gs += (E,)
        axes += ("env_set",)
        if fleet_sets is not None:
            for leaf in jax.tree.leaves(fleet_sets):
                if leaf.shape[0] != E:
                    raise ValueError(
                        f"fleet_sets leaves must stack one fleet per env "
                        f"instance: leading axis {leaf.shape[0]} != {E} envs")
                if leaf.shape[1] != spec.num_agents:
                    raise ValueError(
                        f"fleet_sets fleets carry {leaf.shape[1]} agents, "
                        f"spec.num_agents is {spec.num_agents} (fleets must "
                        "be rectangular across the family)")
    if not share_params:
        P = int(jax.tree.leaves(param_sets)[0].shape[0])
        gs += (P,)
        axes += ("param_set",)
    if spec.channel_sets is not None:
        gs += (len(spec.channel_sets),)
        axes += ("channel",)
    gs += (M, L, R, S)
    axes += BASE_AXES
    G = math.prod(gs)

    grid = np.indices(gs).reshape(len(gs), G)
    mi, li, ri, si = grid[-4], grid[-3], grid[-2], grid[-1]
    ei = grid[0] if env_sets is not None else None
    pi = grid[1 if env_sets is not None else 0] if not share_params else None
    # channel is always the innermost leading axis (right before the base 4)
    ci = grid[len(gs) - 5] if spec.channel_sets is not None else None

    # Pad the flattened run axis so it divides evenly over devices and
    # chunks; padding runs recompute existing cells and are dropped by
    # ``finalize_sweep``.
    D = int(np.prod(mesh.devices.shape)) if mesh is not None else 1
    C = spec.chunk_size or 1
    Gp = D * C * math.ceil(G / (D * C))
    pad = np.arange(Gp) % G
    mi, li, ri, si = mi[pad], li[pad], ri[pad], si[pad]

    mode_ids = jnp.asarray([MODE_IDS[m] for m in spec.modes], jnp.int32)[mi]
    thresholds = jnp.asarray(spec.thresholds())[li, ri]            # (Gp, N)
    tx_probs = jnp.asarray(
        np.broadcast_to(np.asarray(spec.random_tx_prob, np.float32), gs)
        .reshape(G)[pad])
    keys = jnp.stack([jax.random.key(int(s)) for s in spec.seeds])[si]

    shared_params = param_stack = None
    if fleet_sets is not None:
        param_stack = jax.tree.map(jnp.asarray, fleet_sets)
    elif share_params:
        shared_params = sampler.params
    else:
        param_stack = jax.tree.map(jnp.asarray, param_sets)
    env_stack = None
    if env_sets is not None:
        env_stack = jax.tree.map(jnp.asarray, env_sets.params)
        if env_terms is not None:
            env_terms = jax.tree.map(jnp.asarray, env_terms)
    channel_stack = channel_caps = None
    if spec.channel_sets is not None:
        channel_stack = channel_lib.stack_channels(
            spec.channel_sets, spec.num_agents)
        channel_caps = channel_lib.channel_caps(spec.channel_sets)

    per_run = _RunInputs(
        keys=keys, mode_ids=mode_ids, thresholds=thresholds,
        tx_probs=tx_probs,
        set_idx=None if share_params else jnp.asarray(pi[pad], jnp.int32),
        env_idx=(jnp.asarray(ei[pad], jnp.int32)
                 if env_sets is not None else None),
        chan_idx=(jnp.asarray(ci[pad], jnp.int32)
                  if spec.channel_sets is not None else None))

    return SweepPlan(
        spec=spec, per_run=per_run, w0=jnp.asarray(w0),
        shared_params=shared_params, param_stack=param_stack,
        env_stack=env_stack,
        env_terms=env_terms if env_terms is not None else None,
        shared_terms=None if env_terms is not None else terms,
        sampler_fn=sampler.fn, mesh=mesh, gs=gs, axes=axes,
        num_runs=G, padded_runs=Gp, env_indices=ei,
        fleet_by_env=fleet_sets is not None,
        channel_stack=channel_stack, channel_caps=channel_caps,
        state_init_fn=state_init_fn)


def _exec_args(plan: SweepPlan, per_run: _RunInputs,
               chunk_size: Optional[int]):
    spec = plan.spec
    args = (per_run, plan.w0, plan.shared_params, plan.param_stack,
            plan.env_stack, plan.env_terms, plan.shared_terms,
            plan.channel_stack)
    kwargs = dict(
        sampler_fn=plan.sampler_fn, eps=spec.eps,
        num_agents=spec.num_agents, gain_backend=spec.gain_backend,
        step_backend=spec.step_backend,
        batching=spec.batching, share_params=plan.param_stack is None,
        fleet_by_env=plan.fleet_by_env,
        per_run_terms=plan.env_terms is not None,
        trace=resolve_trace(spec.trace), chunk_size=chunk_size,
        channel_caps=plan.channel_caps, sampling=spec.sampling,
        state_init_fn=plan.state_init_fn, mesh=plan.mesh)
    return args, kwargs


def _exec(plan: SweepPlan, per_run: _RunInputs, chunk_size: Optional[int],
          donate: bool = False):
    args, kwargs = _exec_args(plan, per_run, chunk_size)
    if not donate:
        return _sweep_exec(*args, **kwargs)
    with warnings.catch_warnings():
        # only same-shape/dtype leaves can alias (e.g. the (runs,) f32
        # tx_probs -> comm_rate pair); jax warns about the rest of the
        # donated slice every lowering — expected here, not actionable
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        return _sweep_exec_donated(*args, **kwargs)


def exec_plan(plan: SweepPlan):
    """The whole padded run axis as one jitted call (``run_sweep``'s path)."""
    return _exec(plan, plan.per_run, plan.spec.chunk_size)


def exec_plan_segment(plan: SweepPlan, start: int, stop: int,
                      donate: bool = True):
    """One checkpointable segment ``[start, stop)`` of the padded run axis.

    Dispatched as its own (cached-compile) call so the resumable runtime
    can checkpoint between segments; vmapped-segment results are bitwise
    identical to the corresponding rows of ``exec_plan`` on this backend
    (asserted end-to-end by tests/test_runtime_resume.py).

    The per-run input slice is materialized here and not used after the
    call, so its buffers are donated by default — XLA may alias them to
    matching outputs instead of allocating fresh ones (the HLO aliasing is
    asserted by the donation tests); ``plan.per_run`` itself is never
    donated.
    """
    if not (0 <= start < stop <= plan.padded_runs):
        raise ValueError(f"segment [{start}, {stop}) outside "
                         f"[0, {plan.padded_runs})")
    sliced = jax.tree.map(lambda x: x[start:stop], plan.per_run)
    return _exec(plan, sliced, None, donate=donate)


def segment_shapes(plan: SweepPlan):
    """Shape/dtype pytree of one segment's output — traced, never executed.

    The resumable runtime builds its checkpoint-restore template from this
    (``jax.eval_shape`` on the jitted executor), so resuming touches no
    device before the first genuinely-missing segment runs.
    """
    sliced = jax.tree.map(lambda x: x[:plan.segment_runs], plan.per_run)
    args, kwargs = _exec_args(plan, sliced, None)
    return _sweep_exec.eval_shape(*args, **kwargs)


def finalize_sweep(plan: SweepPlan, flat) -> SweepResult:
    """Trim padding, restore the grid shape, attach exact-J summaries."""
    gs, G = plan.gs, plan.num_runs
    flat = jax.tree.map(lambda x: x[:G], flat)
    result = jax.tree.map(lambda x: x.reshape(gs + x.shape[1:]), flat)

    if isinstance(flat, SummaryTrace):
        j_final = result.j_final          # streamed inside the scan
    elif plan.env_terms is not None:
        def _j(i, w):
            t = jax.tree.map(lambda x: x[i], plan.env_terms)
            return t.objective(w)
        j_final = jax.vmap(_j)(jnp.asarray(plan.env_indices, jnp.int32),
                               flat.weights[:, -1, :]).reshape(gs)
    elif plan.shared_terms is not None:
        j_final = jax.vmap(plan.shared_terms.objective)(
            flat.weights[:, -1, :]).reshape(gs)
    else:
        j_final = None
    return SweepResult(trace=result, comm_rate=result.comm_rate,
                       j_final=j_final, axes=plan.axes)


def run_sweep(
    spec: SweepSpec,
    sampler: ParamSampler,
    w0: Array,
    problem: Optional[Union[vfa_lib.VFAProblem, ProblemTerms]] = None,
    *,
    param_sets: Optional[object] = None,
    env_sets: Optional[object] = None,
    fleet_sets: Optional[object] = None,
    mesh=None,
    state_init_fn=None,
) -> SweepResult:
    """Execute the whole grid as one jitted call.

    Args:
      sampler:    the fleet (shared sampling fn + stacked per-agent params).
                  With ``env_sets`` the fn takes THREE arguments
                  ``(env_params, agent_params, rng)`` — see
                  ``repro.envs.base.family_sampler_fn``.
      problem:    exact problem for the theoretical trigger / J summaries
                  (shared across the grid; superseded by per-env terms).
      param_sets: optional pytree of *stacked agent-param sets*, leaves
                  (P, m, ...) — adds a leading ``"param_set"`` axis to the
                  grid (e.g. Fig 2's homogeneous vs heterogeneous regimes in
                  one call).  When given, ``sampler.params`` is ignored.
      env_sets:   optional env family (``repro.envs.base.EnvFamily`` or any
                  object with ``.params`` — leaves (E, ...) — and
                  ``.terms`` — stacked ``ProblemTerms`` or None): adds the
                  outermost ``"env_set"`` axis, so hundreds of random MDPs
                  sweep in the same jitted call.
      fleet_sets: optional pytree of *per-env agent fleets*, leaves
                  (E, m, ...) ZIPPED with the env axis (requires
                  ``env_sets``; exclusive with ``param_sets``): env instance
                  e runs with fleet row e — per-env sampler skew, noise
                  scales, etc. — gathered by the same env index inside the
                  jit.  No grid axis is added, and ``sampler.params`` is
                  ignored.  Build stacks with
                  ``repro.envs.base.stack_env_fleets``.
      mesh:       optional 1-axis device mesh (``launch.mesh.make_sweep_mesh``):
                  the flattened run axis is sharded over its devices via
                  ``shard_map``, padded to a multiple of the device count
                  (and of ``chunk_size``); per-run results are unchanged —
                  bitwise for ``batching="map"``.
      state_init_fn: required iff ``spec.sampling == "markov"``: a stable
                  (module-level) jax-pure ``(agent_params, rng) -> state``
                  building each run's initial sampler-state pytree (e.g.
                  ``repro.core.td.td_init_states`` drawing per-agent chain
                  starts); the rng is derived per run inside the jit as
                  ``fold_in(run_key, SAMPLER_STATE_FOLD)``, so segmented /
                  resumed executions rebuild identical states.

    Returns a SweepResult whose leaves carry the grid shape
    ``([E,] [P,] M, L, R, S)`` and whose ``axes`` names those axes.

    The call's host work is recorded as the span ``sweep.run`` (``runs``,
    ``padded_runs``, ``devices`` and the ``step`` path) with children
    ``sweep.plan``, ``sweep.dispatch`` (until the executor returns its
    unfinished arrays) and ``sweep.finalize`` (``repro.spans``).

    Checkpointable execution of the same grid: ``repro.experiments.runtime
    .run_sweep_resumable`` runs the identical plan segment by segment,
    persisting each completed segment, and reassembles the bit-identical
    ``SweepResult`` after a crash.
    """
    with spans.span("sweep.run") as attrs:
        with spans.span("sweep.plan"):
            plan = plan_sweep(spec, sampler, w0, problem,
                              param_sets=param_sets, env_sets=env_sets,
                              fleet_sets=fleet_sets, mesh=mesh,
                              state_init_fn=state_init_fn)
        step = gain_dispatch._resolve_step(spec.step_backend)
        attrs.update(runs=plan.num_runs, padded_runs=plan.padded_runs,
                     devices=plan.num_devices,
                     step=(step if spec.channel_sets is None
                           else f"{step}+channel"))
        with spans.span("sweep.dispatch"):
            flat = exec_plan(plan)
        with spans.span("sweep.finalize"):
            return finalize_sweep(plan, flat)


def tradeoff_rows(result: SweepResult, spec: SweepSpec, **extra) -> list[dict]:
    """Fig-2-style tradeoff summary: mean over seeds per grid cell.

    Returns one dict per ([env_set,] [param_set,] mode, lambda, rho) with
    the mean communication rate, mean final J (if available) and the
    paper's metric (8) ``lam * comm_rate + J``.  Leading grid axes are read
    from ``result.axes`` — never inferred from array rank — so an env-set
    or device axis cannot mislabel rows.  ``extra`` key/values are attached
    to every row (bench name, regime labels, ...).
    """
    if result.axes[-4:] != BASE_AXES:
        raise ValueError(f"unexpected trailing axes {result.axes!r}")
    lead = result.axes[:-4]
    comm = np.asarray(result.comm_rate).mean(axis=-1)      # seeds out
    jf = (np.asarray(result.j_final).mean(axis=-1)
          if result.j_final is not None else None)
    rows = []
    for idx in np.ndindex(*comm.shape):
        m, l, r = idx[-3], idx[-2], idx[-1]
        row = dict(mode=spec.modes[m], lam=spec.lambdas[l], rho=spec.rhos[r],
                   comm_rate=float(comm[idx]), **extra)
        for name, i in zip(lead, idx):
            row[name] = int(i)
        if jf is not None:
            row["J_final"] = float(jf[idx])
            row["metric8"] = float(spec.lambdas[l] * comm[idx] + jf[idx])
        rows.append(row)
    return rows


def matched_random_probs(result: SweepResult, spec: SweepSpec,
                         mode: str = "theoretical") -> np.ndarray:
    """Per-(cell) transmit probabilities for the rate-matched random baseline.

    Takes the measured comm rates of ``mode`` in ``result``, averages over
    seeds, and broadcasts back to a single-mode grid — ready to be passed as
    ``SweepSpec.random_tx_prob`` for a follow-up ``modes=("random",)`` sweep
    with the same lambdas/rhos/seeds (leading env/param-set axes ride along
    unchanged).
    """
    if result.axes[-4:] != BASE_AXES:
        raise ValueError(f"unexpected trailing axes {result.axes!r}")
    comm = np.asarray(result.comm_rate)
    m = spec.modes.index(mode)
    rates = comm[..., m, :, :, :].mean(axis=-1, keepdims=True)   # (..., L, R, 1)
    return rates[..., None, :, :, :]                             # (..., 1, L, R, 1)
