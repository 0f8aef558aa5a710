"""Single dispatch point for every gain computation (DESIGN.md §3).

The repo grew three gain implementations — the pure-jnp reference
(``repro.core.gain``), the fused Pallas kernels (``repro.kernels.gain``)
and the pytree generalization for deep nets
(``repro.core.fed_sgd.local_gain``).  Algorithm 1 only ever called the
reference, so the kernels never served the hot path.  This module is the one
API the rest of the stack goes through:

* ``practical_gain(g, phi_t, eps, backend=...)`` — eq. 15 in the streaming
  O(T n) form; ``backend="reference"`` is the jnp oracle,
  ``backend="pallas"`` the tiled kernel (interpret-mode off-TPU).  The two
  agree to <= 1e-5 (tests/test_sweep.py::test_gain_dispatch_backend_parity).
* ``theoretical_gain`` / ``norm_gain`` — eq. 13 and the Remark-4 strawman,
  re-exported so callers never import ``repro.core.gain`` directly.
* ``mode_gains`` — the branchless (trace-time mode) form used by the
  batched Algorithm 1 core: evaluates the gain family once per agent and
  selects by mode id, so an entire (mode x lambda x seed) sweep shares one
  jitted program.
* ``family_stats`` — the shared-projection sufficient statistics
  ``[||g||^2, sum_t proj_t^2, g.grad_J, g^T Phi g]`` every mode's gain
  derives from; the heart of the fused step backend.
* ``tree_gain`` — the pytree/HVP path for SPMD training (fed_sgd).

Two orthogonal dispatch axes, both static (they change the compiled
program); everything else is data:

* ``backend`` ("reference" | "pallas") picks the *implementation* of the
  O(T n) projection work: pure jnp, or the Pallas kernels in
  ``repro.kernels.gain`` (interpret mode off-TPU).  Default from
  ``REPRO_GAIN_BACKEND``.
* ``step_backend`` ("reference" | "fused" | "megastep") picks the
  *structure* of the per-step gain family.  "reference" is the original
  three independent vmapped passes (bitwise-unchanged — the oracle the
  parity tests pin against).  "fused" computes the projection
  ``proj = phi @ g`` once per agent per step and derives practical/norm/
  theoretical from the shared ``family_stats``; combined with
  ``backend="pallas"`` the whole family is one batched-agent kernel call
  instead of 3 x m dispatches.  "megastep" widens the fusion boundary to
  the whole inner step: gains, the eq.-9 trigger, and the eq.-6 gated
  server update execute as ONE ``megastep`` dispatch — with
  ``backend="pallas"`` a single VMEM-resident kernel whose scratch carries
  the statistics and the gated gradient sum, and whose grid leads with the
  sweep's run axis (``jax.vmap`` over runs batches the *grid*, not the
  call).  Default from ``REPRO_STEP_BACKEND``.  Both fused and megastep
  match reference to <= 1e-5 across all six modes (tests/test_sweep.py).

The env-var defaults are read at trace time: processes that flip them
mid-run must not reuse already-jitted callables (the repo's test/CI jobs
set them per process).
"""

from __future__ import annotations

import os
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import gain as _ref
from repro.kernels import ops as _kernel_ops

Array = jax.Array

BACKENDS = ("reference", "pallas")
STEP_BACKENDS = ("reference", "fused", "megastep")

# Mode ids shared with repro.core.algorithm1 (kept here so the gain selection
# and the trigger selection use the same enum without a circular import).
MODES = ("theoretical", "practical", "norm", "random", "always", "never")
MODE_THEORETICAL, MODE_PRACTICAL, MODE_NORM, MODE_RANDOM, MODE_ALWAYS, MODE_NEVER = range(6)


def default_backend() -> str:
    return os.environ.get("REPRO_GAIN_BACKEND", "reference")


def default_step_backend() -> str:
    return os.environ.get("REPRO_STEP_BACKEND", "reference")


def _resolve(backend: Optional[str]) -> str:
    backend = backend or default_backend()
    if backend not in BACKENDS:
        raise ValueError(f"backend must be one of {BACKENDS}, got {backend!r}")
    return backend


def _resolve_step(step_backend: Optional[str]) -> str:
    step_backend = step_backend or default_step_backend()
    if step_backend not in STEP_BACKENDS:
        raise ValueError(
            f"step_backend must be one of {STEP_BACKENDS}, got {step_backend!r}")
    return step_backend


def practical_gain(g: Array, phi_t: Array, eps: float,
                   *, backend: Optional[str] = None) -> Array:
    """Eq. 15 streaming gain, O(T n): -eps ||g||^2 + eps^2 (1/T) sum (phi_t.g)^2.

    ``backend="pallas"`` runs the projection through the family kernel as
    a one-agent fleet, so Algorithm 1's hot spot runs the same kernel the
    fused and megastep paths use; off-TPU it executes in interpret mode.
    """
    if _resolve(backend) == "pallas":
        # kernels.ops selects interpret mode by platform (compiled on TPU)
        # and accumulates in f32 regardless of input dtype.
        gnorm2, sumproj2 = _kernel_ops.gain_family_stats(
            phi_t[None], g[None])[0]
        return -eps * gnorm2 + eps**2 * sumproj2 / phi_t.shape[0]
    return _ref.practical_gain_streaming(g, phi_t, eps)


def theoretical_gain(g: Array, grad_j: Array, phi_matrix: Array, eps: float) -> Array:
    """Eq. 13 exact gain (needs the true grad J and second moment Phi)."""
    return _ref.theoretical_gain(g, grad_j, phi_matrix, eps)


def norm_gain(g: Array, eps: float) -> Array:
    """Remark 4 ablation: -eps ||g||^2 (curvature-blind)."""
    return _ref.gain_norm_only(g, eps)


class FamilyStats(NamedTuple):
    """Shared per-agent sufficient statistics of the whole gain family.

    One projection pass yields everything eq. 13 / eq. 15 / Remark 4 need:

      practical = -eps * gnorm2 + eps^2 * sumproj2 / T
      norm      = -eps * gnorm2
      theoretical = -eps * gdotj + eps^2 * quad

    ``gdotj``/``quad`` are None when no exact model is available (the
    theoretical trigger is then invalid anyway — spec validation rejects it).
    """

    gnorm2: Array             # (m,) ||g_i||^2
    sumproj2: Array           # (m,) sum_t (phi_it . g_i)^2
    gdotj: Optional[Array]    # (m,) g_i . grad J(w)
    quad: Optional[Array]     # (m,) g_i^T Phi g_i


def family_stats(
    grads: Array,
    phi_t: Array,
    grad_j: Optional[Array],
    phi_matrix: Optional[Array],
    *,
    backend: Optional[str] = None,
) -> FamilyStats:
    """Compute the gain family's sufficient statistics in one pass.

    ``backend="pallas"`` runs the batched-agent family kernel
    (``repro.kernels.gain.gain_family_stats``): ONE ``pallas_call`` whose
    grid tiles (m, T, n) directly, versus the reference path's m-per-mode
    dispatches.  When no exact model is given the kernel still runs (with
    zero placeholders for grad_J / Phi) and the theoretical columns are
    dropped.
    """
    have_model = grad_j is not None and phi_matrix is not None
    if _resolve(backend) == "pallas":
        # model presence is static, so the no-model case compiles the
        # 2-column kernel variant — no zero-Phi streaming, no O(m n^2)
        # quadratic-form work on practical/norm-only sweeps
        stats = _kernel_ops.gain_family_stats(
            phi_t, grads, grad_j if have_model else None,
            phi_matrix if have_model else None)
        return FamilyStats(
            gnorm2=stats[:, 0], sumproj2=stats[:, 1],
            gdotj=stats[:, 2] if have_model else None,
            quad=stats[:, 3] if have_model else None)
    gf = grads.astype(jnp.float32)
    proj = jax.vmap(lambda p, g: p.astype(jnp.float32) @ g)(phi_t, gf)
    return FamilyStats(
        gnorm2=jnp.sum(gf * gf, axis=-1),
        sumproj2=jnp.sum(proj * proj, axis=-1),
        gdotj=gf @ grad_j if have_model else None,
        quad=jnp.sum((gf @ phi_matrix) * gf, axis=-1) if have_model else None)


def gains_from_stats(mode_id: Array | int, stats: FamilyStats, eps: float,
                     num_samples: int) -> Array:
    """Derive the branchless mode selection from shared family statistics."""
    prac = -eps * stats.gnorm2 + eps**2 * stats.sumproj2 / num_samples
    norm = -eps * stats.gnorm2
    if stats.gdotj is None or stats.quad is None:
        theo = prac  # spec validation guarantees mode_id != theoretical
    else:
        theo = -eps * stats.gdotj + eps**2 * stats.quad
    return jnp.where(mode_id == MODE_THEORETICAL, theo,
                     jnp.where(mode_id == MODE_NORM, norm, prac))


def mode_gains(
    mode_id: Array | int,
    grads: Array,
    phi_t: Array,
    eps: float,
    grad_j: Optional[Array],
    phi_matrix: Optional[Array],
    *,
    backend: Optional[str] = None,
    step_backend: Optional[str] = None,
) -> Array:
    """Per-agent gains for a (possibly traced) trigger-mode id.

    Args:
      mode_id:    scalar int (static or traced) in ``range(len(MODES))``.
      grads:      (m, n) per-agent stochastic gradients.
      phi_t:      (m, T, n) per-agent local feature batches.
      grad_j:     (n,) exact grad J(w), or None when no model is available.
      phi_matrix: (n, n) exact second moment, or None.

    Returns (m,) gains: eq. 13 for the theoretical mode, the norm-only
    ablation for "norm", and eq. 15 for every other mode (random/always/
    never log the practical estimate, matching the reference semantics).
    The selection is branchless so ``mode_id`` can vary across a vmapped
    sweep without retracing.

    ``step_backend="fused"`` (and "megastep", for gain-only callers that
    have no trigger/update to fuse) derives all three gains from one shared
    ``family_stats`` pass; ``"reference"`` (default) keeps the original
    three independent vmapped passes, bitwise unchanged.
    """
    if _resolve_step(step_backend) in ("fused", "megastep"):
        stats = family_stats(grads, phi_t, grad_j, phi_matrix,
                             backend=backend)
        return gains_from_stats(mode_id, stats, eps, phi_t.shape[1])
    prac = jax.vmap(lambda gi, pi: practical_gain(gi, pi, eps, backend=backend))(
        grads, phi_t)
    norm = jax.vmap(lambda gi: norm_gain(gi, eps))(grads)
    if grad_j is None or phi_matrix is None:
        theo = prac  # spec validation guarantees mode_id != theoretical
    else:
        theo = jax.vmap(
            lambda gi: theoretical_gain(gi, grad_j, phi_matrix, eps))(grads)
    return jnp.where(mode_id == MODE_THEORETICAL, theo,
                     jnp.where(mode_id == MODE_NORM, norm, prac))


def megastep(
    mode_id: Array | int,
    w: Array,
    grads: Array,
    phi_t: Array,
    eps: float,
    threshold: Array,
    alpha_rand: Array,
    grad_j: Optional[Array],
    phi_matrix: Optional[Array],
    *,
    backend: Optional[str] = None,
    deliver: Optional[Array] = None,
) -> tuple[Array, Array, Array]:
    """One whole gated-SGD inner step: gains + trigger + eq.-6 update.

    The widest fusion boundary (``step_backend="megastep"``): everything
    Algorithm 1's step does after the stochastic gradients comes back in a
    single dispatch — mode-selected gains, the eq.-9 trigger with the
    random/always/never baselines, and the gated server update.

    Args:
      mode_id:    scalar int (static or traced) in ``range(len(MODES))``.
      w:          (n,) current server weights.
      grads:      (m, n) per-agent stochastic gradients.
      phi_t:      (m, T, n) per-agent local feature batches.
      threshold:  scalar lambda_k (traced — a per-iteration schedule entry).
      alpha_rand: (m,) pre-drawn f32 bernoulli decisions for random mode.
      grad_j:     (n,) exact grad J(w), or None when no model is available.
      phi_matrix: (n, n) exact second moment, or None.
      deliver:    optional (m,) 0/1 channel keep mask (repro.core.channel):
                  the update aggregates ``alphas * deliver`` — one extra
                  multiply after the threshold compare — while the returned
                  ``alphas`` stay the *attempted* transmissions.

    Returns ``(w_next (n,), alphas (m,), gains (m,))``.

    ``backend="pallas"`` executes the step as ONE VMEM-resident kernel
    (``repro.kernels.gain.megastep``): the family statistics, gains, the
    transmit mask, and the gated gradient sum never leave VMEM, and
    ``jax.vmap`` over runs batches the kernel *grid* (R runs x m agents in
    one program) instead of dispatching a kernel per run.
    ``backend="reference"`` is the pure-jnp emulation built from the same
    shared ``family_stats`` the fused step backend uses.
    """
    have_model = grad_j is not None and phi_matrix is not None
    if _resolve(backend) == "pallas":
        ctl = jnp.stack([jnp.asarray(threshold, jnp.float32),
                         jnp.asarray(mode_id).astype(jnp.float32)])
        return _kernel_ops.megastep(
            phi_t, grads, w, ctl, alpha_rand,
            grad_j if have_model else None,
            phi_matrix if have_model else None, deliver=deliver, eps=eps)
    stats = family_stats(grads, phi_t, grad_j, phi_matrix, backend=backend)
    gains = gains_from_stats(mode_id, stats, eps, phi_t.shape[1])
    gate = (gains <= -threshold).astype(jnp.float32)
    m = grads.shape[0]
    alphas = jnp.where(mode_id == MODE_ALWAYS, jnp.ones(m),
                       jnp.where(mode_id == MODE_NEVER, jnp.zeros(m),
                                 jnp.where(mode_id == MODE_RANDOM,
                                           alpha_rand, gate)))
    # Same constant-folding barrier as gated_sgd_core's reference path (see
    # the comment there): keeps per-run (concrete mode_id) programs
    # bit-compatible with the traced-mode sweep program.
    if not isinstance(mode_id, jax.core.Tracer):
        alphas = jax.lax.optimization_barrier(alphas)
    gf = grads.astype(jnp.float32)
    eff = alphas if deliver is None else alphas * deliver
    upd = jnp.einsum("m,mn->n", eff, gf) / jnp.maximum(jnp.sum(eff), 1.0)
    return w - eps * upd, alphas, gains


def tree_gain(g: Any, cfg: Any,
              grad_fn: Optional[Callable[[Any], Any]] = None,
              params: Optional[Any] = None) -> Array:
    """Pytree gain for deep-net training (HVP eq. 13 / gnorm ablation).

    Thin re-export of ``repro.core.fed_sgd.local_gain`` so SPMD callers and
    the reference stack share one entry point.  Imported lazily to avoid a
    core <-> fed_sgd import cycle.
    """
    from repro.core import fed_sgd
    return fed_sgd.local_gain(g, cfg, grad_fn=grad_fn, params=params)
