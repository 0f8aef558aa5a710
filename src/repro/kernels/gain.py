"""Pallas TPU kernels for the paper's gain hot spot (eq. 13 / 15).

The O(T n) quantity is ``proj_t = phi_t . g`` followed by ``sum_t proj_t^2``;
footnote 2 of the paper promises O(T n) per agent and these kernels deliver
it without ever materializing ``Phi_hat = (1/T) sum phi phi^T`` (n x n) in
HBM.  Two entry points:

* ``gain_family_stats`` — the batched-agent *family* kernel the fused sweep
  step runs (DESIGN.md §3).  The grid tiles ``(m, T, n)`` directly — agents
  are a grid axis, not a vmap around a scalar kernel — and one pass over the
  (BM x BT x BN) feature block emits every sufficient statistic the six-mode
  gain family needs: ``||g||^2``, ``sum_t proj_t^2``, ``g . grad_J`` and the
  theoretical quadratic form ``g^T Phi g``.  Each agent's projection block
  accumulates across n-tiles in VMEM scratch (the innermost, sequential grid
  axis) and is squared-and-reduced once per T-tile on the last n-tile; the
  n-scale vector statistics accumulate on the first T-tile only, so nothing
  is computed twice.  One ``pallas_call`` replaces the 3 x m per-agent
  dispatches of the reference path — the call-count reduction
  ``benchmarks/sweep_step.py`` measures.  The single-agent eq.-15 gain of
  the reference step structure is this kernel at m = 1
  (``repro.core.gain_dispatch.practical_gain``).

* ``megastep`` — the whole-inner-step kernel (DESIGN.md §7,
  ``step_backend="megastep"``).  One ``pallas_call`` executes everything
  Algorithm 1's gated-SGD step does after the gradients exist: the family
  statistics above, the per-mode gain derivation, the eq.-9 threshold
  compare (plus the random/always/never baseline gating), and the gated
  aggregate + server weight update (eq. 6) — none of the intermediates
  (per-agent stats, gains, transmit mask, the gated gradient sum) ever
  round-trips through HBM between XLA ops.  The grid carries a leading
  *run-batch* axis ``(R, m-blocks, T-tiles, n-tiles)``: the sweep engine's
  vmap over the flattened run axis lands on a ``jax.custom_batching``
  rule that feeds all R runs x m agents into ONE kernel program instead of
  batching the kernel per run.  The gated gradient sum accumulates in a
  run-wide VMEM scratch row as each agent block's gains complete; the last
  agent block of a run writes ``w_next``.

With the default tiles every BlockSpec obeys the TPU tiling rule — the
last two block dims are multiples of (8, 128) or equal the array's dims —
at any (m, T, n); an override keeps it as long as agent and T tiles stay
multiples of 8 and feature tiles multiples of 128 (or cover the whole
dim).  ``tests/test_tpu_compile.py`` compiles both kernels for a described
v5e at the sweep's real widths.

Block constants below are *defaults*: every kernel entry point takes
per-call overrides, and ``REPRO_KERNEL_BLOCKS`` (comma-separated
``name=int`` pairs, e.g. ``block_m=4,family_block_t=64``) rebinds them
process-wide — read at trace time, so smoke-sized problems and bench-sized
shapes stop sharing one hard-coded tiling.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

# Family-kernel tiles: (BM, BT, BN) = (8, 128, 256) f32 is a 1 MB feature
# block, double-buffered well inside the 16 MB scoped VMEM of a v5e core,
# and every dim is a multiple of the (8, 128) f32 tile.  Not yet tuned on
# the chip (the defaults were first picked against the interpreter).
BLOCK_M = 8
FAMILY_BLOCK_T = 128
FAMILY_BLOCK_N = 256

# Megastep agent block: larger than the family kernel's because the gated
# update needs the full (BM, n) gradient rows resident per agent block
# anyway, and fewer agent blocks directly cut the Phi/grad_J re-streaming
# term of the roofline model (revisits = (m/BM) * (T/BT)).  BM*BT*BN*4B =
# 4 MB of VMEM for the feature block — double-buffered, half the 16 MB
# scoped VMEM of a v5e core.  Not yet tuned on the chip.
MEGASTEP_BLOCK_M = 32

# Column order of the (m, 4) stats array gain_family_stats emits.
STAT_GNORM2, STAT_SUMPROJ2, STAT_GDOTJ, STAT_QUAD = range(4)

# Trigger-mode ids, mirrored from repro.core.gain_dispatch.MODES (kept as
# plain ints here so the kernels stay import-light; pinned by a test).
_MODE_THEORETICAL, _MODE_PRACTICAL, _MODE_NORM = 0, 1, 2
_MODE_RANDOM, _MODE_ALWAYS, _MODE_NEVER = 3, 4, 5

_BLOCKS_ENV = "REPRO_KERNEL_BLOCKS"

# every block constant _block() can resolve; an env override naming
# anything else is a typo that would otherwise silently do nothing
_KNOWN_BLOCKS = ("block_m", "family_block_t", "family_block_n",
                 "megastep_block_m")


def env_blocks() -> dict[str, int]:
    """Parse ``REPRO_KERNEL_BLOCKS`` into a name->int override map."""
    raw = os.environ.get(_BLOCKS_ENV, "")
    out: dict[str, int] = {}
    for item in raw.split(","):
        item = item.strip()
        if not item:
            continue
        if "=" not in item:
            raise ValueError(
                f"{_BLOCKS_ENV} entries must be name=int, got {item!r}")
        name, _, val = item.partition("=")
        name = name.strip()
        if name not in _KNOWN_BLOCKS:
            raise ValueError(
                f"{_BLOCKS_ENV}: unknown block name {name!r} "
                f"(valid names: {', '.join(_KNOWN_BLOCKS)})")
        try:
            out[name] = int(val)
        except ValueError:
            raise ValueError(
                f"{_BLOCKS_ENV}: {name}={val.strip()!r} is not an "
                "integer") from None
    return out


def _block(name: str, override: Optional[int], default: int) -> int:
    """Per-call override > env override > module default (trace-time)."""
    if override is not None:
        return override
    return env_blocks().get(name, default)


# ---------------------------------------------------------------------------
# Batched-agent family kernel (the fused sweep step's one projection pass).
# ---------------------------------------------------------------------------


def _family_kernel(with_model: bool, phi_ref, g_ref, *rest):
    """Kernel body: see module docstring for the accumulation schedule.

    With a model, ``g`` arrives twice — as the (BM, BN) column block
    matching the current n-tile and as the full (BM, n_pad) row the
    quadratic form's second factor needs; both views alias the same HBM
    buffer, so no extra memory moves through the host.  Without one
    (``with_model=False`` — no exact grad J / Phi available), the
    theoretical inputs, their O(m n^2) quadratic-form work and the Phi
    streaming are compiled out entirely and ``out`` carries two columns.
    """
    if with_model:
        gj_ref, pm_ref, gfull_ref, out_ref, proj_ref = rest
    else:
        out_ref, proj_ref = rest
    ti = pl.program_id(1)
    ni = pl.program_id(2)
    nn = pl.num_programs(2)

    @pl.when(jnp.logical_and(ti == 0, ni == 0))
    def _init_out():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(ni == 0)
    def _init_proj():
        proj_ref[...] = jnp.zeros_like(proj_ref)

    phi = phi_ref[...].astype(jnp.float32)            # (BM, BT, BN)
    g = g_ref[...].astype(jnp.float32)                # (BM, BN)
    proj_ref[...] += jax.lax.dot_general(
        phi, g, dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)           # (BM, BT)

    @pl.when(ti == 0)
    def _vector_stats():
        # n-scale statistics accumulate over n-tiles on the first T-tile
        # only, so the compute touches each column block exactly once.
        out_ref[:, STAT_GNORM2] += jnp.sum(g * g, axis=-1)
        if with_model:
            gj = gj_ref[...].astype(jnp.float32)      # (1, BN)
            pm = pm_ref[...].astype(jnp.float32)      # (BN, n_pad)
            gfull = gfull_ref[...].astype(jnp.float32)  # (BM, n_pad)
            out_ref[:, STAT_GDOTJ] += g @ gj[0]
            # quadratic form, row-block at a time:
            # g_blk @ (Phi[blk, :] @ g_full)
            out_ref[:, STAT_QUAD] += jnp.sum(
                jnp.dot(g, pm, preferred_element_type=jnp.float32) * gfull,
                axis=-1)

    @pl.when(ni == nn - 1)
    def _projection_stats():
        p = proj_ref[...]
        out_ref[:, STAT_SUMPROJ2] += jnp.sum(p * p, axis=-1)


def gain_family_stats(phi: Array, g: Array,
                      grad_j: Optional[Array] = None,
                      phi_matrix: Optional[Array] = None,
                      *, interpret: bool = True,
                      block_m: Optional[int] = None,
                      block_t: Optional[int] = None,
                      block_n: Optional[int] = None) -> Array:
    """Per-agent gain-family sufficient statistics in one fused pass.

    Args:
      phi:        (m, T, n) per-agent local feature batches.
      g:          (m, n) per-agent stochastic gradients.
      grad_j:     (n,) exact grad J(w), or None when no model is available.
      phi_matrix: (n, n) exact second moment Phi, or None.

    With a model, returns (m, 4) float32 ``[||g||^2, sum_t (phi_t.g)^2,
    g.grad_J, g^T Phi g]`` — everything eq. 13 / eq. 15 / Remark 4 need, so
    the six trigger modes derive from one projection pass
    (``repro.core.gain_dispatch.mode_gains`` with ``step_backend="fused"``).
    Without one (both None), returns (m, 2) ``[||g||^2, sum proj^2]`` from
    a kernel variant that never streams Phi nor pays the O(m n^2)
    quadratic form — the common practical/norm-only sweep.
    """
    with_model = grad_j is not None and phi_matrix is not None
    m, T, n = phi.shape
    bm = min(_block("block_m", block_m, BLOCK_M), m)
    bt = min(_block("family_block_t", block_t, FAMILY_BLOCK_T), T)
    bn = min(_block("family_block_n", block_n, FAMILY_BLOCK_N), n)
    pad_m = (-m) % bm
    pad_t = (-T) % bt
    pad_n = (-n) % bn
    if pad_m or pad_t or pad_n:
        # zero padding is exact: padded rows/columns contribute 0 to every
        # accumulated statistic, and padded agents are sliced off below
        phi = jnp.pad(phi, ((0, pad_m), (0, pad_t), (0, pad_n)))
        g = jnp.pad(g, ((0, pad_m), (0, pad_n)))
    if pad_n and with_model:
        grad_j = jnp.pad(grad_j, (0, pad_n))
        phi_matrix = jnp.pad(phi_matrix, ((0, pad_n), (0, pad_n)))
    mp, Tp, np_ = phi.shape
    grid = (mp // bm, Tp // bt, np_ // bn)
    in_specs = [
        pl.BlockSpec((bm, bt, bn), lambda ai, ti, ni: (ai, ti, ni)),
        pl.BlockSpec((bm, bn), lambda ai, ti, ni: (ai, ni)),
    ]
    operands = [phi, g]
    cols = 2
    if with_model:
        in_specs += [
            pl.BlockSpec((1, bn), lambda ai, ti, ni: (0, ni)),
            pl.BlockSpec((bn, np_), lambda ai, ti, ni: (ni, 0)),
            pl.BlockSpec((bm, np_), lambda ai, ti, ni: (ai, 0)),
        ]
        operands += [grad_j[None, :], phi_matrix, g]
        cols = 4
    out = pl.pallas_call(
        functools.partial(_family_kernel, with_model),
        name="gain_family_stats",
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, cols), lambda ai, ti, ni: (ai, 0)),
        out_shape=jax.ShapeDtypeStruct((mp, cols), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bt), jnp.float32)],
        interpret=interpret,
    )(*operands)
    return out[:m]


# ---------------------------------------------------------------------------
# Whole-inner-step megastep kernel (gain family + trigger + gated update).
# ---------------------------------------------------------------------------


def _megastep_kernel(with_model: bool, with_deliver: bool,
                     eps: float, num_samples: int, num_agents: int,
                     block_m: int, *refs):
    """Kernel body: one whole gated-SGD step, grid (R, m-blk, T-tile, n-tile).

    Tiles accumulate exactly like ``_family_kernel`` (projection scratch per
    (run, agent-block, T-tile); n-scale stats on the first T-tile only), but
    the statistics stay in VMEM scratch instead of leaving as an output:
    when an agent block's statistics complete (last T-tile, last n-tile) the
    gains are derived, the trigger fires, the block's transmit mask and
    gains are written, and the gated gradient sum accumulates into a
    run-wide scratch row; the last agent block of each run writes
    ``w_next = w - eps * upd / max(cnt, 1)`` (eq. 6).  Per-run control
    scalars ``[threshold, mode_id]`` sit in SMEM as one flat (2R,) array.
    Per-agent values travel as (BM, 1) columns — the layout the lane
    reductions produce — so their blocks satisfy the TPU (8, 128) rule for
    any agent block that is a multiple of 8.

    ``with_deliver`` adds the lossy-channel keep mask (repro.core.channel):
    the gated-update accumulation aggregates ``alphas * deliver`` — one
    extra multiply after the threshold compare — while the alphas output
    stays the attempted transmissions.
    """
    refs = list(refs)
    (ctl_ref, phi_ref, gcol_ref, gfull_ref, arand_ref, w_ref) = refs[:6]
    refs = refs[6:]
    dlv_ref = refs.pop(0) if with_deliver else None
    if with_model:
        gj_ref, pm_ref = refs[:2]
        refs = refs[2:]
    (wout_ref, aout_ref, gout_ref,
     proj_ref, stats_ref, upd_ref, cnt_ref) = refs
    r = pl.program_id(0)
    ai = pl.program_id(1)
    ti = pl.program_id(2)
    ni = pl.program_id(3)
    na = pl.num_programs(1)
    nt = pl.num_programs(2)
    nn = pl.num_programs(3)
    first = jnp.logical_and(ti == 0, ni == 0)
    last = jnp.logical_and(ti == nt - 1, ni == nn - 1)

    @pl.when(jnp.logical_and(ai == 0, first))
    def _init_run():
        upd_ref[...] = jnp.zeros_like(upd_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    @pl.when(first)
    def _init_stats():
        stats_ref[...] = jnp.zeros_like(stats_ref)

    @pl.when(ni == 0)
    def _init_proj():
        proj_ref[...] = jnp.zeros_like(proj_ref)

    phi = phi_ref[...].astype(jnp.float32)          # (BM, BT, BN)
    g = gcol_ref[...].astype(jnp.float32)           # (BM, BN)
    proj_ref[...] += jax.lax.dot_general(
        phi, g, dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)         # (BM, BT)

    @pl.when(ti == 0)
    def _vector_stats():
        stats_ref[:, STAT_GNORM2] += jnp.sum(g * g, axis=-1)
        if with_model:
            gj = gj_ref[0].astype(jnp.float32)                  # (BN,)
            pm = pm_ref[...].astype(jnp.float32)                # (BN, n_pad)
            gfull = gfull_ref[...].astype(jnp.float32)          # (BM, n_pad)
            stats_ref[:, STAT_GDOTJ] += g @ gj
            stats_ref[:, STAT_QUAD] += jnp.sum(
                jnp.dot(g, pm, preferred_element_type=jnp.float32) * gfull,
                axis=-1)

    @pl.when(ni == nn - 1)
    def _projection_stats():
        p = proj_ref[...]
        stats_ref[:, STAT_SUMPROJ2] += jnp.sum(p * p, axis=-1)

    @pl.when(last)
    def _gate_and_update():
        s = stats_ref[...]

        def col(c):                                             # (BM, 1)
            return s[:, c:c + 1]

        prac = (-eps * col(STAT_GNORM2)
                + eps**2 * col(STAT_SUMPROJ2) / num_samples)
        norm = -eps * col(STAT_GNORM2)
        if with_model:
            theo = -eps * col(STAT_GDOTJ) + eps**2 * col(STAT_QUAD)
        else:
            theo = prac   # spec validation keeps mode != theoretical
        thresh = ctl_ref[2 * r]
        mode = ctl_ref[2 * r + 1]
        gains = jnp.where(mode == _MODE_THEORETICAL, theo,
                          jnp.where(mode == _MODE_NORM, norm, prac))
        gate = (gains <= -thresh).astype(jnp.float32)
        alphas = jnp.where(mode == _MODE_ALWAYS, 1.0,
                           jnp.where(mode == _MODE_NEVER, 0.0,
                                     jnp.where(mode == _MODE_RANDOM,
                                               arand_ref[...], gate)))
        # zero padded agents so they never transmit (the gated mean divides
        # by the transmitter count — a phantom always-mode agent would skew
        # it)
        idx = ai * block_m + jax.lax.broadcasted_iota(
            jnp.int32, (block_m, 1), 0)
        alphas = alphas * (idx < num_agents).astype(jnp.float32)
        gout_ref[...] = gains
        aout_ref[...] = alphas
        # channel keep mask: only delivered transmissions enter the update
        eff = alphas * dlv_ref[...] if with_deliver else alphas
        gfull = gfull_ref[...].astype(jnp.float32)              # (BM, n_pad)
        upd_ref[...] += jnp.sum(eff * gfull, axis=0, keepdims=True)
        cnt_ref[...] += jnp.sum(eff, keepdims=True)

    @pl.when(jnp.logical_and(ai == na - 1, last))
    def _write_weights():
        w = w_ref[...].astype(jnp.float32)                      # (1, n_pad)
        upd = upd_ref[...] / jnp.maximum(cnt_ref[...], 1.0)
        wout_ref[...] = w - eps * upd


def megastep_call(phi: Array, g: Array, w: Array, ctl: Array,
                  alpha_rand: Array,
                  grad_j: Optional[Array] = None,
                  phi_matrix: Optional[Array] = None,
                  deliver: Optional[Array] = None,
                  *, eps: float, interpret: bool = True,
                  block_m: Optional[int] = None,
                  block_t: Optional[int] = None,
                  block_n: Optional[int] = None
                  ) -> tuple[Array, Array, Array]:
    """One whole gated-SGD inner step for R runs in a single ``pallas_call``.

    Args (leading axis R = batched runs; the sweep engine's run axis):
      phi:        (R, m, T, n) per-agent local feature batches.
      g:          (R, m, n) per-agent stochastic gradients.
      w:          (R, n) current server weights.
      ctl:        (R, 2) f32 per-run control ``[threshold, mode_id]``; it
                  sits whole in the 1 MiB SMEM of a v5e core, which bounds
                  one call to about 100k runs.
      alpha_rand: (R, m) pre-drawn f32 bernoulli decisions (random mode).
      grad_j:     (R, n) exact grad J(w), or None when no model is given.
      phi_matrix: (n, n) grid-shared — or (R, n, n) per-run — exact second
                  moment Phi, or None.
      deliver:    optional (R, m) 0/1 channel keep mask; when given, the
                  gated update aggregates ``alphas * deliver`` while the
                  alphas output stays the attempted transmissions.

    Returns ``(w_next (R, n), alphas (R, m), gains (R, m))`` — everything
    Algorithm 1's step emits after the gradients: eq. 13/15/Remark-4 gains
    selected by mode, the eq.-9 trigger (with the random/always/never
    baselines), and the eq.-6 server update, with no HBM round-trip between
    the stages.
    """
    with_model = grad_j is not None and phi_matrix is not None
    R, m, T, n = phi.shape
    bm = min(_block("megastep_block_m", block_m, MEGASTEP_BLOCK_M), m)
    bt = min(_block("family_block_t", block_t, FAMILY_BLOCK_T), T)
    bn = min(_block("family_block_n", block_n, FAMILY_BLOCK_N), n)
    pad_m = (-m) % bm
    pad_t = (-T) % bt
    pad_n = (-n) % bn
    if pad_m or pad_t or pad_n:
        # zero padding is exact: padded rows/columns contribute 0 to every
        # statistic and to the gated update, and padded agents are masked
        # out of the transmit count in-kernel
        phi = jnp.pad(phi, ((0, 0), (0, pad_m), (0, pad_t), (0, pad_n)))
        g = jnp.pad(g, ((0, 0), (0, pad_m), (0, pad_n)))
        alpha_rand = jnp.pad(alpha_rand, ((0, 0), (0, pad_m)))
        if deliver is not None:
            deliver = jnp.pad(deliver, ((0, 0), (0, pad_m)))
    if pad_n:
        w = jnp.pad(w, ((0, 0), (0, pad_n)))
        if with_model:
            grad_j = jnp.pad(grad_j, ((0, 0), (0, pad_n)))
            phi_matrix = jnp.pad(
                phi_matrix, ((0, 0),) * (phi_matrix.ndim - 2)
                + ((0, pad_n), (0, pad_n)))
    _, mp, Tp, np_ = phi.shape
    grid = (R, mp // bm, Tp // bt, np_ // bn)
    # Run dims are squeezed (None): every block's last two dims are then
    # agent/feature tiles or whole array dims, never a 1 against R.
    agent_col = pl.BlockSpec((None, bm, 1), lambda r, a, t, i: (r, a, 0))
    run_row = pl.BlockSpec((None, 1, np_), lambda r, a, t, i: (r, 0, 0))
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec((None, bm, bt, bn), lambda r, a, t, i: (r, a, t, i)),
        pl.BlockSpec((None, bm, bn), lambda r, a, t, i: (r, a, i)),
        pl.BlockSpec((None, bm, np_), lambda r, a, t, i: (r, a, 0)),
        agent_col,
        run_row,
    ]
    operands = [ctl.astype(jnp.float32).reshape(2 * R), phi, g, g,
                alpha_rand[..., None], w[:, None, :]]
    with_deliver = deliver is not None
    if with_deliver:
        in_specs.append(agent_col)
        operands.append(deliver[..., None])
    if with_model:
        in_specs.append(
            pl.BlockSpec((None, 1, bn), lambda r, a, t, i: (r, 0, i)))
        if phi_matrix.ndim == 3:
            in_specs.append(
                pl.BlockSpec((None, bn, np_), lambda r, a, t, i: (r, i, 0)))
        else:
            in_specs.append(
                pl.BlockSpec((bn, np_), lambda r, a, t, i: (i, 0)))
        operands += [grad_j[:, None, :], phi_matrix]
    w_next, alphas, gains = pl.pallas_call(
        functools.partial(_megastep_kernel, with_model, with_deliver,
                          eps, T, m, bm),
        name="megastep",
        grid=grid,
        in_specs=in_specs,
        out_specs=[run_row, agent_col, agent_col],
        out_shape=[
            jax.ShapeDtypeStruct((R, 1, np_), jnp.float32),
            jax.ShapeDtypeStruct((R, mp, 1), jnp.float32),
            jax.ShapeDtypeStruct((R, mp, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bm, bt), jnp.float32),    # projection accumulator
            pltpu.VMEM((bm, 4), jnp.float32),     # family statistics
            pltpu.VMEM((1, np_), jnp.float32),    # gated gradient sum
            pltpu.VMEM((1, 1), jnp.float32),      # transmitter count
        ],
        interpret=interpret,
    )(*operands)
    return w_next[:, 0, :n], alphas[:, :m, 0], gains[:, :m, 0]


@functools.lru_cache(maxsize=None)
def _megastep_batched(with_model: bool, with_deliver: bool, eps: float,
                      interpret: bool, block_m: Optional[int],
                      block_t: Optional[int], block_n: Optional[int]):
    """Per-run megastep with a ``custom_vmap`` rule that turns the sweep
    engine's vmap over runs into the kernel's leading grid axis.

    The base function services per-run callers (and the bit-compat
    ``batching="map"`` path) as an R=1 grid; under ``jax.vmap`` the rule
    re-dispatches ONE ``megastep_call`` whose grid leads with the batch
    axis — R runs x m agents in the same program, never a kernel per run.
    A grid-shared ``phi_matrix`` (the common case) stays unbatched all the
    way into the kernel's BlockSpecs instead of being broadcast R times.
    ``with_deliver`` adds the channel keep mask as a batched (m,) operand
    right after ``alpha_rand`` (same shape, same batching rule).
    """
    kw = dict(eps=eps, interpret=interpret, block_m=block_m,
              block_t=block_t, block_n=block_n)

    def _call(phi, g, w, ctl, arand, deliver=None, grad_j=None,
              phi_matrix=None):
        return megastep_call(phi, g, w, ctl, arand, grad_j, phi_matrix,
                             deliver, **kw)

    if with_model and with_deliver:
        @jax.custom_batching.custom_vmap
        def step(phi, g, w, ctl, arand, deliver, grad_j, phi_matrix):
            out = _call(phi[None], g[None], w[None], ctl[None], arand[None],
                        deliver[None], grad_j[None], phi_matrix)
            return jax.tree.map(lambda x: x[0], out)

        @step.def_vmap
        def _rule(axis_size, in_batched, phi, g, w, ctl, arand, deliver,
                  grad_j, phi_matrix):
            def up(x, b):
                return x if b else jnp.broadcast_to(x, (axis_size,) + x.shape)
            args = [up(a, b) for a, b in zip(
                (phi, g, w, ctl, arand, deliver, grad_j), in_batched[:7])]
            # phi_matrix: batched => (R, n, n) per-run slabs; unbatched =>
            # shared (n, n), streamed once for every run's grid programs
            out = _call(*args, phi_matrix)
            return out, (True, True, True)
    elif with_model:
        @jax.custom_batching.custom_vmap
        def step(phi, g, w, ctl, arand, grad_j, phi_matrix):
            out = _call(phi[None], g[None], w[None], ctl[None], arand[None],
                        None, grad_j[None], phi_matrix)
            return jax.tree.map(lambda x: x[0], out)

        @step.def_vmap
        def _rule(axis_size, in_batched, phi, g, w, ctl, arand, grad_j,
                  phi_matrix):
            def up(x, b):
                return x if b else jnp.broadcast_to(x, (axis_size,) + x.shape)
            args = [up(a, b) for a, b in zip(
                (phi, g, w, ctl, arand), in_batched[:5])]
            args += [None, up(grad_j, in_batched[5])]
            # phi_matrix: batched => (R, n, n) per-run slabs; unbatched =>
            # shared (n, n), streamed once for every run's grid programs
            out = _call(*args, phi_matrix)
            return out, (True, True, True)
    elif with_deliver:
        @jax.custom_batching.custom_vmap
        def step(phi, g, w, ctl, arand, deliver):
            out = _call(phi[None], g[None], w[None], ctl[None], arand[None],
                        deliver[None])
            return jax.tree.map(lambda x: x[0], out)

        @step.def_vmap
        def _rule(axis_size, in_batched, phi, g, w, ctl, arand, deliver):
            def up(x, b):
                return x if b else jnp.broadcast_to(x, (axis_size,) + x.shape)
            out = _call(*[up(a, b) for a, b in zip(
                (phi, g, w, ctl, arand, deliver), in_batched)])
            return out, (True, True, True)
    else:
        @jax.custom_batching.custom_vmap
        def step(phi, g, w, ctl, arand):
            out = _call(phi[None], g[None], w[None], ctl[None], arand[None])
            return jax.tree.map(lambda x: x[0], out)

        @step.def_vmap
        def _rule(axis_size, in_batched, phi, g, w, ctl, arand):
            def up(x, b):
                return x if b else jnp.broadcast_to(x, (axis_size,) + x.shape)
            out = _call(*[up(a, b) for a, b in zip(
                (phi, g, w, ctl, arand), in_batched)])
            return out, (True, True, True)

    return step


def megastep(phi: Array, g: Array, w: Array, ctl: Array, alpha_rand: Array,
             grad_j: Optional[Array] = None,
             phi_matrix: Optional[Array] = None,
             deliver: Optional[Array] = None,
             *, eps: float, interpret: bool = True,
             block_m: Optional[int] = None, block_t: Optional[int] = None,
             block_n: Optional[int] = None) -> tuple[Array, Array, Array]:
    """Per-run (no leading R axis) whole-step kernel; vmap-aware.

    Shapes are ``megastep_call``'s without the leading run axis; vmapping
    this function batches the *kernel grid*, not the call.
    """
    with_model = grad_j is not None and phi_matrix is not None
    step = _megastep_batched(
        with_model, deliver is not None, eps, interpret,
        block_m, block_t, block_n)
    args = (phi, g, w, ctl, alpha_rand)
    if deliver is not None:
        args += (deliver,)
    if with_model:
        args += (grad_j, phi_matrix)
    return step(*args)
