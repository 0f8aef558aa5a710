"""Public jit'd wrappers for the Pallas kernels.

The interpret flag is keyed on the platform: on a TPU every kernel is
compiled by Mosaic, anywhere else it runs through the Pallas interpreter
(how the CPU test suite checks it against the pure-jnp oracles in
``repro.kernels.ref``).  No wrapper falls back to another implementation.
"""

from __future__ import annotations

import functools

import jax

from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.gain import gain_family_stats as _gain_family_stats
from repro.kernels.gain import megastep as _megastep
from repro.kernels.ssd_scan import ssd_chunked_pallas as _ssd

Array = jax.Array


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k"))
def flash_attention(q: Array, k: Array, v: Array, *, causal: bool = True,
                    window: int = 0, block_q: int = 128, block_k: int = 512) -> Array:
    return _flash(q, k, v, causal=causal, window=window, block_q=block_q,
                  block_k=block_k, interpret=_default_interpret())


@jax.jit
def gain_family_stats(phi: Array, g: Array, grad_j=None,
                      phi_matrix=None) -> Array:
    """Batched-agent gain-family statistics in one kernel pass: (m, 4)
    with an exact model, (m, 2) without (the model-free kernel variant)."""
    return _gain_family_stats(phi, g, grad_j, phi_matrix,
                              interpret=_default_interpret())


@functools.partial(jax.jit, static_argnames=("eps",))
def megastep(phi: Array, g: Array, w: Array, ctl: Array, alpha_rand: Array,
             grad_j=None, phi_matrix=None, deliver=None, *,
             eps: float) -> tuple[Array, Array, Array]:
    """One whole gated-SGD inner step (stats + gains + trigger + eq.-6
    update) in a single kernel; vmapping over runs batches the grid.
    ``deliver`` is the optional (m,) lossy-channel keep mask — the update
    aggregates ``alphas * deliver``; alphas stay the attempted decisions."""
    return _megastep(phi, g, w, ctl, alpha_rand, grad_j, phi_matrix, deliver,
                     eps=eps, interpret=_default_interpret())


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_chunked(xh: Array, dt: Array, a: Array, b_mat: Array, c_mat: Array,
                chunk: int = 128):
    return _ssd(xh, dt, a, b_mat, c_mat, chunk=chunk,
                interpret=_default_interpret())
