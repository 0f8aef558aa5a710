"""Kernel microbenchmarks: Pallas (interpret) vs pure-jnp oracle vs XLA ref.

NOTE: wall-times on this CPU container measure the *interpreter*, not TPU
performance — the derived column reports the arithmetic the kernel performs
(GFLOP per call) which is what the TPU roofline consumes.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import gain_dispatch
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gain import gain_family_stats
from repro.kernels.ssd_scan import ssd_chunk_tiles


def _time(fn, *a, reps=3):
    out = fn(*a)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*a)
        jax.block_until_ready(out)
    return out, (time.perf_counter() - t0) / reps * 1e6


def run(smoke: bool = False) -> list[dict]:
    rng = np.random.default_rng(0)
    rows = []

    # single-agent eq.-15 gain: the paper's O(Tn) agent-side computation,
    # on the family kernel as a one-agent fleet
    T, n = (256, 256) if smoke else (4096, 2048)
    phi = jnp.asarray(rng.normal(size=(T, n)).astype(np.float32))
    g = jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
    got, us = _time(lambda: gain_dispatch.practical_gain(
        g, phi, 0.5, backend="pallas"))
    want = ref.practical_gain_ref(phi, g, 0.5)
    err = float(jnp.abs(got - want))
    rows.append(dict(bench="kernel_gain", shape=f"T{T}xn{n}", us_per_call=us,
                     gflop_per_call=2 * T * n / 1e9, max_abs_err=err))

    # batched-agent gain-family kernel: the fused sweep step's one pass over
    # (m, T, n) — the path sweeps actually run (DESIGN.md §3).  FLOPs: the
    # m batched projections (2mTn) plus the per-agent n-scale statistics
    # (norm, g.gradJ: 2mn each; quadratic form: 2mn^2 + 2mn).
    m, Tf, nf = (8, 128, 64) if smoke else (64, 1024, 512)
    phi_b = jnp.asarray(rng.normal(size=(m, Tf, nf)).astype(np.float32))
    g_b = jnp.asarray(rng.normal(size=(m, nf)).astype(np.float32))
    gj = jnp.asarray(rng.normal(size=(nf,)).astype(np.float32))
    pm = jnp.asarray(rng.normal(size=(nf, nf)).astype(np.float32))
    got, us = _time(lambda: gain_family_stats(phi_b, g_b, gj, pm))
    want = ref.gain_family_stats_ref(phi_b, g_b, gj, pm)
    err = float(jnp.max(jnp.abs(got - want) / (jnp.abs(want) + 1.0)))
    flops = 2 * m * Tf * nf + 2 * m * nf**2 + 6 * m * nf
    rows.append(dict(bench="kernel_gain_family", shape=f"m{m}xT{Tf}xn{nf}",
                     us_per_call=us, gflop_per_call=flops / 1e9,
                     max_rel_err=err))

    # flash attention tile
    B, L, H, KVH, D = (1, 256, 2, 1, 64) if smoke else (1, 512, 4, 2, 64)
    q = jnp.asarray(rng.normal(size=(B, L, H, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, L, KVH, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, L, KVH, D)).astype(np.float32))
    got, us = _time(lambda: flash_attention(q, k, v, block_q=128, block_k=128))
    want = ref.flash_attention_ref(q, k, v)
    err = float(jnp.max(jnp.abs(got - want)))
    rows.append(dict(bench="kernel_flash", shape=f"B{B}L{L}H{H}D{D}",
                     us_per_call=us,
                     gflop_per_call=2 * 2 * B * H * L * L * D / 1e9,
                     max_abs_err=err))

    # ssd intra-chunk tile
    Bc, nc, Q, Hh, P, N = ((1, 2, 64, 2, 32, 16) if smoke
                           else (2, 4, 128, 4, 64, 32))
    dtx = jnp.asarray(rng.normal(size=(Bc, nc, Q, Hh, P)).astype(np.float32))
    cum = jnp.asarray((-np.abs(rng.normal(size=(Bc, nc, Q, Hh))).cumsum(2) * 0.1
                       ).astype(np.float32))
    bm = jnp.asarray(rng.normal(size=(Bc, nc, Q, N)).astype(np.float32))
    cm = jnp.asarray(rng.normal(size=(Bc, nc, Q, N)).astype(np.float32))
    (y, st), us = _time(lambda: ssd_chunk_tiles(dtx, cum, bm, cm))
    yr, sr = ref.ssd_chunk_ref(dtx[0, 0, :, 0], cum[0, 0, :, 0], bm[0, 0], cm[0, 0])
    err = float(jnp.max(jnp.abs(y[0, 0, :, 0] - yr)))
    flops = Bc * nc * Hh * (2 * Q * Q * N + 2 * Q * Q * P + 2 * Q * N * P)
    rows.append(dict(bench="kernel_ssd", shape=f"Q{Q}H{Hh}P{P}N{N}",
                     us_per_call=us, gflop_per_call=flops / 1e9,
                     max_abs_err=err))
    return rows
