"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gain_dispatch
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gain import (
    gain_family_stats,
    megastep,
    megastep_call,
)
from repro.kernels.ssd_scan import ssd_chunk_tiles, ssd_chunked_pallas
from repro.models.ssm import ssd_chunked

from parity import assert_megastep_outputs


@pytest.mark.parametrize("T,n", [(10, 6), (100, 25), (257, 130), (1024, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gain_kernel_sweep(rng, T, n, dtype):
    """The single-agent eq.-15 gain on the Pallas path — the family kernel
    as a one-agent fleet — vs the jnp oracle."""
    phi = jnp.asarray(rng.normal(size=(T, n))).astype(dtype)
    g = jnp.asarray(rng.normal(size=(n,))).astype(dtype)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
    gg = gain_dispatch.practical_gain(g, phi, 0.5, backend="pallas")
    ww = ref.practical_gain_ref(phi, g, 0.5)
    np.testing.assert_allclose(gg, ww, rtol=tol * 5, atol=tol * 10)


@pytest.mark.parametrize("m,T,n", [
    (1, 10, 6),       # below every block size
    (2, 8, 25),       # the repo's typical tiny-fleet shape
    (8, 128, 256),    # exactly one (BM, BT, BN) block
    (13, 100, 30),    # ragged on every axis
    (33, 257, 130),   # ragged + multi-block on every axis
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gain_family_kernel_sweep(rng, m, T, n, dtype):
    """Batched-agent family kernel vs the jnp oracle: one pass emits
    ||g||^2, sum proj^2, g.gradJ and the theoretical quadratic form."""
    phi = jnp.asarray(rng.normal(size=(m, T, n))).astype(dtype)
    g = jnp.asarray(rng.normal(size=(m, n))).astype(dtype)
    gj = jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
    pm = jnp.asarray(rng.normal(size=(n, n)).astype(np.float32))
    got = gain_family_stats(phi, g, gj, pm)
    want = ref.gain_family_stats_ref(phi, g, gj, pm)
    assert got.shape == (m, 4) and got.dtype == jnp.float32
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
    scale = np.abs(np.asarray(want)) + 1.0
    np.testing.assert_allclose(np.asarray(got) / scale,
                               np.asarray(want) / scale, atol=tol)


def test_gain_family_kernel_model_free_variant(rng):
    """Without an exact model the kernel compiles the 2-column variant —
    no Phi streaming, no quadratic form — and matches the oracle prefix."""
    m, T, n = 13, 100, 30
    phi = jnp.asarray(rng.normal(size=(m, T, n)).astype(np.float32))
    g = jnp.asarray(rng.normal(size=(m, n)).astype(np.float32))
    got = gain_family_stats(phi, g)
    assert got.shape == (m, 2)
    want = ref.gain_family_stats_ref(phi, g)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    gj = jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
    pm = jnp.asarray(rng.normal(size=(n, n)).astype(np.float32))
    full = gain_family_stats(phi, g, gj, pm)
    np.testing.assert_array_equal(np.asarray(full[:, :2]), np.asarray(got))


def test_gain_family_kernel_under_vmap(rng):
    """The sweep engine vmaps the kernel over the run axis (per-run grad_J):
    batching must agree with the per-run loop."""
    G, m, T, n = 3, 5, 12, 9
    phi = jnp.asarray(rng.normal(size=(G, m, T, n)).astype(np.float32))
    g = jnp.asarray(rng.normal(size=(G, m, n)).astype(np.float32))
    gj = jnp.asarray(rng.normal(size=(G, n)).astype(np.float32))
    pm = jnp.asarray(rng.normal(size=(n, n)).astype(np.float32))
    got = jax.vmap(lambda p, gg, j: gain_family_stats(p, gg, j, pm))(phi, g, gj)
    for i in range(G):
        want = ref.gain_family_stats_ref(phi[i], g[i], gj[i], pm)
        np.testing.assert_allclose(np.asarray(got[i]), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


def _megastep_inputs(rng, R, m, T, n):
    phi = jnp.asarray(rng.normal(size=(R, m, T, n)).astype(np.float32))
    g = jnp.asarray(rng.normal(size=(R, m, n)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(R, n)).astype(np.float32))
    arand = jnp.asarray(rng.integers(0, 2, size=(R, m)).astype(np.float32))
    gj = jnp.asarray(rng.normal(size=(R, n)).astype(np.float32))
    pm = jnp.asarray(rng.normal(size=(n, n)).astype(np.float32))
    return phi, g, w, arand, gj, pm


@pytest.mark.parametrize("m,T,n,bm", [
    (2, 8, 25, None),     # tiny fleet, below every block
    (5, 37, 23, 4),       # ragged everywhere + padded agents in the mask
    (33, 129, 30, 8),     # multi-block on every axis
])
def test_megastep_kernel_all_modes_vs_oracle(rng, m, T, n, bm):
    """Whole-inner-step kernel vs the jnp oracle: mode-selected gains, the
    eq.-9 trigger (all six modes as runtime data), and the eq.-6 gated
    update.  alphas must be EXACT — a flipped decision diverges weights."""
    R = 2
    phi, g, w, arand, gj, pm = _megastep_inputs(rng, R, m, T, n)
    for mode in range(6):
        thresh = 0.8 * float(jnp.median(jnp.abs(g)))
        ctl = jnp.tile(jnp.asarray([[thresh, float(mode)]], jnp.float32),
                       (R, 1))
        got = megastep_call(phi, g, w, ctl, arand, gj, pm, eps=0.5,
                            block_m=bm)
        want = jax.vmap(lambda p, gg, ww, c, ar, j: ref.megastep_ref(
            p, gg, ww, c, ar, j, pm, eps=0.5))(phi, g, w, ctl, arand, gj)
        assert_megastep_outputs(got, want, label=f"mode {mode}")


def test_megastep_kernel_model_free_variant(rng):
    """No exact model => the 2-column statistics variant; spec validation
    keeps the theoretical mode off this path."""
    R, m, T, n = 2, 5, 20, 9
    phi, g, w, arand, _, _ = _megastep_inputs(rng, R, m, T, n)
    ctl = jnp.tile(jnp.asarray([[0.01, 1.0]], jnp.float32), (R, 1))
    got = megastep_call(phi, g, w, ctl, arand, eps=0.5)
    want = jax.vmap(lambda p, gg, ww, c, ar: ref.megastep_ref(
        p, gg, ww, c, ar, eps=0.5))(phi, g, w, ctl, arand)
    assert_megastep_outputs(got, want, label="model-free", check_gains=False)


def test_megastep_run_axis_bitwise_vs_per_run(rng):
    """The custom_vmap rule batches the kernel GRID: vmapping the per-run
    entry must be bitwise identical to R=1 calls (the sweep engine's
    per-run <-> vmap bit-compat contract rides on this)."""
    R, m, T, n = 4, 5, 12, 9
    phi, g, w, arand, gj, pm = _megastep_inputs(rng, R, m, T, n)
    ctl = jnp.tile(jnp.asarray([[0.01, 1.0]], jnp.float32), (R, 1))
    # shared phi_matrix stays unbatched through the rule (closed over)
    batched = jax.vmap(lambda p, gg, ww, c, ar, j: megastep(
        p, gg, ww, c, ar, j, pm, eps=0.5))(phi, g, w, ctl, arand, gj)
    for r in range(R):
        single = megastep(phi[r], g[r], w[r], ctl[r], arand[r], gj[r], pm,
                          eps=0.5)
        for name, a, b in zip(("w_next", "alphas", "gains"), single, batched):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b[r]),
                                          f"run {r} {name}")


def test_kernel_blocks_env_override(rng, monkeypatch):
    """REPRO_KERNEL_BLOCKS retiles the kernels without changing results
    (the per-call override is exercised by the sweep tests above)."""
    m, T, n = 5, 37, 23
    phi = jnp.asarray(rng.normal(size=(m, T, n)).astype(np.float32))
    g = jnp.asarray(rng.normal(size=(m, n)).astype(np.float32))
    base = gain_family_stats(phi, g)
    monkeypatch.setenv("REPRO_KERNEL_BLOCKS",
                       "block_m=2, family_block_t=16, family_block_n=8")
    retiled = gain_family_stats(phi, g)
    np.testing.assert_allclose(np.asarray(retiled), np.asarray(base),
                               rtol=1e-5, atol=1e-5)
    monkeypatch.setenv("REPRO_KERNEL_BLOCKS", "family_block_t=oops")
    with pytest.raises(ValueError):
        gain_family_stats(phi, g)
    monkeypatch.setenv("REPRO_KERNEL_BLOCKS", "16")
    with pytest.raises(ValueError, match="name=int"):
        gain_family_stats(phi, g)


@pytest.mark.parametrize("case", [
    dict(B=2, Lq=64, Lk=64, H=4, KVH=4, D=32, causal=True, window=0),
    dict(B=1, Lq=128, Lk=128, H=8, KVH=2, D=64, causal=True, window=0),
    dict(B=2, Lq=100, Lk=100, H=4, KVH=1, D=16, causal=True, window=32),
    dict(B=1, Lq=96, Lk=96, H=2, KVH=2, D=128, causal=False, window=0),
    dict(B=1, Lq=160, Lk=160, H=2, KVH=1, D=64, causal=True, window=64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(rng, case, dtype):
    c = case
    q = jnp.asarray(rng.normal(size=(c["B"], c["Lq"], c["H"], c["D"]))).astype(dtype)
    k = jnp.asarray(rng.normal(size=(c["B"], c["Lk"], c["KVH"], c["D"]))).astype(dtype)
    v = jnp.asarray(rng.normal(size=(c["B"], c["Lk"], c["KVH"], c["D"]))).astype(dtype)
    got = flash_attention(q, k, v, causal=c["causal"], window=c["window"],
                          block_q=32, block_k=32)
    want = ref.flash_attention_ref(q, k, v, causal=c["causal"], window=c["window"])
    tol = 3e-2 if dtype == jnp.bfloat16 else 3e-4
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), rtol=tol, atol=tol)


def test_ssd_tile_kernel_vs_oracle(rng):
    B, nc, Q, H, P, N = 2, 3, 32, 4, 16, 8
    dtx = jnp.asarray(rng.normal(size=(B, nc, Q, H, P)).astype(np.float32))
    cum = jnp.asarray(
        (-np.abs(rng.normal(size=(B, nc, Q, H))).cumsum(axis=2) * 0.1).astype(np.float32))
    bm = jnp.asarray(rng.normal(size=(B, nc, Q, N)).astype(np.float32))
    cm = jnp.asarray(rng.normal(size=(B, nc, Q, N)).astype(np.float32))
    y, st = ssd_chunk_tiles(dtx, cum, bm, cm)
    for bi in range(B):
        for ci in range(nc):
            for h in range(H):
                yr, sr = ref.ssd_chunk_ref(dtx[bi, ci, :, h], cum[bi, ci, :, h],
                                           bm[bi, ci], cm[bi, ci])
                np.testing.assert_allclose(y[bi, ci, :, h], yr, rtol=1e-4, atol=1e-4)
                np.testing.assert_allclose(st[bi, ci, h], sr, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("L,chunk", [(64, 32), (200, 64), (128, 128)])
def test_ssd_pallas_full_path(rng, L, chunk):
    B, H, P, N = 2, 4, 16, 8
    xh = jnp.asarray(rng.normal(size=(B, L, H, P)).astype(np.float32))
    dt = jnp.asarray(np.abs(rng.normal(size=(B, L, H))).astype(np.float32) * 0.1)
    a = jnp.asarray(-np.abs(rng.normal(size=(H,))).astype(np.float32))
    bm = jnp.asarray(rng.normal(size=(B, L, N)).astype(np.float32))
    cm = jnp.asarray(rng.normal(size=(B, L, N)).astype(np.float32))
    y1, h1 = ssd_chunked_pallas(xh, dt, a, bm, cm, chunk=chunk)
    y2, h2 = ssd_chunked(xh, dt, a, bm, cm, chunk=chunk)
    np.testing.assert_allclose(y1, y2, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(h1, h2, rtol=2e-4, atol=2e-4)
