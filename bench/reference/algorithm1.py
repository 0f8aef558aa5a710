"""Plain reference of Algorithm 1's inner loop (arXiv:2112.05908, §II-IV).

Written from the paper and nothing else: it imports no module of the system
under test and takes nothing the system has made.  One run, for a fixed
V_current, is N steps of

    every agent i draws T samples and forms   g_i = (2/T) sum_t phi_t (phi_t.w - y_t)
    theoretical gain (eq. 13)                  -eps g_i.gradJ(w) + eps^2 g_i' Phi g_i
    practical gain (eq. 15)                    -eps |g_i|^2 + eps^2 (1/T) sum_t (phi_t.g_i)^2
    trigger (eq. 9)                            alpha_i = [gain_i <= -lambda_k],
                                               lambda_k = lambda / (N rho^(N-1-k))
    server (eq. 6)                             w <- w - eps * sum_i alpha_i g_i / max(sum_i alpha_i, 1)

with gradJ(w) = 2 (Phi w - b).  A lossy channel keeps each transmission with
probability 1 - drop and lands the masked mean of what step k delivered at
step k + delay (nothing lands before step ``delay``; the last ``delay``
aggregates never land).

Randomness is the reference's own stream (``jax.random`` keys derived from
the benchmark seed, never the program's), so the comparison in
``bench/compare.py`` is between two draws of the same distribution.

An environment module (``bench/reference/<env>.py``) supplies the sampler
and the feature contractions.  Every contraction goes through
``contraction(precision)``: "highest" is float32; "high" is the chip's
three-pass bfloat16 product (hi*hi + hi*lo + lo*hi of each operand's
bfloat16 split, summed in float32) and "default" its one-pass product
(hi*hi), both written out so that they compute the same on any backend.
``bench/control.py`` can run this code below the configurations' "highest"
in the program's place, which a CPU test can hold.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

MODES = ("theoretical", "practical")


def thresholds(lam: float, rho: float, num_iterations: int) -> np.ndarray:
    """Eq. 9 with the proof's 1/N: lambda_k = lambda / (N rho^(N-1-k))."""
    k = np.arange(num_iterations)
    return (lam / (num_iterations * rho ** (num_iterations - 1 - k))).astype(
        np.float32)


def contraction(precision: str):
    """``ein(subscripts, a, b)``: a two-operand einsum at ``precision``."""
    if precision == "highest":
        return lambda s, a, b: jnp.einsum(
            s, a, b, precision=jax.lax.Precision.HIGHEST)
    if precision not in ("high", "default"):
        raise ValueError(f"precision must be 'highest', 'high' or "
                         f"'default', got {precision!r}")

    def split(x):
        hi = x.astype(jnp.bfloat16)
        return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)

    def ein(s, a, b):
        (ah, al), (bh, bl) = split(a), split(b)
        part = lambda x, y: jnp.einsum(  # noqa: E731
            s, x, y, preferred_element_type=jnp.float32)
        if precision == "default":
            return part(ah, bh)
        return part(ah, bh) + part(ah, bl) + part(al, bh)

    return ein


def simulate(env, runs, *, eps: float, rho: float, num_iterations: int,
             num_agents: int, num_samples: int, key, channel=None,
             precision: str = "highest") -> dict:
    """Run every ``(mode, lam)`` of ``runs``; returns numpy arrays.

    ``channel`` is ``None`` or ``{"drop_prob": p, "delay": d}``.  The result
    holds, per run, ``final_weights`` (R, n), ``comm_rate`` (R,),
    ``tx_counts`` (R, m), on a lossy channel ``delivered_counts`` (R, m)
    and ``delivered_rate`` (R,) (None on a perfect one), and ``j_final``
    (R,), the objective
    of the final weights evaluated on the device at ``precision``
    (``contraction``'s).
    """
    N, m, T, n = num_iterations, num_agents, num_samples, env.n
    mode_ids = jnp.asarray([MODES.index(mode) for mode, _ in runs])
    thr = jnp.asarray(np.stack([thresholds(lam, rho, N) for _, lam in runs]))
    phi = jnp.asarray(env.phi_matrix, jnp.float32)
    b = jnp.asarray(env.bvec, jnp.float32)
    c0 = jnp.float32(env.c0)
    drop = 0.0 if channel is None else float(channel["drop_prob"])
    delay = 0 if channel is None else int(channel["delay"])
    ein = contraction(precision)

    def step(carry, inp):
        w, fifo_sum, fifo_cnt, tx, dl, mode_id = carry
        thr_k, key_k = inp
        k_sample, k_keep = jax.random.split(key_k)
        feat, y = env.sample(k_sample, m, T)
        resid = env.dot(feat, w, ein) - y                            # (m, T)
        g = (2.0 / T) * env.tdot(feat, resid, ein)                  # (m, n)
        grad_j = 2.0 * (ein("ij,j->i", phi, w) - b)
        theo = (-eps * ein("mn,n->m", g, grad_j)
                + eps**2 * jnp.sum(ein("mn,nk->mk", g, phi) * g, -1))
        proj = env.dot_rows(feat, g, ein)                            # (m, T)
        prac = -eps * jnp.sum(g * g, -1) + eps**2 * jnp.mean(proj * proj, -1)
        gain = jnp.where(mode_id == 0, theo, prac)
        alpha = (gain <= -thr_k).astype(jnp.float32)
        keep = jax.random.bernoulli(k_keep, 1.0 - drop, (m,)).astype(
            jnp.float32)
        sent = alpha * keep
        agg, cnt = ein("m,mn->n", sent, g), jnp.sum(sent)
        if delay:
            land, land_cnt = fifo_sum[0], fifo_cnt[0]
            fifo_sum = jnp.concatenate([fifo_sum[1:], agg[None]])
            fifo_cnt = jnp.concatenate([fifo_cnt[1:], cnt[None]])
        else:
            land, land_cnt = agg, cnt
        w = w - eps * land / jnp.maximum(land_cnt, 1.0)
        return (w, fifo_sum, fifo_cnt, tx + alpha, dl + sent, mode_id), None

    def one(mode_id, thr_r, key_r):
        init = (jnp.zeros((n,), jnp.float32),
                jnp.zeros((delay, n), jnp.float32), jnp.zeros((delay,)),
                jnp.zeros((m,)), jnp.zeros((m,)), mode_id)
        (w, _, _, tx, dl, _), _ = jax.lax.scan(
            step, init, (thr_r, jax.random.split(key_r, N)))
        j = (ein("i,i->", w, ein("ij,j->i", phi, w))
             - 2.0 * ein("i,i->", b, w) + c0)
        return w, jnp.sum(tx) / (N * m), tx, dl, jnp.sum(dl) / (N * m), j

    keys = jax.random.split(key, len(runs))
    # one run at a time: the reference's peak memory stays that of one run
    out = [jax.jit(one)(mode_ids[r], thr[r], keys[r]) for r in range(len(runs))]
    w, comm, tx, dl, dl_rate, j = (np.stack([np.asarray(o[i]) for o in out])
                                   for i in range(6))
    lossy = channel is not None
    return {"final_weights": w, "comm_rate": comm, "tx_counts": tx,
            "delivered_counts": dl if lossy else None,
            "delivered_rate": dl_rate if lossy else None, "j_final": j}
