"""Continuous-state example (paper §V, Fig. 3).

State space X = R^2, dynamics  x_+ = A x + w,  w ~ N(0, sigma2 I), quadratic
cost c(x) = ||x||^2, discount gamma = 0.9.  Value functions are approximated
in the degree-2 polynomial basis

    phi(x) = [x1^2, x2^2, x1 x2, x1, x2, 1]  in R^6,

and the data distribution d is uniform on [0, 1]^2.

This class is *closed under the Bellman operator*: if V_cur is a quadratic
polynomial then c(x) + gamma E[V_cur(Ax + w)] is again a quadratic polynomial
in x, so the exact target coefficients, the exact Phi (moments of the uniform
square), w*, and J(w) are all available in closed form — enabling the
theoretical trigger (eq. 9) and Theorem 1 validation on this example.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import vfa as vfa_lib

Array = jax.Array

N_FEATURES = 6  # [x1^2, x2^2, x1*x2, x1, x2, 1]


def poly_features(x: Array) -> Array:
    """phi(x) for x of shape (..., 2) -> (..., 6)."""
    x1, x2 = x[..., 0], x[..., 1]
    return jnp.stack([x1**2, x2**2, x1 * x2, x1, x2, jnp.ones_like(x1)], axis=-1)


def _quad_from_weights(w: np.ndarray) -> tuple[np.ndarray, np.ndarray, float]:
    """Weights -> (Q, b, c0) with V(x) = x^T Q x + b^T x + c0."""
    Q = np.array([[w[0], w[2] / 2.0], [w[2] / 2.0, w[1]]])
    b = np.array([w[3], w[4]])
    return Q, b, float(w[5])


def _weights_from_quad(Q: np.ndarray, b: np.ndarray, c0: float) -> np.ndarray:
    return np.array([Q[0, 0], Q[1, 1], 2.0 * Q[0, 1], b[0], b[1], c0])


@dataclasses.dataclass(frozen=True)
class LinearSystem:
    a_matrix: tuple = ((0.8, -0.2), (0.1, 1.0))
    noise_var: float = 0.1
    gamma: float = 0.9

    @property
    def A(self) -> np.ndarray:
        return np.asarray(self.a_matrix)

    # -- exact quantities ----------------------------------------------------

    @staticmethod
    def second_moment() -> np.ndarray:
        """Phi = E_d phi phi^T for d = Uniform([0,1]^2), in closed form.

        Uses E[x^k] = 1/(k+1) for independent U(0,1) coordinates.
        """
        def m(k: int) -> float:  # E[u^k], u ~ U(0,1)
            return 1.0 / (k + 1)

        # feature exponent table: phi_i = x1^{p_i} x2^{q_i}
        exps = [(2, 0), (0, 2), (1, 1), (1, 0), (0, 1), (0, 0)]
        phi = np.empty((N_FEATURES, N_FEATURES))
        for i, (p1, q1) in enumerate(exps):
            for j, (p2, q2) in enumerate(exps):
                phi[i, j] = m(p1 + p2) * m(q1 + q2)
        return phi

    def bellman_target_weights(self, v_weights: np.ndarray) -> np.ndarray:
        """Exact coefficients of  c(x) + gamma E[V_cur(Ax + w)]  (eq. 1 RHS).

        With V_cur(y) = y^T Q y + b^T y + c0:
          E[V_cur(Ax + w)] = x^T A^T Q A x + b^T A x + c0 + sigma2 * tr(Q).
        Adding c(x) = ||x||^2 keeps the target inside the quadratic class.
        """
        Q, b, c0 = _quad_from_weights(np.asarray(v_weights))
        A = self.A
        Qn = self.gamma * A.T @ Q @ A + np.eye(2)       # + I from c(x) = ||x||^2
        bn = self.gamma * A.T @ b
        cn = self.gamma * (c0 + self.noise_var * np.trace(Q))
        return _weights_from_quad(Qn, bn, cn)

    def vfa_problem(self, v_weights: np.ndarray, grid: int = 64) -> vfa_lib.VFAProblem:
        """Population problem (3) on a quadrature grid over [0,1]^2.

        The targets are evaluated from the *exact* Bellman-target polynomial,
        so the only approximation is the quadrature of E_d (midpoint rule on
        ``grid``^2 cells), which is exact enough for degree-<=4 integrands at
        grid >= 64 for every diagnostic we run.
        """
        t = (np.arange(grid) + 0.5) / grid
        xx, yy = np.meshgrid(t, t, indexing="ij")
        pts = np.stack([xx.ravel(), yy.ravel()], axis=-1)          # (G^2, 2)
        phi_m = np.asarray(poly_features(jnp.asarray(pts)))        # (G^2, 6)
        tw = self.bellman_target_weights(v_weights)
        targets = phi_m @ tw
        return vfa_lib.VFAProblem(
            phi_matrix=jnp.asarray(phi_m),
            d_weights=jnp.full((pts.shape[0],), 1.0 / pts.shape[0]),
            targets=jnp.asarray(targets),
            gamma=self.gamma,
        )

    # -- sampling (jax-pure) ---------------------------------------------------

    def sampler_fn(self, num_samples: int) -> Callable[[dict, Array], tuple[Array, Array]]:
        """Parameterized form of ``make_sampler`` for the sweep engine.

        Per-agent params: ``v`` (6,) V_current weights and ``noise_scale``
        (scalar) multiplying the process-noise std — a >1 scale models a
        noisy edge agent whose samples are less informative (heterogeneity
        the informativeness trigger can exploit).
        """
        A = jnp.asarray(self.A)
        sig = jnp.sqrt(self.noise_var)

        def fn(params, rng):
            # each stage under a named scope, which names its ops in the
            # compiled program and the device trace
            r_x, r_w = jax.random.split(rng)
            with jax.named_scope("state_draw"):
                x = jax.random.uniform(r_x, (num_samples, 2))
            with jax.named_scope("next_state"):
                noise = (sig * params["noise_scale"]
                         * jax.random.normal(r_w, (num_samples, 2)))
                x_next = x @ A.T + noise
            with jax.named_scope("target"):
                cost = jnp.sum(x**2, axis=-1)
                targets = (cost
                           + self.gamma * poly_features(x_next) @ params["v"])
            with jax.named_scope("features"):
                return poly_features(x), targets

        return fn

    def agent_param_row(self, v_weights: Array, noise_scale: float = 1.0) -> dict:
        return {"v": jnp.asarray(v_weights, jnp.float32),
                "noise_scale": jnp.float32(noise_scale)}

    def agent_params(self, v_weights: Array, num_agents: int,
                     noise_scale: float = 1.0) -> dict:
        row = self.agent_param_row(v_weights, noise_scale)
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x, (num_agents,) + x.shape), row)

    def make_sampler(self, v_weights: Array, num_samples: int) -> Callable[[Array], tuple[Array, Array]]:
        """sampler(rng) -> (phi_t (T,6), targets_t (T,)).

        x ~ Uniform([0,1]^2), x_+ = A x + w with w ~ N(0, sigma2 I); sampled
        target is c(x) + gamma * V_cur(x_+) with V_cur(y) = v_weights . phi(y).
        """
        A = jnp.asarray(self.A)
        sig = jnp.sqrt(self.noise_var)

        def sampler(rng: Array) -> tuple[Array, Array]:
            r_x, r_w = jax.random.split(rng)
            x = jax.random.uniform(r_x, (num_samples, 2))
            noise = sig * jax.random.normal(r_w, (num_samples, 2))
            x_next = x @ A.T + noise
            cost = jnp.sum(x**2, axis=-1)
            targets = cost + self.gamma * poly_features(x_next) @ v_weights
            return poly_features(x), targets

        return sampler
