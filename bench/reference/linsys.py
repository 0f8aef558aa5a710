"""Plain reference of the paper's continuous example (arXiv:2112.05908 §V).

x ~ U([0, 1]^2), phi(x) = [x1^2, x2^2, x1 x2, x1, x2, 1], cost |x|^2,
successor x_+ = A x + w with w ~ N(0, noise_var I), and V_current = |x|^2
(``v_current: "cost"``), so a sample's Bellman target is
|x|^2 + gamma |x_+|^2, and its mean given x is
|x|^2 + gamma (|A x|^2 + 2 noise_var).  The population problem of the
objective J and of the theoretical trigger is the one the configuration
states: d is the midpoint rule on a ``population_grid`` x
``population_grid`` grid of the square, so Phi = E_d phi phi',
b = E_d phi y and c0 = E_d y^2 of that mean y are sums over its points, in
float64.  The mean target lies in the span of phi, so J(w*) = 0 under any
d.

Features are held feature-major, (n, m, T), so no axis of length 6 lands
in the chip's 128-wide minor dimension.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def features64(x1, x2) -> np.ndarray:
    return np.stack([x1 * x1, x2 * x2, x1 * x2, x1, x2, np.ones_like(x1)], -1)


class LinSys:
    def __init__(self, cfg: dict):
        if any(cfg["w0"]):
            raise ValueError("the reference starts from w0 = 0")
        if cfg["v_current"] != "cost":
            raise ValueError(f"unknown v_current {cfg['v_current']!r}")
        self.a = np.asarray(cfg["a_matrix"], np.float64)
        self.noise_var = float(cfg["noise_var"])
        self.gamma = float(cfg["gamma"])
        grid = cfg["population_grid"]
        t = (np.arange(grid) + 0.5) / grid
        x1, x2 = (a.ravel() for a in np.meshgrid(t, t, indexing="ij"))
        phi = features64(x1, x2)                      # (grid^2, 6)
        ax = self.a @ np.stack([x1, x2])
        target = (x1 * x1 + x2 * x2
                  + self.gamma * (np.sum(ax * ax, 0) + 2 * self.noise_var))
        self.n = phi.shape[1]
        self.phi_matrix = phi.T @ phi / len(t) ** 2
        self.bvec = phi.T @ target / len(t) ** 2
        self.c0 = float(np.mean(target**2))
        self.wstar = np.linalg.solve(self.phi_matrix, self.bvec)
        self.jstar = max(self.c0 - self.bvec @ self.wstar, 0.0)

    def objective64(self, w) -> float:
        """J(w) = (w - w*)' Phi (w - w*) + J(w*) in float64."""
        e = np.asarray(w, np.float64) - self.wstar
        return float(e @ self.phi_matrix @ e + self.jstar)

    def sample(self, key, m: int, T: int):
        k_x, k_w = jax.random.split(key)
        x = jax.random.uniform(k_x, (2, m, T))
        x1, x2 = x[0], x[1]
        w = np.sqrt(self.noise_var) * jax.random.normal(k_w, (2, m, T))
        a = self.a.astype(np.float32)
        y1 = a[0, 0] * x1 + a[0, 1] * x2 + w[0]
        y2 = a[1, 0] * x1 + a[1, 1] * x2 + w[1]
        feat = jnp.stack([x1 * x1, x2 * x2, x1 * x2, x1, x2,
                          jnp.ones_like(x1)])
        return feat, x1 * x1 + x2 * x2 + self.gamma * (y1 * y1 + y2 * y2)

    @staticmethod
    def dot(feat, w, ein):
        return ein("nmt,n->mt", feat, w)

    @staticmethod
    def dot_rows(feat, g, ein):
        return ein("nmt,mn->mt", feat, g)

    @staticmethod
    def tdot(feat, r, ein):
        return ein("nmt,mt->mn", feat, r)


def make(cfg: dict) -> LinSys:
    return LinSys(cfg)
