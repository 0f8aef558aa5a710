"""Plain reference of a GARNET instance with tabular features.

The instance follows the GARNET recipe (Archibald, McKinnon & Thomas 1995)
with the instance seeding that defines the configuration's instance id:
for every (s, a), ``branching`` distinct successors drawn without
replacement and stick-breaking weights, from
``numpy.random.default_rng((instance, S, A, b, 0))``; costs c(s) ~ U(0, 1)
from stream 1.  Features are phi(s) = e_s, visits are uniform (d = 1/S),
the policy is uniform, and V_current = c (``v_current: "cost"``), so a
sample at state x draws an action a uniformly, a successor x' among the
``branching`` successors of (x, a) by their weights, and has the Bellman
target c(x) + gamma c(x').  The population target is
c + gamma P_pi c, P_pi the mean of P over actions.

Features travel as state indices: phi_t . w is a gather and
sum_t phi_t r_t a scatter-add, which is the same arithmetic as the dense
one-hot form without its (T, S) intermediate.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


class Garnet:
    def __init__(self, cfg: dict):
        S, A, b = cfg["num_states"], cfg["num_actions"], cfg["branching"]
        if any(cfg["w0"]):
            raise ValueError("the reference starts from w0 = 0")
        if cfg["v_current"] != "cost":
            raise ValueError(f"unknown v_current {cfg['v_current']!r}")
        rng = np.random.default_rng((cfg["instance"], S, A, b, 0))
        succ = np.zeros((S, A, b), np.int32)
        prob = np.zeros((S, A, b))
        for s in range(S):
            for a in range(A):
                succ[s, a] = rng.choice(S, size=b, replace=False)
                cuts = np.sort(np.concatenate([[0.0], rng.random(b - 1),
                                               [1.0]]))
                prob[s, a] = np.diff(cuts)
        P = np.zeros((S, A, S))
        for a in range(A):
            np.add.at(P[:, a], (np.arange(S)[:, None], succ[:, a]),
                      prob[:, a])
        self.transitions = P
        self.cost = np.random.default_rng((cfg["instance"], S, A, b, 1)).random(S)
        self.gamma = float(cfg["gamma"])
        self.n = S
        d = np.full(S, 1.0 / S)
        target = self.cost + self.gamma * P.mean(axis=1) @ self.cost
        self.phi_matrix = np.diag(d)
        self.bvec = d * target
        self.c0 = float(np.sum(d * target**2))
        self.target = target
        self.d = d
        self._cost32 = jnp.asarray(self.cost, jnp.float32)
        self._succ = jnp.asarray(succ)
        # cumulative weights of all but the last successor, for inverse-CDF
        self._cum = jnp.asarray(np.cumsum(prob, -1)[..., :-1], jnp.float32)

    def objective64(self, w) -> float:
        """J(w) = E_d (w.phi(x) - target(x))^2 in float64."""
        w = np.asarray(w, np.float64)
        return float(np.sum(self.d * (w - self.target) ** 2))

    def sample(self, key, m: int, T: int):
        k_x, k_a, k_u = jax.random.split(key, 3)
        x = jax.random.randint(k_x, (m, T), 0, self.n)
        a = jax.random.randint(k_a, (m, T), 0, self._succ.shape[1])
        u = jax.random.uniform(k_u, (m, T, 1))
        j = jnp.sum(u >= self._cum[x, a], -1)
        x_next = jnp.take_along_axis(self._succ[x, a], j[..., None], -1)[..., 0]
        return x, self._cost32[x] + self.gamma * self._cost32[x_next]

    @staticmethod
    def dot(x, w, ein):
        """phi_t . w for every sample: (m, T)."""
        return w[x]

    @staticmethod
    def dot_rows(x, g, ein):
        """phi_t . g_i with agent i's own g: (m, T)."""
        return jnp.take_along_axis(g, x, axis=1)

    def tdot(self, x, r, ein):
        """sum_t phi_t r_t per agent: (m, n)."""
        return jax.vmap(lambda xi, ri: jnp.zeros(self.n).at[xi].add(ri))(x, r)


def make(cfg: dict) -> Garnet:
    return Garnet(cfg)
