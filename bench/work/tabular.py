"""Necessary work of one inner step of Algorithm 1 with tabular features.

Counted from the algorithm, not from any implementation: a sample is its
state index and its target, phi(x) = e_x is never formed, and nothing the
program keeps in between (random noise, one-hot rows) counts.  Per step,
for each run of the grid:

* each of the m T samples: 8 bytes (int32 index, f32 target); 4 operations
  (residual w[x] - y, accumulate it into g[x], and for the practical gain
  square and add g[x]);
* each agent: its gradient once (4 n bytes) and 4 scalars of gain terms
  (16 bytes); n operations to scale g by 2/T, 2n for |g|^2 (practical) or
  2n for g.gradJ plus 3n for g' Phi g with Phi = diag(d) (theoretical), one
  compare, and 2n to add alpha_i g_i into the aggregate;
* the run: w read and written, diag(Phi) and b read (16 n bytes); 3n for
  gradJ = 2 (Phi w - b) on the theoretical runs and 3n for the update.
"""

from __future__ import annotations


def per_step(modes, m: int, T: int, n: int) -> dict:
    """{"flops", "bytes"} of one inner step over runs whose modes are given."""
    flops = bytes_ = 0
    for mode in modes:
        theo = mode == "theoretical"
        sample_ops = 2 if theo else 4
        agent_ops = n + (5 * n if theo else 2 * n) + 1 + 2 * n
        run_ops = (3 * n if theo else 0) + 3 * n
        flops += m * T * sample_ops + m * agent_ops + run_ops
        bytes_ += m * T * 8 + m * (4 * n + 16) + 16 * n
    return {"flops": flops, "bytes": bytes_}
