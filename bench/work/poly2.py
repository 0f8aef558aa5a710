"""Necessary work of one inner step of Algorithm 1 with the degree-2
polynomial features of the paper's §V example (n = 6 for x in R^2).

Counted from the algorithm, not from any implementation.  Per step, for
each run of the grid:

* each of the m T samples: 12 bytes (two f32 coordinates, f32 target);
  3 operations for the features x1^2, x2^2, x1 x2, 2n for phi.w - y, 2n to
  accumulate phi r into g, and for the practical gain 2n for phi.g plus 2
  to square and add it;
* each agent: its gradient once (4 n bytes) and 4 scalars of gain terms
  (16 bytes); n operations to scale g, 2n for |g|^2 (practical) or
  2n for g.gradJ plus 2n^2 + 2n for g' Phi g (theoretical), one compare,
  and 2n to add alpha_i g_i into the aggregate;
* the run: w read and written, Phi and b read (4 n^2 + 12 n bytes);
  2n^2 + 2n for gradJ = 2 (Phi w - b) on the theoretical runs and 3n for
  the update.
"""

from __future__ import annotations


def per_step(modes, m: int, T: int, n: int) -> dict:
    """{"flops", "bytes"} of one inner step over runs whose modes are given."""
    flops = bytes_ = 0
    for mode in modes:
        theo = mode == "theoretical"
        sample_ops = 3 + 4 * n + (0 if theo else 2 * n + 2)
        agent_ops = (n + (2 * n * n + 4 * n if theo else 2 * n) + 1
                     + 2 * n)
        run_ops = (2 * n * n + 2 * n if theo else 0) + 3 * n
        flops += m * T * sample_ops + m * agent_ops + run_ops
        bytes_ += m * T * 12 + m * (4 * n + 16) + 4 * n * n + 12 * n
    return {"flops": flops, "bytes": bytes_}
