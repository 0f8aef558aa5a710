"""Environment correctness: transition kernels, exact values, closed forms."""

import math
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from scipy import stats

from repro.envs import GarnetMDP, GridWorld, LinearSystem
from repro.envs.base import (cdf_table, family_sampler_fn,
                             inverse_cdf_draw, stack_agent_params,
                             stack_env_family, stack_env_fleets,
                             table_select)
from repro.envs.linear_system import poly_features


def test_gridworld_transition_is_stochastic_matrix():
    gw = GridWorld()
    P = gw.transition_matrix()
    np.testing.assert_allclose(P.sum(-1), 1.0)
    assert np.all(P >= 0)
    goal = gw._idx(*gw.goal)
    np.testing.assert_allclose(P[goal, :, goal], 1.0)   # absorbing


def test_gridworld_wind_only_on_top_row():
    gw = GridWorld(wind_prob=0.5)
    P = gw.transition_matrix()
    # a bottom-row interior state moving left is deterministic
    s = gw._idx(3, 2)
    assert np.isclose(P[s, 2].max(), 1.0)
    # a top-row state has split probability
    s = gw._idx(0, 1)
    assert 0.4 < P[s, 2].max() < 0.6 or np.isclose(P[s, 2].max(), 1.0)
    split = [P[gw._idx(0, c), a].max() for c in range(gw.width - 1) for a in range(4)]
    assert any(0.4 < x < 0.6 for x in split)


def test_gridworld_exact_value_is_bellman_fixed_point():
    gw = GridWorld()
    v = gw.exact_value()
    np.testing.assert_allclose(gw.bellman_update(v), v, atol=1e-9)
    assert v[gw._idx(*gw.goal)] == 0.0
    assert np.all(v[np.arange(25) != gw._idx(*gw.goal)] > 0)


def test_gridworld_sampler_statistics(key):
    """Sampled targets agree in expectation with the exact Bellman update."""
    gw = GridWorld()
    v_cur = np.linspace(0, 1, gw.num_states)
    sampler = gw.make_sampler(jnp.asarray(v_cur), 50_000)
    phi_t, targets = sampler(key)
    states = np.argmax(np.asarray(phi_t), axis=1)
    exact = gw.bellman_update(v_cur)
    for s in range(0, gw.num_states, 7):
        sel = states == s
        if sel.sum() > 500:
            np.testing.assert_allclose(np.asarray(targets)[sel].mean(),
                                       exact[s], atol=5e-2)


def test_linear_system_phi_closed_form_matches_quadrature():
    ls = LinearSystem()
    phi_exact = ls.second_moment()
    prob = ls.vfa_problem(np.zeros(6), grid=128)
    np.testing.assert_allclose(np.asarray(prob.second_moment()), phi_exact,
                               atol=2e-5)
    assert np.linalg.eigvalsh(phi_exact).min() > 0   # Assumption 1


def test_linear_system_bellman_weights_match_monte_carlo(key):
    """Closed-form target polynomial == MC estimate of c(x) + g E V(Ax+w)."""
    ls = LinearSystem()
    vw = np.array([0.5, -0.2, 0.3, 0.1, -0.4, 0.7])
    tw = ls.bellman_target_weights(vw)
    x = np.array([[0.3, 0.8], [0.1, 0.2], [0.9, 0.5]])
    keys = jax.random.split(key, 200_000)
    noise = np.asarray(jax.random.normal(key, (200_000, 2))) * np.sqrt(ls.noise_var)
    for xi in x:
        xn = xi @ ls.A.T + noise
        v_next = np.asarray(poly_features(jnp.asarray(xn))) @ vw
        mc = (xi @ xi) + ls.gamma * v_next.mean()
        exact = np.asarray(poly_features(jnp.asarray(xi))) @ tw
        np.testing.assert_allclose(exact, mc, rtol=2e-2)


def test_linear_system_sampler_features(key):
    ls = LinearSystem()
    sampler = ls.make_sampler(jnp.zeros(6), 1000)
    phi_t, targets = sampler(key)
    assert phi_t.shape == (1000, 6)
    np.testing.assert_allclose(np.asarray(phi_t)[:, 5], 1.0)  # bias feature
    assert np.all(np.asarray(targets) >= 0)  # c(x) >= 0 and V_cur = 0


# The tabular sampler's target reads c[x] and v[x'] by an exact select
# (``table_select``); these tests hold it bit for bit to the gather form.

T_SAMPLES = 8


def _gather_sampler(num_samples):
    """``family_sampler_fn`` with both table lookups of the target written
    as gathers; also returns the next states.  The states are drawn as the
    program draws them, by inverse CDF on the same keys."""
    def fn(env_params, params, rng):
        P, c = env_params["P"], env_params["c"]
        r_x, r_a, r_n, r_t = jax.random.split(rng, 4)
        visit = cdf_table(jax.nn.softmax(params["visit_logits"]))
        x = inverse_cdf_draw(r_x, visit, (num_samples,))
        a = jax.random.randint(r_a, (num_samples,), 0, P.shape[1])
        x_next = inverse_cdf_draw(r_n, cdf_table(P)[x, a])
        targets = (c[x] + env_params["gamma"] * params["v"][x_next]
                   + params["noise_scale"]
                   * jax.random.normal(r_t, (num_samples,)))
        return jax.nn.one_hot(x, P.shape[0]), targets, x_next
    return fn


def _fleet(env, v):
    """Three agents: uniform visits, skewed visits, and target noise."""
    S = env.num_states
    skew = np.zeros(S, np.float32)
    skew[S // 2] = 3.0
    return stack_agent_params(
        env.agent_param_row(v),
        env.agent_param_row(v, visit_logits=jnp.asarray(skew)),
        env.agent_param_row(v, noise_scale=0.5))


def _sampler_case(name):
    """``(program, gather, args)``: the program's sampler as its caller runs
    it and the gather form, both vmapped over agents (and envs), with a
    ``v`` of negative entries."""
    if name == "family3":
        envs = [GarnetMDP(seed=s) for s in range(3)]
        v = np.linspace(-1.0, 1.0, envs[0].num_states).astype(np.float32)
        fam = stack_env_family(envs, v, with_terms=False)
        fleets = stack_env_fleets([_fleet(e, v) for e in envs])
        keys = jax.random.split(jax.random.key(3), (3, 3))
        program = jax.vmap(jax.vmap(family_sampler_fn(T_SAMPLES),
                                    (None, 0, 0)))
        gather = jax.vmap(jax.vmap(_gather_sampler(T_SAMPLES), (None, 0, 0)))
        return program, gather, (fam.params, fleets, keys)
    env = {"garnet20": GarnetMDP(num_states=20),
           "garnet128": GarnetMDP(num_states=128),
           "gridworld": GridWorld()}[name]
    v = np.linspace(-1.0, 1.0, env.num_states).astype(np.float32)
    keys = jax.random.split(jax.random.key(1), 3)
    program = jax.vmap(env.sampler_fn(T_SAMPLES))
    gather = jax.vmap(partial(_gather_sampler(T_SAMPLES), env.env_params()))
    return program, gather, (_fleet(env, v), keys)


SAMPLER_CASES = ["garnet20", "garnet128", "gridworld", "family3"]


def _with_nan_in_unselected_lane(args, x_next):
    """``args`` with NaN in the lane of each agent's ``v`` that is the first
    state none of that agent's samples reads as its next state."""
    *rest, fleet, keys = args
    v = np.array(fleet["v"])
    reads = np.asarray(x_next)
    for row, read in zip(v.reshape(-1, v.shape[-1]),
                         reads.reshape(-1, reads.shape[-1])):
        row[np.setdiff1d(np.arange(row.size), read)[0]] = np.nan
    return (*rest, dict(fleet, v=jnp.asarray(v)), keys)


@pytest.mark.parametrize("case", SAMPLER_CASES)
def test_tabular_sampler_targets_bitwise_equal_gather_form(case):
    program, gather, args = _sampler_case(case)
    args = _with_nan_in_unselected_lane(args, gather(*args)[2])
    phi, targets = jax.jit(program)(*args)
    phi_ref, targets_ref, _ = jax.jit(gather)(*args)
    targets, targets_ref = np.asarray(targets), np.asarray(targets_ref)
    assert not np.isnan(targets).any()
    assert np.array_equal(targets, targets_ref)
    assert np.array_equal(targets.view(np.uint32),
                          targets_ref.view(np.uint32))
    assert np.array_equal(np.asarray(phi), np.asarray(phi_ref))


@pytest.mark.parametrize("case", SAMPLER_CASES)
def test_tabular_sampler_gathers_only_the_transition_row(case):
    """Of the sampler's table reads only ``P[x, a]`` stays a gather: the
    target's ``c[x]`` and ``v[x']`` lower to selects."""
    program, gather, args = _sampler_case(case)

    def count(fn):
        text = jax.jit(fn).lower(*args).as_text()
        return sum('"stablehlo.gather"(' in line
                   for line in text.splitlines())

    assert count(gather) == 3
    assert count(program) == 1


def test_table_select_is_the_entry_bit_for_bit():
    """Every lane chosen once, over signed zeros, infinities, NaN, a
    subnormal and negatives."""
    table = jnp.asarray([-0.0, 0.0, np.inf, -np.inf, np.nan, 1e-45, -2.5,
                         3.0], jnp.float32)
    idx = jnp.asarray([7, 6, 5, 4, 3, 2, 1, 0, 0, 4], jnp.int32)
    got = np.asarray(jax.jit(table_select)(table, idx))
    want = np.asarray(table)[np.asarray(idx)]
    assert np.array_equal(got.view(np.uint32), want.view(np.uint32))


def _random_bit_sizes(text):
    """Element counts of every threefry block in lowered program text."""
    sizes = []
    for line in text.splitlines():
        if "call @threefry2x32" in line:
            for dims in re.findall(r"tensor<([0-9x]+)xui32>",
                                   line.split("->")[-1]):
                sizes.append(math.prod(int(d) for d in dims.split("x")))
    return sizes


@pytest.mark.parametrize("case", ["garnet128", "family3"])
def test_tabular_sampler_random_bits_grow_with_samples_not_states(case):
    """The sampler draws one uniform per sample and draw: no block of
    random bits has T * S entries, as a Gumbel-max categorical's has."""
    program, _, args = _sampler_case(case)
    keys = args[-1]
    S = args[-2]["v"].shape[-1]
    sizes = _random_bit_sizes(jax.jit(program).lower(*args).as_text())
    assert sizes and max(sizes) == keys.size * T_SAMPLES
    gumbel = jax.jit(lambda k: jax.random.categorical(
        k, jnp.zeros(S), shape=(T_SAMPLES,)))
    assert max(_random_bit_sizes(
        gumbel.lower(jax.random.key(0)).as_text())) == T_SAMPLES * S


N_DRAWS = 1 << 20


def _draw(probs, seed=0):
    """``N_DRAWS`` states from one row of probabilities, as counts."""
    cdf = cdf_table(jnp.asarray(probs, jnp.float32))
    x = jax.jit(partial(inverse_cdf_draw, shape=(N_DRAWS,)))(
        jax.random.key(seed), cdf)
    assert x.dtype == jnp.int32
    return np.bincount(np.asarray(x), minlength=len(probs))


_GEOMETRIC = [2.0 ** -k for k in range(1, 11)]   # total 1 - 2^-10, exact

ZERO_ROWS = {
    "zeros_at_start": [0.0, 0.0, 0.5, 0.25, 0.25],
    "zeros_in_middle": [0.3, 0.0, 0.0, 0.4, 0.0, 0.3],
    "zeros_at_end": [0.25, 0.75, 0.0, 0.0, 0.0],
    "total_below_one": _GEOMETRIC + [0.0, 0.0],
    "rounded_below_one": [np.float32(1 / 12)] * 12,
    "neg_inf_visit_logits": np.asarray(jax.nn.softmax(jnp.asarray(
        [-np.inf, 0.0, -np.inf, 1.0, 0.5, -np.inf, -np.inf, -np.inf]))),
}


@pytest.mark.parametrize("case", sorted(ZERO_ROWS))
def test_inverse_cdf_draw_never_returns_a_zero_probability_state(case):
    probs = np.asarray(ZERO_ROWS[case], np.float32)
    counts = _draw(probs)
    assert counts.size == probs.size          # no state past the row
    np.testing.assert_array_equal(counts > 0, probs > 0)


def test_inverse_cdf_draw_gives_the_tail_below_one_to_the_last_state():
    """A row summing to 1 - 2^-10: the last positive state takes its own
    2^-10 and the missing 2^-10, not a trailing zero state."""
    counts = _draw(np.asarray(_GEOMETRIC + [0.0, 0.0], np.float32))
    expect = N_DRAWS * 2.0 ** -9
    assert abs(counts[9] - expect) < 5 * math.sqrt(expect)
    assert counts[10:].sum() == 0


def _chi_square_rows():
    garnet = GarnetMDP(num_states=128)
    P = np.asarray(garnet.env_params()["P"])
    gw = GridWorld()
    P_gw = np.asarray(gw.env_params()["P"])
    skew = _fleet(garnet, np.zeros(128, np.float32))["visit_logits"][1]
    return {
        "garnet128_row": P[0, 0],
        "garnet128_row_last": P[127, 3],
        "gridworld_windy_row": P_gw[gw._idx(0, 1), 2],
        "skewed_visits": np.asarray(jax.nn.softmax(skew)),
    }


@pytest.mark.parametrize("case", ["garnet128_row", "garnet128_row_last",
                                  "gridworld_windy_row", "skewed_visits"])
def test_inverse_cdf_draw_matches_the_distribution(case):
    probs = np.asarray(_chi_square_rows()[case], np.float32)
    support = probs > 0
    assert support.sum() > 1
    counts = _draw(probs, seed=17)
    assert counts[~support].sum() == 0
    p = probs[support].astype(np.float64)
    _, p_value = stats.chisquare(counts[support], N_DRAWS * p / p.sum())
    assert p_value > 1e-3


def test_inverse_cdf_draw_is_int32_of_the_batch_shape_under_vmap():
    """A row shared by T samples and the same row gathered per sample draw
    the same int32 states on the same keys."""
    agents, S = 4, 16
    probs = jax.random.dirichlet(jax.random.key(5), jnp.ones(S), (agents,))
    cdf = cdf_table(probs)
    keys = jax.random.split(jax.random.key(6), agents)
    shared = jax.vmap(partial(inverse_cdf_draw, shape=(T_SAMPLES,)))(
        keys, cdf)
    rows = jnp.broadcast_to(cdf[:, None], (agents, T_SAMPLES, S))
    gathered = jax.vmap(inverse_cdf_draw)(keys, rows)
    for x in (shared, gathered):
        assert x.dtype == jnp.int32
        assert x.shape == (agents, T_SAMPLES)
    assert np.array_equal(np.asarray(shared), np.asarray(gathered))
    assert 0 <= int(shared.min()) and int(shared.max()) < S
