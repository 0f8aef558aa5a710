"""The timed path broken underneath a whole tiny run: ``correct`` is false.

Each test plants one fault in the system under test and drives the rest of
a run as ``bench/run.py`` does (the look for a chip skipped)."""

import os

import jax
import jax.numpy as jnp
import pytest

from benchtiny import tiny_checkout

import run


def _run(tmp_path, cell="garnet_clean"):
    jax.clear_caches()
    root = tiny_checkout(tmp_path)
    code, res = run.run_cell(root, cell, 2**31 + 7, 0.2, False,
                             require_chip=False,
                             bench=os.path.join(root, "bench"))
    assert code == 0
    return res


def test_sound_program_is_correct(tmp_path):
    assert _run(tmp_path)["correct"]


@pytest.mark.parametrize("cell", ["garnet_clean", "linsys_clean",
                                  "garnet_lossy"])
def test_step_that_returns_its_state_unchanged(tmp_path, monkeypatch, cell):
    import repro.core.vfa as vfa
    monkeypatch.setattr(vfa, "stochastic_gradient",
                        lambda w, phi, y: jnp.zeros_like(w))
    res = _run(tmp_path, cell)
    assert not res["correct"]
    assert res["failed"] > 0


def test_half_the_batch_left_out(tmp_path, monkeypatch):
    import repro.core.vfa as vfa
    full = vfa.stochastic_gradient

    def half(w, phi, y):
        h = phi.shape[0] // 2
        return full(w, phi[:h], y[:h])

    monkeypatch.setattr(vfa, "stochastic_gradient", half)
    res = _run(tmp_path)
    assert not res["correct"]


def _stay_garnet(monkeypatch):
    """GARNET's sampler with the next state x' replaced by x."""
    import repro.envs.base as base

    def family_sampler_fn(num_samples):
        def fn(env_params, params, rng):
            c, S = env_params["c"], env_params["c"].shape[0]
            r_x, _, _, r_t = jax.random.split(rng, 4)
            x = jax.random.categorical(r_x, params["visit_logits"],
                                       shape=(num_samples,))
            targets = (c[x] + env_params["gamma"] * params["v"][x]
                       + params["noise_scale"]
                       * jax.random.normal(r_t, (num_samples,)))
            return jax.nn.one_hot(x, S), targets
        return fn

    monkeypatch.setattr(base, "family_sampler_fn", family_sampler_fn)


def _stay_linsys(monkeypatch):
    """The §V sampler with the successor A x + w replaced by x."""
    from repro.envs import linear_system as ls

    def sampler_fn(self, num_samples):
        def fn(params, rng):
            x = jax.random.uniform(jax.random.split(rng)[0],
                                   (num_samples, 2))
            targets = (jnp.sum(x**2, -1)
                       + self.gamma * ls.poly_features(x) @ params["v"])
            return ls.poly_features(x), targets
        return fn

    monkeypatch.setattr(ls.LinearSystem, "sampler_fn", sampler_fn)


@pytest.mark.parametrize("cell,plant", [("garnet_clean", _stay_garnet),
                                        ("linsys_clean", _stay_linsys)])
def test_next_state_left_where_it_was(tmp_path, monkeypatch, cell, plant):
    plant(monkeypatch)
    res = _run(tmp_path, cell)
    assert not res["correct"]


def test_answer_altered_where_it_is_produced(tmp_path, monkeypatch):
    import repro.experiments as experiments
    real = experiments.run_sweep

    def altered(*args, **kwargs):
        out = real(*args, **kwargs)
        return out._replace(comm_rate=jnp.flip(out.comm_rate))

    monkeypatch.setattr(experiments, "run_sweep", altered)
    res = _run(tmp_path)
    assert not res["correct"]
