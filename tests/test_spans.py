"""Host spans of the sweep engine (``repro.spans``) and the named scopes of
the inner step: one ``sweep.run`` per ``run_sweep`` call with its three
children, runtime spans under the caller's call id (the checkpoint writer
thread included), no span inside traced code, a bounded buffer, and the
stage names in the lowered grid program's op metadata."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import spans
from repro.core.channel import ChannelSpec
from repro.envs import GarnetMDP, LinearSystem, as_param_sampler
from repro.experiments import SweepSpec, run_sweep
from repro.experiments.runtime import run_sweep_resumable
from repro.experiments.sweep import exec_plan, plan_sweep

GARNET = GarnetMDP(num_states=8, num_actions=2, branching=2, seed=0)
V = np.asarray(GARNET.cost_vector(), np.float32)
PROB = GARNET.vfa_problem(V)
W0 = jnp.zeros(8)


def _spec(**kw):
    base = dict(modes=("theoretical", "practical"), lambdas=(0.1, 1.0),
                seeds=(0,), rhos=(0.99,), eps=0.5, num_iterations=4,
                num_agents=4, trace="summary")
    base.update(kw)
    return SweepSpec(**base)


def _sampler(num_agents=4):
    return as_param_sampler(GARNET, V, num_agents=num_agents, num_samples=8)


def _last_call():
    """The newest top-level span and every span of its call."""
    top = spans.recorded()[-1]
    assert top.parent is None
    return top, [s for s in spans.recorded() if s.call == top.call]


def test_run_sweep_records_one_run_span_with_three_children():
    jax.block_until_ready(run_sweep(_spec(), _sampler(), W0, problem=PROB))
    top, call = _last_call()
    assert top.name == "sweep.run"
    assert [s.name for s in call] == ["sweep.plan", "sweep.dispatch",
                                      "sweep.finalize", "sweep.run"]
    assert all(s.parent == "sweep.run" for s in call[:-1])
    assert top.attrs == {"runs": 4, "padded_runs": 4, "devices": 1,
                         "step": "reference"}
    for child in call[:-1]:
        assert top.start <= child.start <= child.end <= top.end
    assert len({s.thread for s in call}) == 1


def test_channel_sets_name_the_step_path():
    spec = _spec(channel_sets=(ChannelSpec(drop_prob=0.3, delay=1),))
    jax.block_until_ready(run_sweep(spec, _sampler(), W0, problem=PROB))
    top, _ = _last_call()
    assert top.attrs["step"] == "reference+channel"


@pytest.mark.parametrize("num_iterations,seeds", [(3, (0,)),
                                                  (9, (0, 1, 2))])
def test_span_count_per_call_is_independent_of_n_and_runs(num_iterations,
                                                          seeds):
    spec = _spec(num_iterations=num_iterations, seeds=seeds)
    jax.block_until_ready(run_sweep(spec, _sampler(), W0, problem=PROB))
    top, call = _last_call()
    assert top.attrs["runs"] == 4 * len(seeds)
    assert len(call) == 4


def test_tracing_the_grid_program_records_no_span():
    plan = plan_sweep(_spec(), _sampler(), W0, PROB)
    before = list(spans.recorded())
    jax.jit(lambda: exec_plan(plan)).lower()
    assert list(spans.recorded()) == before


def test_buffer_stays_bounded():
    for i in range(spans.MAXLEN + 10):
        with spans.span("test.flood", i=i):
            pass
    assert len(spans.recorded()) == spans.MAXLEN
    assert spans.recorded()[0].attrs["i"] == 10
    assert spans.recorded()[-1].attrs["i"] == spans.MAXLEN + 9


def test_nested_spans_share_a_call_and_top_level_spans_do_not():
    with spans.span("test.outer") as attrs:
        with spans.span("test.inner"):
            pass
        attrs["late"] = 1
    with spans.span("test.other"):
        pass
    inner, outer, other = list(spans.recorded())[-3:]
    assert (inner.parent, inner.call) == ("test.outer", outer.call)
    assert outer.attrs == {"late": 1}
    assert other.call != outer.call and other.parent is None


def test_resumable_records_segments_checkpoints_and_one_drain(tmp_path):
    spec = _spec(modes=("practical",), chunk_size=1)   # two segments
    run_sweep_resumable(spec, _sampler(), W0, problem=PROB,
                        store_dir=str(tmp_path))
    top, call = _last_call()
    assert top.name == "runtime.run"
    names = [s.name for s in call]
    assert names.count("runtime.segment") == 2
    assert names.count("runtime.checkpoint") == 2
    assert names.count("runtime.drain") == 1
    segments = [s for s in call if s.name == "runtime.segment"]
    assert [s.attrs for s in segments] == [
        {"index": 0, "restored": False}, {"index": 1, "restored": False}]
    writes = [s for s in call if s.name == "runtime.checkpoint"]
    assert sorted(s.attrs["index"] for s in writes) == [0, 1]
    assert all(s.thread.startswith("sweep-ckpt") for s in writes)
    assert all(s.thread != top.thread for s in writes)

    # a second call restores both segments and writes nothing
    run_sweep_resumable(spec, _sampler(), W0, problem=PROB,
                        store_dir=str(tmp_path))
    top2, call2 = _last_call()
    assert top2.call != top.call
    assert [s.attrs["restored"] for s in call2
            if s.name == "runtime.segment"] == [True, True]
    assert "runtime.checkpoint" not in [s.name for s in call2]


def _scopes(spec, sampler, w0, problem):
    """The lowered grid program's op names, with ``vmap(...)`` transform
    wrappers removed so scope paths read ``step/sample/next_state``."""
    plan = plan_sweep(spec, sampler, w0, problem)
    text = jax.jit(lambda: exec_plan(plan)).lower().as_text(debug_info=True)
    while True:
        unwrapped = re.sub(r"vmap\(([^()]*)\)", r"\1", text)
        if unwrapped == text:
            return text
        text = unwrapped


@pytest.mark.parametrize("scope", [
    "step/keys", "step/sample/state_draw", "step/sample/action_draw",
    "step/sample/next_state", "step/sample/target", "step/sample/features",
    "step/grad", "step/gains", "step/trigger", "step/update",
    "step/summary"])
def test_garnet_grid_program_names_every_stage(scope):
    assert scope + "/" in _scopes(_spec(), _sampler(), W0, PROB)


@pytest.mark.parametrize("scope", [
    "step/sample/state_draw", "step/sample/next_state",
    "step/sample/target", "step/sample/features", "step/grad",
    "step/update"])
def test_linear_system_grid_program_names_the_sampler_stages(scope):
    env = LinearSystem()
    v = np.zeros(6, np.float32)
    sampler = as_param_sampler(env, v, num_agents=4, num_samples=8)
    spec = _spec(modes=("practical",))
    assert scope + "/" in _scopes(spec, sampler, jnp.zeros(6),
                                  env.vfa_problem(v, grid=8))


def test_lossy_grid_program_names_the_channel_stage():
    spec = _spec(channel_sets=(ChannelSpec(drop_prob=0.3, delay=2),))
    text = _scopes(spec, _sampler(), W0, PROB)
    for scope in ("step/channel", "step/update", "step/summary"):
        assert scope + "/" in text


@pytest.mark.parametrize("step_backend,kernel", [
    ("megastep", "megastep"), ("fused", "gain_family_stats")])
def test_kernels_carry_stable_names(step_backend, kernel):
    spec = _spec(step_backend=step_backend, gain_backend="pallas",
                 num_iterations=2)
    text = _scopes(spec, _sampler(), W0, PROB)
    assert f"{kernel}/pallas_call" in text
