"""Ahead-of-time compiles of the sweep's Pallas kernels for a described
TPU v5e, at the real widths of the chip smoke run (m = 1024 agents, T = 256
samples, n = 128 features, R = 16 runs).

Interpret mode hides what the chip's compiler refuses (block shapes that
break the (8, 128) tiling rule, VMEM overruns), so every case compiles
with ``interpret=False`` and asserts the kernel (``tpu_custom_call``) is in
the program.  Nothing runs: no chip is needed, only the TPU compiler.  The
topology is described inside a fixture — never at import — and the tests
skip where it cannot be described.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import gain_dispatch
from repro.kernels import gain as kg
from repro.kernels import ops

M, T, N, R = 1024, 256, 128, 16
EPS = 0.5


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip can be written to the persistent
    # cache but never read back without one
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("with_model", [True, False])
def test_family_stats_compiles_for_v5e(one_chip, with_model):
    if with_model:
        c = _compile(lambda p, g, j, pm: kg.gain_family_stats(
            p, g, j, pm, interpret=False), one_chip,
            (M, T, N), (M, N), (N,), (N, N))
    else:
        c = _compile(lambda p, g: kg.gain_family_stats(
            p, g, interpret=False), one_chip, (M, T, N), (M, N))
    _assert_kernel(c)


@pytest.mark.parametrize("with_deliver", [True, False])
@pytest.mark.parametrize("with_model", [True, False])
def test_megastep_call_compiles_for_v5e(one_chip, with_model, with_deliver):
    shapes = [(R, M, T, N), (R, M, N), (R, N), (R, 2), (R, M)]
    if with_deliver:
        shapes.append((R, M))
    if with_model:
        shapes += [(R, N), (N, N)]

    def step(phi, g, w, ctl, arand, *rest):
        rest = list(rest)
        deliver = rest.pop(0) if with_deliver else None
        grad_j, pm = rest if with_model else (None, None)
        return kg.megastep_call(phi, g, w, ctl, arand, grad_j, pm, deliver,
                                eps=EPS, interpret=False)

    _assert_kernel(_compile(step, one_chip, *shapes))


def test_vmapped_megastep_compiles_for_v5e(one_chip):
    """The sweep's own route: vmap over runs through the custom_vmap rule,
    with the grid-shared Phi left unbatched."""
    def sweep_step(phi, g, w, ctl, arand, grad_j, pm):
        return jax.vmap(lambda p, gg, ww, c, a, j: kg.megastep(
            p, gg, ww, c, a, j, pm, eps=EPS, interpret=False))(
                phi, g, w, ctl, arand, grad_j)

    _assert_kernel(_compile(sweep_step, one_chip, (R, M, T, N), (R, M, N),
                            (R, N), (R, 2), (R, M), (R, N), (N, N)))


def test_pallas_practical_gain_compiles_for_v5e(one_chip, monkeypatch):
    """The reference step structure on the Pallas backend: the eq.-15 gain
    per agent, vmapped over the fleet — compiled as on a TPU host, where
    ``kernels.ops`` never interprets."""
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)

    def gains(g, phi):
        return jax.vmap(lambda gi, pi: gain_dispatch.practical_gain(
            gi, pi, EPS, backend="pallas"))(g, phi)

    _assert_kernel(_compile(gains, one_chip, (M, N), (M, T, N)))
