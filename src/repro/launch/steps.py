"""Step builders: federated train step, prefill step, decode (serve) step.

The train step realizes the paper's Algorithm-1 inner update at datacenter
scale (DESIGN §4): ``jax.shard_map`` is *manual* over the federation axis
only (``pod`` on the multi-pod mesh, else ``data``) and *auto* everywhere
else, so

  * each federation-axis member computes the gradient of its own batch
    shard (GSPMD still auto-shards model/tensor dims and, multi-pod, the
    intra-pod data dim — that all-reduce is the cheap intra-pod one);
  * the member evaluates the local performance gain (eq. 13/15 analogue)
    and its transmit decision alpha_i (eq. 9);
  * the masked cross-agent psum implements the server rule (eq. 6).

Serving steps are plain pjit (no gradient exchange -> the paper's technique
does not apply; see DESIGN §6).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.core.fed_sgd import FedConfig, FedStats, gate_and_aggregate
from repro.launch.mesh import federation_axis
from repro.optim.optimizers import Optimizer, apply_updates, clip_by_global_norm
from repro.parallel import specs as spec_lib
from repro.parallel.context import activation_sharding

PyTree = Any


def _replicated_like(tree) -> PyTree:
    return jax.tree.map(lambda _: P(), tree)


def opt_state_specs(opt_state_shape, pspecs) -> PyTree:
    """Optimizer State namedtuples: moment trees mirror param sharding."""
    fields = []
    params_struct = jax.tree.structure(pspecs)
    for name in opt_state_shape._fields:
        sub = getattr(opt_state_shape, name)
        if sub is None:
            fields.append(None)
        elif jax.tree.structure(sub) == params_struct:
            fields.append(pspecs)
        else:
            fields.append(jax.tree.map(lambda _: P(), sub))
    return type(opt_state_shape)(*fields)


def fed_state_specs(fed_axis: str) -> FedStats:
    return FedStats(steps=P(), tx=P(), last_alpha=P(fed_axis), last_gain=P(fed_axis))


@dataclasses.dataclass(frozen=True)
class TrainStepBundle:
    step: Any                 # jitted (params, opt_state, fed_state, batch) -> ...
    pspecs: PyTree
    opt_specs: PyTree
    batch_specs: PyTree
    fed_specs: FedStats
    num_agents: int
    params_shape: PyTree = None
    opt_shape: PyTree = None
    fed_shape: PyTree = None


def build_train_step(
    model,
    cfg: ModelConfig,
    mesh,
    optimizer: Optimizer,
    fed_cfg: FedConfig | None = None,
    grad_clip: float = 1.0,
) -> TrainStepBundle:
    fed_axis = federation_axis(mesh)
    num_agents = mesh.shape[fed_axis]
    if fed_cfg is not None and fed_cfg.axis != fed_axis:
        fed_cfg = dataclasses.replace(fed_cfg, axis=fed_axis)

    params_shape = jax.eval_shape(model.init, jax.random.key(0))
    pspecs = spec_lib.param_specs(cfg, params_shape, mesh)
    opt_shape = jax.eval_shape(optimizer.init, params_shape)
    ospecs = opt_state_specs(opt_shape, pspecs)
    bspecs = spec_lib.batch_spec(cfg, mesh)
    fspecs = fed_state_specs(fed_axis)

    # Axes that stay GSPMD-auto inside the manual-over-federation shard_map.
    # On the multi-pod mesh (manual='pod') the batch must be explicitly
    # re-constrained to 'data' inside the region — without this, propagation
    # through the layer scan falls back to replicated compute over 'data'
    # (observed: 16x flops blow-up in the dry-run).
    inner_batch_axes = tuple(a for a in ("data",) if a != fed_axis
                             and a in mesh.axis_names)

    def core(params, opt_state, fed_state, batch):
        with activation_sharding(mesh, inner_batch_axes):
            return _core_body(params, opt_state, fed_state, batch)

    def _core_body(params, opt_state, fed_state, batch):
        if inner_batch_axes:
            def _constrain(x):
                spec = P(inner_batch_axes, *([None] * (x.ndim - 1)))
                return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
            batch = jax.tree.map(_constrain, batch)

        def local_loss(p):
            return model.loss_fn(p, batch)[0]

        loss, grads = jax.value_and_grad(local_loss)(params)
        grads, gnorm = clip_by_global_norm(grads, grad_clip)

        if fed_cfg is not None and fed_cfg.lam > 0:
            if fed_cfg.hvp_subsample > 1:
                # curvature term estimated on a batch subsample: unbiased-ish
                # g^T H g at 1/k the HVP compute + activation memory
                k = fed_cfg.hvp_subsample
                sub = jax.tree.map(lambda x: x[: max(x.shape[0] // k, 1)], batch)
                grad_fn = jax.grad(lambda p: model.loss_fn(p, sub)[0])
            else:
                grad_fn = jax.grad(local_loss)
            agg, fed_state = gate_and_aggregate(
                grads, fed_state, fed_cfg, grad_fn=grad_fn, params=params
            )
        else:
            agg = jax.tree.map(lambda g: jax.lax.pmean(g, fed_axis), grads)
            fed_state = FedStats(
                steps=fed_state.steps + 1,
                tx=fed_state.tx + 1.0,
                last_alpha=jnp.ones((1,), jnp.float32),
                last_gain=jnp.zeros((1,), jnp.float32),
            )

        updates, opt_state = optimizer.update(agg, opt_state, params)
        params = apply_updates(params, updates)
        metrics = {
            "loss": jax.lax.pmean(loss, fed_axis),
            "grad_norm": jax.lax.pmean(gnorm, fed_axis),
            "comm_rate": fed_state.tx / jnp.maximum(fed_state.steps.astype(jnp.float32), 1.0),
        }
        return params, opt_state, fed_state, metrics

    # shard_map: manual over the federation axis; model (and, multi-pod, data)
    # dims stay GSPMD-auto.
    auto_axes = tuple(a for a in mesh.axis_names if a != fed_axis)

    def _strip(spec_tree):
        # in_specs for shard_map name only the manual axis; auto axes are
        # applied via jit in_shardings below.  A dim spec may be a tuple of
        # axes (e.g. ("pod", "data") for the batch dim) — keep only the
        # federation axis from it.
        def keep_axis(a):
            if isinstance(a, tuple):
                return fed_axis if fed_axis in a else None
            return a if a == fed_axis else None

        def keep(spec):
            return P(*[keep_axis(a) for a in (spec if spec is not None else ())])

        return jax.tree.map(keep, spec_tree,
                            is_leaf=lambda x: isinstance(x, P) or x is None)

    smapped = jax.shard_map(
        core,
        mesh=mesh,
        in_specs=(
            _replicated_like(pspecs),
            jax.tree.map(lambda s: P(), ospecs,
                         is_leaf=lambda x: isinstance(x, P) or x is None),
            fspecs,
            _strip(bspecs),
        ),
        out_specs=(
            _replicated_like(pspecs),
            jax.tree.map(lambda s: P(), ospecs,
                         is_leaf=lambda x: isinstance(x, P) or x is None),
            fspecs,
            P(),
        ),
        check_vma=False,
        axis_names={fed_axis},
    )

    def shard(tree, spec_tree):
        return jax.tree.map(
            lambda s: NamedSharding(mesh, s) if s is not None else None,
            spec_tree, is_leaf=lambda x: isinstance(x, P) or x is None,
        )

    step = jax.jit(
        smapped,
        in_shardings=(shard(params_shape, pspecs), shard(opt_shape, ospecs),
                      shard(None, fspecs), shard(None, bspecs)),
        out_shardings=(shard(params_shape, pspecs), shard(opt_shape, ospecs),
                       shard(None, fspecs), None),
        donate_argnums=(0, 1),
    )
    return TrainStepBundle(step=step, pspecs=pspecs, opt_specs=ospecs,
                           batch_specs=bspecs, fed_specs=fspecs,
                           num_agents=num_agents,
                           params_shape=params_shape, opt_shape=opt_shape,
                           fed_shape=jax.eval_shape(
                               lambda: FedStats.init(num_agents)))


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------

def build_prefill_step(model, cfg: ModelConfig, mesh):
    params_shape = jax.eval_shape(model.init, jax.random.key(0))
    pspecs = spec_lib.param_specs(cfg, params_shape, mesh)
    dp = spec_lib.batch_axes(mesh)

    def prefill(params, batch):
        with activation_sharding(mesh, dp):
            return model.prefill(params, batch["tokens"], batch.get("prefix_emb"))

    in_b = {"tokens": NamedSharding(mesh, P(dp, None))}
    if cfg.frontend != "none":
        in_b["prefix_emb"] = NamedSharding(mesh, P(dp, None, None))
    step = jax.jit(
        prefill,
        in_shardings=(jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs), in_b),
        out_shardings=None,
    )
    return step, pspecs


def build_serve_step(model, cfg: ModelConfig, mesh, shape: ShapeConfig):
    """One-token decode step against a seq_len-deep cache."""
    params_shape = jax.eval_shape(model.init, jax.random.key(0))
    pspecs = spec_lib.param_specs(cfg, params_shape, mesh)
    cache_shape = jax.eval_shape(
        functools.partial(model.init_cache, shape.global_batch, shape.seq_len)
    )
    batch_sharded = shape.global_batch >= max(
        mesh.shape.get("pod", 1) * mesh.shape["data"], 2
    )
    cspecs = spec_lib.cache_specs(cfg, cache_shape, mesh, batch_sharded=batch_sharded)
    dp = spec_lib.batch_axes(mesh) if batch_sharded else None

    def serve(params, cache, token, t):
        with activation_sharding(mesh, dp or ()):
            return model.decode_step(params, cache, token, t)

    step = jax.jit(
        serve,
        in_shardings=(
            jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs),
            jax.tree.map(lambda s: NamedSharding(mesh, s), cspecs),
            NamedSharding(mesh, P(dp)),
            None,
        ),
        out_shardings=(None, jax.tree.map(lambda s: NamedSharding(mesh, s), cspecs)),
        donate_argnums=(1,),
    )
    return step, pspecs, cspecs, cache_shape
