"""Training driver: federated gain-gated training of any zoo architecture.

On this CPU container it runs reduced configs end-to-end (the full configs
are exercised via dryrun.py); on a real TPU fleet the same driver runs the
production mesh — the only difference is ``--host-mesh``.

Example (CPU smoke, 2x2 host mesh on 4 forced host devices):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.train --arch mamba2-370m --reduced \
      --steps 20 --lam 1e-3 --log-every 5
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro.checkpoint import save as save_ckpt
from repro.configs import ARCH_NAMES, get_config
from repro.core.fed_sgd import FedConfig, FedStats
from repro.data.synthetic_lm import SyntheticLMConfig, make_lm_batch
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import federation_axis, make_host_mesh, make_production_mesh
from repro.launch.steps import build_train_step
from repro.models import build_model
from repro.optim import adamw, cosine_schedule


def make_batch_fn(cfg, seq_len: int, global_batch: int):
    lm = SyntheticLMConfig(vocab_size=cfg.vocab_size, seq_len=seq_len,
                           global_batch=global_batch)

    def fn(rng, step):
        batch = make_lm_batch(lm, rng, step)
        if cfg.frontend == "vision":
            P = cfg.num_prefix
            batch = {
                "tokens": batch["tokens"][:, P:] if batch["tokens"].shape[1] > P
                          else batch["tokens"],
                "targets": batch["targets"][:, P:] if batch["targets"].shape[1] > P
                           else batch["targets"],
                "mask": batch["mask"][:, P:] if batch["mask"].shape[1] > P
                        else batch["mask"],
                "prefix_emb": 0.02 * jax.random.normal(
                    jax.random.fold_in(rng, 17), (global_batch, P, cfg.frontend_dim)),
            }
        elif cfg.frontend == "audio":
            batch["prefix_emb"] = 0.02 * jax.random.normal(
                jax.random.fold_in(rng, 19),
                (global_batch, cfg.num_prefix, cfg.frontend_dim))
        return batch

    return fn


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES, required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced (CPU-scale) variant of the same family")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--lam", type=float, default=0.0,
                    help="communication price lambda (0 => always transmit)")
    ap.add_argument("--rho", type=float, default=0.999)
    ap.add_argument("--estimator", choices=("hvp", "gnorm"), default="hvp")
    ap.add_argument("--host-mesh", action="store_true", default=True)
    ap.add_argument("--model-axis", type=int, default=1)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--checkpoint", default="")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    mesh = (make_host_mesh(args.model_axis) if args.host_mesh
            else make_production_mesh())
    fed_axis = federation_axis(mesh)

    fed_cfg = FedConfig(axis=fed_axis, eps=1.0, lam=args.lam, rho=args.rho,
                        horizon=args.steps, estimator=args.estimator)
    opt = adamw(cosine_schedule(args.lr, warmup=max(args.steps // 10, 1),
                                total=args.steps))
    bundle = build_train_step(model, cfg, mesh, opt,
                              fed_cfg=fed_cfg if args.lam > 0 else None)

    rng = jax.random.key(args.seed)
    params = model.init(rng)
    params = jax.device_put(
        params, jax.tree.map(lambda s: NamedSharding(mesh, s), bundle.pspecs))
    opt_state = opt.init(params)
    fed_state = FedStats.init(bundle.num_agents)
    batch_fn = make_batch_fn(cfg, args.seq_len, args.global_batch)

    print(f"[train] arch={cfg.name} agents={bundle.num_agents} "
          f"fed_axis={fed_axis} lam={args.lam} estimator={args.estimator}")
    t0 = time.time()
    history = []
    for step in range(args.steps):
        batch = batch_fn(rng, step)
        params, opt_state, fed_state, metrics = bundle.step(
            params, opt_state, fed_state, batch)
        if step % args.log_every == 0 or step == args.steps - 1:
            m = jax.tree.map(float, metrics)
            m["step"] = step
            m["wall_s"] = round(time.time() - t0, 2)
            history.append(m)
            print(f"[train] step={step:5d} loss={m['loss']:.4f} "
                  f"gnorm={m['grad_norm']:.3f} comm_rate={m['comm_rate']:.3f} "
                  f"({m['wall_s']}s)")

    if args.checkpoint:
        save_ckpt(args.checkpoint, jax.device_get(params),
                  metadata={"arch": cfg.name, "steps": args.steps,
                            "history": history})
        print(f"[train] checkpoint -> {args.checkpoint}")
    print(json.dumps({"final": history[-1]}))


if __name__ == "__main__":
    main()
