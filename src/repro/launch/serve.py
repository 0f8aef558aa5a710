"""Serving driver: batched prefill + token-by-token decode for any zoo arch.

CPU smoke: reduced configs, host mesh.  Production shapes lower via
dryrun.py (decode_32k / long_500k lower exactly this serve_step).

  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-370m --reduced \
      --prompt-len 64 --gen-len 32 --batch 4
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCH_NAMES, get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import build_serve_step
from repro.configs.base import ShapeConfig
from repro.models import build_model


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES, required=True)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--model-axis", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--greedy", action="store_true", default=True)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    mesh = make_host_mesh(args.model_axis)
    max_len = args.prompt_len + args.gen_len

    rng = jax.random.key(args.seed)
    params = model.init(rng)

    shape = ShapeConfig("serve", max_len, args.batch, "decode")
    step, pspecs, cspecs, cache_shape = build_serve_step(model, cfg, mesh, shape)

    cache = model.init_cache(args.batch, max_len)
    tokens = jax.random.randint(rng, (args.batch, args.prompt_len), 0,
                                cfg.vocab_size, dtype=jnp.int32)

    # prefill by stepping the decode path (keeps the cache layout uniform for
    # every family; bulk prefill is exercised by prefill_32k in the dry-run)
    t0 = time.time()
    logits = None
    for t in range(args.prompt_len):
        logits, cache = step(params, cache, tokens[:, t], jnp.int32(t))
    prefill_s = time.time() - t0

    out = []
    t0 = time.time()
    cur = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    for t in range(args.prompt_len, max_len):
        out.append(cur)
        logits, cache = step(params, cache, cur, jnp.int32(t))
        cur = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    gen_s = time.time() - t0
    gen = jnp.stack(out, axis=1)

    print(f"[serve] arch={cfg.name} batch={args.batch} "
          f"prompt={args.prompt_len} gen={args.gen_len}")
    print(f"[serve] prefill {prefill_s:.2f}s  "
          f"decode {gen_s:.2f}s ({args.gen_len * args.batch / max(gen_s, 1e-9):.1f} tok/s)")
    print(f"[serve] sample tokens: {gen[0, :16].tolist()}")
    assert bool(jnp.all(jnp.isfinite(logits))), "non-finite logits"
    print("[serve] OK")


if __name__ == "__main__":
    main()
