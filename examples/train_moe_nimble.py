"""End-to-end driver: expert-parallel MoE training with NIMBLE dispatch.

Trains a granite-family MoE LM on a (data=2, model=4) mesh of 8 forced host
devices.  The experts are sharded over the model axis; every train step's
token dispatch/combine is a skewed All-to-Allv executed by the NIMBLE
dataplane (live demand -> jittable MWU plan -> scheduled ppermute rounds).
Exactly the paper's §V-D workload, end to end in JAX.  The dispatch stack
is wired through one ``repro.api.Session`` describing the EP fabric
(``ParallelContext.session``, DESIGN.md §5) — no per-application planner
or telemetry plumbing.

Presets:
    default : ~8M params,  200 steps  — a couple of minutes on CPU
    --big   : ~100M params, 300 steps — the brief's "train ~100M for a few
              hundred steps" driver (expect ~1h on CPU; instant on a pod)

Run:
    PYTHONPATH=src python examples/train_moe_nimble.py [--big] [--mode direct]
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import Session, SessionSpec, TopologySpec
from repro.configs.base import get_config
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.launch.mesh import make_test_mesh
from repro.models.registry import build_model
from repro.optim import adamw
from repro.sharding.context import ParallelContext
from repro.sharding.specs import build_param_shardings
from repro.train.step import make_train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--big", action="store_true", help="~100M params preset")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--mode", default="nimble",
                    choices=["nimble", "direct", "stripe"],
                    help="dispatch/combine routing mode")
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    base = get_config("granite-moe-1b-a400m")
    if args.big:
        cfg = dataclasses.replace(
            base, name="granite-moe-100m", n_layers=10, d_model=512,
            n_heads=8, n_kv_heads=4, d_ff=512, vocab=16384,
            n_experts=8, top_k=2,
        )
        steps = args.steps or 300
        seq = args.seq or 256
    else:
        cfg = dataclasses.replace(
            base, name="granite-moe-8m", n_layers=4, d_model=256,
            n_heads=4, n_kv_heads=2, d_ff=256, vocab=4096,
            n_experts=8, top_k=2,
        )
        steps = args.steps or 200
        seq = args.seq or 128

    mesh = make_test_mesh(model=4)
    # one declarative session describes the EP fabric (4 chips = 2 "nodes"
    # x 2) and hands the model zoo ready-wired NIMBLE dispatchers
    session = Session(SessionSpec(
        topology=TopologySpec(n_devices=4, group_size=2), tenant="moe-train",
    ))
    ctx = ParallelContext(mesh=mesh, data_axes=("data",), ep_size=4,
                          group_size=2, moe_mode=args.mode, session=session)
    model = build_model(cfg, ctx)
    params = model.init(jax.random.PRNGKey(args.seed))
    n_par = sum(x.size for x in jax.tree.leaves(params))
    print(f"[moe-train] {cfg.name}: {n_par / 1e6:.1f}M params, "
          f"{cfg.n_experts}e top-{cfg.top_k}, mesh=(data=2, model=4), "
          f"mode={args.mode}")

    opt_cfg = adamw.AdamWConfig(lr=args.lr, warmup_steps=20, total_steps=steps)
    opt = adamw.init(params)
    step_fn = make_train_step(model, opt_cfg)
    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=seq,
                                  global_batch=args.batch, seed=args.seed))

    with jax.set_mesh(mesh):
        params = jax.device_put(params, build_param_shardings(params, ctx))
        jf = jax.jit(step_fn, donate_argnums=(0, 1))
        losses, t0 = [], time.time()
        for s in range(steps):
            b = {k: jnp.asarray(v) for k, v in data.batch(s).items()}
            params, opt, m = jf(params, opt, b)
            losses.append(float(m["loss"]))
            if s % 20 == 0 or s == steps - 1:
                print(f"[moe-train] step {s:4d} loss {losses[-1]:.4f} "
                      f"gnorm {float(m['grad_norm']):.3f} "
                      f"({time.time() - t0:.1f}s)", flush=True)

    first = np.mean(losses[:10])
    last = np.mean(losses[-10:])
    print(f"[moe-train] loss {first:.4f} -> {last:.4f} "
          f"({'improved' if last < first else 'NOT improved'})")
    assert last < first, "training did not reduce loss"
    session.close()
    return losses


if __name__ == "__main__":
    main()
