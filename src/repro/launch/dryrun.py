import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) combo.

Proves the distribution config is coherent without hardware:
  * builds the production mesh (16x16 single pod / 2x16x16 multi-pod);
  * instantiates abstract params/optimizer/caches via ``jax.eval_shape``
    (ShapeDtypeStruct only — no allocation);
  * ``jax.jit(step, in_shardings=...).lower(...).compile()`` must succeed;
  * records ``memory_analysis()`` (fits?), ``cost_analysis()`` (FLOPs/bytes)
    and the parsed collective bytes into experiments/dryrun/*.json for the
    roofline table (EXPERIMENTS.md §Dry-run / §Roofline).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""

import argparse
import functools
import sys
import time
import traceback
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.jsonio import json_dumps
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ARCH_IDS, INPUT_SHAPES, get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.models.registry import build_model
from repro.optim import adamw
from repro.roofline.analysis import analyze, count_params, model_flops
from repro.serve.engine import make_serve_step
from repro.sharding.context import ParallelContext
from repro.sharding.specs import (
    build_cache_specs,
    build_param_specs,
    input_specs_sharding,
)
from repro.train.step import make_train_step

OUT_DIR = os.path.join(os.path.dirname(__file__), "../../../experiments/dryrun")


def make_ctx(mesh, multi_pod: bool, moe_mode: str = "nimble",
             planner_iters: int = 12) -> ParallelContext:
    return ParallelContext(
        mesh=mesh,
        data_axes=("pod", "data") if multi_pod else ("data",),
        model_axis="model",
        ep_size=16,
        group_size=4,
        moe_mode=moe_mode,
        param_dtype=jnp.bfloat16,
        compute_dtype=jnp.bfloat16,
        remat=True,
    )


def _shardings_of(tree_specs, mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_specs)


def run_one(arch: str, shape_name: str, *, multi_pod: bool,
            moe_mode: str = "nimble", alt_frac: float = 0.5,
            cfg_overrides: Dict | None = None,
            ctx_overrides: Dict | None = None) -> Dict:
    t0 = time.time()
    import dataclasses as _dc
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(mesh.devices.shape))
    ctx = make_ctx(mesh, multi_pod, moe_mode)
    if alt_frac != 0.5:
        ctx = _dc.replace(ctx, moe_alt_frac=alt_frac)
    if ctx_overrides:
        ctx = _dc.replace(ctx, **ctx_overrides)
    cfg = get_config(arch)
    if cfg_overrides:
        cfg = _dc.replace(cfg, **cfg_overrides)
    shape = INPUT_SHAPES[shape_name]
    model = build_model(cfg, ctx)
    rec: Dict = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "mode": moe_mode,
    }
    if not model.supports(shape):
        rec["status"] = "skipped (DESIGN.md §7)"
        return rec
    if shape.name == "long_500k" and cfg.arch_type == "audio":
        rec["status"] = "skipped"
        return rec

    rng = jax.random.PRNGKey(0)
    params_abs = jax.eval_shape(model.init, rng)
    n_params = count_params(params_abs)
    rec["n_params"] = n_params
    p_specs = build_param_specs(params_abs, ctx)
    p_shard = _shardings_of(p_specs, mesh)

    ispecs = model.input_specs(shape)

    with jax.set_mesh(mesh):
        if shape.kind in ("train",):
            opt_cfg = adamw.AdamWConfig()
            opt_abs = jax.eval_shape(adamw.init, params_abs)
            o_shard = jax.tree.map(
                lambda l, s=None: None, opt_abs)  # placeholder
            o_specs = {
                "m": p_specs, "v": p_specs,
            }
            o_shard = adamw.OptState(
                m=_shardings_of(p_specs, mesh),
                v=_shardings_of(p_specs, mesh),
                step=NamedSharding(mesh, P()),
            )
            step_fn = make_train_step(model, opt_cfg)
            b_shard = input_specs_sharding(ispecs, ctx, shape)
            jf = jax.jit(
                step_fn,
                in_shardings=(p_shard, o_shard, b_shard),
                donate_argnums=(0, 1),
            )
            lowered = jf.lower(params_abs, opt_abs, ispecs)
            tokens = shape.global_batch * shape.seq_len
            kind = "train"
        elif shape.kind == "prefill":
            # §Perf B1: slice hidden state before lm_head (last_only) so the
            # TP logits collective is [B, 1, V] not [B, S, V].  Disable via
            # --set-ctx to measure the baseline.
            last_only = bool(int(os.environ.get("NIMBLE_PREFILL_FULL", "0")) == 0)

            def prefill(params, batch):
                logits, _ = model.forward(params, batch, last_only=last_only)
                return logits[:, -1]
            b_shard = input_specs_sharding(ispecs, ctx, shape)
            jf = jax.jit(prefill, in_shardings=(p_shard, b_shard))
            lowered = jf.lower(params_abs, ispecs)
            tokens = shape.global_batch * shape.seq_len
            kind = "prefill"
        else:  # decode
            cache_abs = jax.eval_shape(
                functools.partial(model.init_cache, shape.global_batch, shape)
            )
            c_specs = build_cache_specs(cache_abs, ctx)
            c_shard = _shardings_of(c_specs, mesh)
            serve = make_serve_step(model)
            tok_shard = input_specs_sharding(ispecs, ctx, shape)
            jf = jax.jit(
                serve,
                in_shardings=(p_shard, c_shard, tok_shard["token"],
                              tok_shard["pos"]),
                donate_argnums=(1,),
            )
            lowered = jf.lower(params_abs, cache_abs, ispecs["token"],
                               ispecs["pos"])
            tokens = shape.global_batch
            kind = "decode"

        compiled = lowered.compile()

    mem = compiled.memory_analysis()
    rec["bytes_per_device"] = {
        "argument": getattr(mem, "argument_size_in_bytes", None),
        "output": getattr(mem, "output_size_in_bytes", None),
        "temp": getattr(mem, "temp_size_in_bytes", None),
        "peak": (getattr(mem, "argument_size_in_bytes", 0) or 0)
        + (getattr(mem, "temp_size_in_bytes", 0) or 0),
    }
    mf = model_flops(cfg, n_params, tokens, kind)
    roof = analyze(compiled, n_chips, mf)
    rec["roofline"] = roof.as_dict()
    rec["status"] = "ok"
    rec["compile_s"] = round(time.time() - t0, 1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--moe-mode", default="nimble",
                    choices=["nimble", "direct", "stripe"])
    ap.add_argument("--alt-frac", type=float, default=0.5)
    ap.add_argument("--set", action="append", default=[], metavar="K=V",
                    help="ModelConfig override, e.g. --set mlstm_chunk=64")
    ap.add_argument("--set-ctx", action="append", default=[], metavar="K=V",
                    help="ParallelContext override, e.g. --set-ctx remat=False")
    ap.add_argument("--tag", default="", help="suffix for the output json")
    ap.add_argument("--out", default=OUT_DIR)
    args = ap.parse_args()
    enable_compile_cache()

    def _parse_kv(items):
        out = {}
        for it in items:
            k, v = it.split("=", 1)
            for cast in (int, float):
                try:
                    v = cast(v)
                    break
                except ValueError:
                    continue
            if v in ("True", "true"):
                v = True
            elif v in ("False", "false"):
                v = False
            out[k] = v
        return out

    cfg_overrides = _parse_kv(args.set)
    ctx_overrides = _parse_kv(args.set_ctx)

    os.makedirs(args.out, exist_ok=True)
    combos = []
    archs = ARCH_IDS[:-1] if args.all else [args.arch]  # paper-moe via bench
    shapes = list(INPUT_SHAPES) if (args.all or not args.shape) else [args.shape]
    for a in archs:
        for s in shapes:
            combos.append((a, s))

    failures = 0
    for a, s in combos:
        tag = f"{a}_{s}_{'2x16x16' if args.multi_pod else '16x16'}_{args.moe_mode}"
        if args.alt_frac != 0.5:
            tag += f"_alt{args.alt_frac}"
        if args.tag:
            tag += f"_{args.tag}"
        path = os.path.join(args.out, tag + ".json")
        try:
            rec = run_one(a, s, multi_pod=args.multi_pod,
                          moe_mode=args.moe_mode, alt_frac=args.alt_frac,
                          cfg_overrides=cfg_overrides,
                          ctx_overrides=ctx_overrides)
            if cfg_overrides or ctx_overrides:
                rec["overrides"] = {**cfg_overrides,
                                    **{f"ctx.{k}": v
                                       for k, v in ctx_overrides.items()}}
        except Exception as e:
            rec = {"arch": a, "shape": s, "status": "FAIL",
                   "error": f"{type(e).__name__}: {e}",
                   "trace": traceback.format_exc()[-2000:]}
            failures += 1
        with open(path, "wb") as f:
            f.write(json_dumps(rec, indent=True))
        status = rec.get("status")
        roof = rec.get("roofline", {})
        print(
            f"[dryrun] {a:24s} {s:12s} {status:8s} "
            f"dom={roof.get('dominant','-'):10s} "
            f"comp={roof.get('compute_s',0):.3e}s "
            f"mem={roof.get('memory_s',0):.3e}s "
            f"coll={roof.get('collective_s',0):.3e}s "
            f"({rec.get('compile_s','-')}s)",
            flush=True,
        )
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
