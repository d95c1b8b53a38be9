"""Smoke run of the NIMBLE MoE system on TPU v5e, through its normal entry points.

Default, on one chip: granite-moe-1b-a400m at its published widths (24 layers,
d=1024, 32 experts top-8, vocab 49155, f32 params, random weights from
``--seed``), in five phases:

  serve     ``ServeEngine.generate``: batch 4, prompt 128, 16 new tokens, greedy;
  forward   one full-sequence ``forward`` at S=2048, whose compiled program must
            hold both Pallas kernels (``flash_attention``, ``grouped_ffn``);
  flash     the flash kernel alone at granite's heads and S=2048 (16 KV
            blocks) against the quadratic reference ``mha_ref``;
  agree     ``forward``'s last-position logits against the decode path's
            (``ServeEngine.prefill``) for the serve prompts;
  planner   the jitted MWU planner (``plan_flows`` + ``quantize_chunks``) at the
            v5e:2x2 geometry (n=4, G=2) on hot-spot demand, against host
            ``solve_mwu`` within the 25% Z parity of DESIGN.md §2.4.

``--chips 4``, on the four-chip v5e:2x2 host, runs only the multi-chip path
(``repro.launch.selftest`` checks at real sizes): the NIMBLE dataplane in its
three modes and stock ``all_to_all`` on bf16 128 KiB chunks under uniform
random counts and under hot-spot 0.9 skew, bit-exact against
``ref_all_to_allv``; ``MoEDispatcher`` at granite widths
(8 experts held per chip) against the dense per-token reference; and EP train
steps of full-width granite on a (data=1, model=4) mesh in ``nimble`` mode.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # v5e:2x2

Each phase prints its compile and steady seconds and its checks; the run then
prints peak device memory and, as its last line,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Where a check fails, where JAX finds no TPU, or where ``src/repro`` is not
beside this file, it exits non-zero and prints no such line.  Everything runs
in this one process, which holds the chips.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"

ARCH = "granite-moe-1b-a400m"
MB = float(1 << 20)
#: forward-vs-decode agreement at f32 ("highest") matmul precision, relative
#: to the largest logit.  On v5e it reads 3.8e-7, and 1.7e-2 at the default
#: (bf16-pass) precision; see PERF.md
AGREE_RTOL = 1e-5
#: flash kernel vs ``mha_ref`` at S=2048, relative to the largest output.
#: On v5e it reads 4.3e-7, and 2.7e-3 on bf16-rounded inputs; see PERF.md
FLASH_RTOL = 1e-5
#: DESIGN.md §2.4: host and jit MWU agree on max normalized load within 25%
Z_RTOL = 0.25
KERNELS = ("flash_attention", "grouped_ffn")


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def _timed(fn, *args):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def phase_serve(engine, prompts, n_new: int) -> bool:
    """Greedy generation twice: the first call compiles, the second is
    steady; greedy decoding must repeat itself exactly."""
    out, first_s = _timed(lambda: engine.generate(prompts, n_new=n_new))
    again, steady_s = _timed(lambda: engine.generate(prompts, n_new=n_new))
    vocab = engine.model.cfg.vocab
    ok = (out.shape == (prompts.shape[0], n_new)
          and bool(((out >= 0) & (out < vocab)).all())
          and (out == again).all())
    log(f"serve: batch={prompts.shape[0]} prompt={prompts.shape[1]} "
        f"new={n_new} first_call_s={first_s:.3f} steady_s={steady_s:.3f} "
        f"tokens={out.shape} {'OK' if ok else 'FAIL'}")
    return bool(ok)


def phase_forward(model, params, tokens):
    """One full-sequence forward.  Returns (ok, kernels compiled into it)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import tpu_kernels

    fwd = jax.jit(lambda p, t: model.forward(p, {"tokens": t})[0])
    t0 = time.perf_counter()
    compiled = fwd.lower(params, tokens).compile()
    compile_s = time.perf_counter() - t0
    kernels = tpu_kernels(compiled.as_text())
    _, first_s = _timed(compiled, params, tokens)
    logits, steady_s = _timed(compiled, params, tokens)
    b, s = tokens.shape
    ok = (logits.shape == (b, s, model.cfg.vocab)
          and bool(jnp.isfinite(logits).all()))
    log(f"forward: batch={b} seq={s} compile_s={compile_s:.3f} "
        f"first_run_s={first_s:.3f} steady_s={steady_s:.3f} "
        f"logits={tuple(logits.shape)} finite={ok} "
        f"kernels={sorted(kernels)} {'OK' if ok else 'FAIL'}")
    return ok, kernels


def phase_flash(cfg, seq: int, seed: int = 0) -> bool:
    """The Pallas flash kernel alone at the model's head widths, over
    ``seq / 128`` KV blocks, against the quadratic reference in f32."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.flash_attention.flash import flash_attention
    from repro.kernels.flash_attention.ref import mha_ref

    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q, k, v = (jax.random.normal(key, (1, h, seq, cfg.head_dim), jnp.float32)
               for key, h in zip(keys, (cfg.n_heads, cfg.n_kv_heads,
                                        cfg.n_kv_heads)))
    kernel = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=cfg.window))
    ref = jax.jit(lambda q, k, v: mha_ref(q, k, v, causal=True,
                                          window=cfg.window))
    with jax.default_matmul_precision("highest"):
        out, first_s = _timed(kernel, q, k, v)
        want = np.asarray(ref(q, k, v))
    err = float(np.abs(np.asarray(out) - want).max())
    scale = float(np.abs(want).max())
    ok = err <= FLASH_RTOL * scale
    log(f"flash: heads={cfg.n_heads}/{cfg.n_kv_heads} dh={cfg.head_dim} "
        f"seq={seq} max_abs_err={err:.3e} max_abs_out={scale:.3e} "
        f"rel={err / scale:.3e} tol_rel={FLASH_RTOL:g} "
        f"first_call_s={first_s:.3f} {'OK' if ok else 'FAIL'}")
    return ok


def phase_agree(model, engine, prompts, precision: str = "highest") -> bool:
    """Last-position logits of ``forward`` vs the prefill (decode path)."""
    import jax
    import numpy as np

    fwd = jax.jit(lambda p, t: model.forward(p, {"tokens": t},
                                             last_only=True)[0][:, -1])
    with jax.default_matmul_precision(precision):
        (dec, _), dec_s = _timed(engine.prefill, prompts)
        full, fwd_s = _timed(fwd, engine.params, prompts)
    dec, full = np.asarray(dec), np.asarray(full)
    err = float(np.abs(dec - full).max())
    scale = float(np.abs(full).max())
    ok = bool(np.isfinite(dec).all()) and err <= AGREE_RTOL * scale
    log(f"agree: forward vs decode last-position logits max_abs_err={err:.3e} "
        f"max_abs_logit={scale:.3e} rel={err / scale:.3e} "
        f"tol_rel={AGREE_RTOL:g} precision={precision} "
        f"prefill_first_call_s={dec_s:.3f} "
        f"forward_first_call_s={fwd_s:.3f} {'OK' if ok else 'FAIL'}")
    return ok


def phase_planner(n: int = 4, group_size: int = 2, max_chunks: int = 32,
                  hotspot: float = 0.9) -> bool:
    """Jitted MWU plan + chunk quantization vs host ``solve_mwu``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.dataplane import NimbleAllToAll
    from repro.core.mcf import solve_mwu
    from repro.core.planner import plan_flows, quantize_chunks
    from repro.launch.selftest import hot_spot_counts

    comm = NimbleAllToAll("x", n, group_size, max_chunks=max_chunks,
                          chunk_bytes=MB)
    counts = hot_spot_counts(n, max_chunks, hotspot)
    demand = counts.astype(np.float32) * np.float32(MB)

    def plan(d, dc):
        flows, loads = plan_flows(d, comm.tables, comm.cfg)
        chunks = quantize_chunks(flows, dc, comm.sched.S, comm.rel_of_pair,
                                 MB)
        return flows, loads, chunks

    t0 = time.perf_counter()
    compiled = jax.jit(plan).lower(jnp.asarray(demand),
                                   jnp.asarray(counts)).compile()
    compile_s = time.perf_counter() - t0
    compiled(jnp.asarray(demand), jnp.asarray(counts))
    (flows, loads, chunks), steady_s = _timed(
        compiled, jnp.asarray(demand), jnp.asarray(counts))
    flows, chunks = np.asarray(flows), np.asarray(chunks)
    z_jit = float(np.max(np.asarray(loads) / comm.tables.caps))
    demands = {(s, d): float(demand[s, d]) for s in range(n)
               for d in range(n) if demand[s, d] > 0}
    z_host = solve_mwu(comm.topo, demands, eps=MB).max_normalized_load()
    caps = np.asarray(comm.sched.S)[np.maximum(comm.rel_of_pair, 0)]
    routed = np.allclose(flows.sum(-1), demand, rtol=1e-5)
    exact = np.array_equal(chunks.sum(-1), counts) and bool(
        (chunks[..., 1:] <= caps[..., 1:]).all())
    parity = max(z_jit, z_host) <= min(z_jit, z_host) * (1 + Z_RTOL)
    ok = bool(routed and exact and parity)
    log(f"planner: n={n} G={group_size} hotspot={hotspot} "
        f"compile_s={compile_s:.3f} steady_s={steady_s:.6f} "
        f"z_jit={z_jit:.6g} z_host={z_host:.6g} z_parity={parity} "
        f"all_bytes_routed={routed} chunks_exact={exact} "
        f"{'OK' if ok else 'FAIL'}")
    return ok


def run_one_chip(seed: int) -> bool:
    import jax
    import numpy as np

    from repro.configs.base import get_config
    from repro.models.registry import build_model
    from repro.serve.engine import ServeEngine

    cfg = get_config(ARCH)
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"{cfg.name}: {cfg.n_layers}L d={cfg.d_model} E={cfg.n_experts} "
        f"top-{cfg.top_k} vocab={cfg.vocab} params={n_params} f32")
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, cfg.vocab, (4, 128), dtype=np.int32)
    engine = ServeEngine(model, params, max_len=128 + 16)
    ok = phase_serve(engine, prompts, n_new=16)
    long_seq = rng.integers(0, cfg.vocab, (1, 2048), dtype=np.int32)
    fwd_ok, kernels = phase_forward(model, params, long_seq)
    missing = sorted(set(KERNELS) - kernels)
    log(f"kernels: tpu_custom_call for {sorted(kernels)} "
        f"missing={missing} {'OK' if not missing else 'FAIL'}")
    ok &= fwd_ok and not missing
    ok &= phase_flash(cfg, seq=2048, seed=seed)
    ok &= phase_agree(model, engine, prompts)
    ok &= phase_planner()
    return bool(ok)


def run_four_chips(cfg, seed: int = 0, *, chunk_elems: int = 64 * 1024,
                   moe_tokens: int = 256, seq: int = 256,
                   steps: int = 3) -> bool:
    """The multi-chip path on every device JAX finds (four on v5e:2x2).
    The default chunk is 64 Ki bf16 elements = 128 KiB."""
    import jax
    import jax.numpy as jnp

    from repro.launch.selftest import (
        EP_SIZE,
        check_dataplane,
        check_ep_train,
        check_moe_comm,
        hot_spot_counts,
        uniform_counts,
    )

    n = len(jax.devices())
    t0 = time.perf_counter()
    ok = True
    for demand, counts in (("uniform", uniform_counts(n, 32, seed)),
                           ("hotspot0.9", hot_spot_counts(n, 32, 0.9))):
        ok &= check_dataplane(n, n // 2, counts, max_chunks=32,
                              chunk_elems=chunk_elems, dtype=jnp.bfloat16,
                              demand=demand)
    t1 = time.perf_counter()
    log(f"dataplane: wall_s={t1 - t0:.3f} (compile included)")
    ok &= check_moe_comm(n, tokens=moe_tokens, d_model=cfg.d_model,
                         top_k=cfg.top_k, n_experts=cfg.n_experts,
                         chunk_tokens=16)
    t2 = time.perf_counter()
    log(f"moe_comm: experts_per_chip={cfg.n_experts // n} "
        f"wall_s={t2 - t1:.3f} (compile included)")
    ok &= check_ep_train(cfg, batch=EP_SIZE, seq=seq, steps=steps, seed=seed)
    log(f"ep_train: wall_s={time.perf_counter() - t2:.3f} "
        f"(compile included)")
    return bool(ok)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serve/forward/planner on one chip; "
                         "4: the multi-chip path on v5e:2x2")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no src/repro beside {Path(__file__).name}",
              file=sys.stderr)
        return 2
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found platform {platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    log(f"device: {platform} {devices[0].device_kind} x{len(devices)} "
        f"compile_cache={cache_dir}")
    t0 = time.perf_counter()
    if args.chips == 4:
        from repro.configs.base import get_config

        ok = run_four_chips(get_config(ARCH), args.seed)
    else:
        ok = run_one_chip(args.seed)
    for d in devices[:args.chips]:
        peak = (d.memory_stats() or {}).get("peak_bytes_in_use")
        log(f"memory: device {d.id} peak_bytes_in_use={peak}")
    log(f"total_s={time.perf_counter() - t0:.3f} {'ALL OK' if ok else 'FAIL'}")
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
