"""Multi-device selftest on whatever devices JAX finds.

Checks, each against a plain reference:

  1. the NIMBLE dataplane (``nimble``, ``direct``, ``stripe``) and stock
     ``all_to_all`` move an All-to-Allv bit-exactly (``ref_all_to_allv``),
     under uniform random counts (many pairs full at once) and under
     hot-spot skew;
  2. MoE dispatch/combine equals the dense per-token reference under skew;
  3. an expert-parallel train step on a ``(data, model)`` mesh gives a finite
     first-step loss equal to the single-device loss and to ``direct`` mode,
     with the expert weights spread over every device of the mesh.

Every NIMBLE axis is split into two groups ("nodes"): the flat dataplane over
n devices uses group size n // 2, and the EP model axis (4 wide) uses 2.
On four chips that is the ``v5e:2x2`` host; on the CPU give the process 8
virtual devices before JAX starts:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=src python -m repro.launch.selftest

Each ``check_*`` takes its sizes as arguments, so ``chip_smoke.py`` runs the
same checks in its own process at real sizes.  Exit code 0 = all pass.
"""

from __future__ import annotations

import dataclasses
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.dataplane import (
    NimbleAllToAll,
    baseline_all_to_all,
    ref_all_to_allv,
)
from repro.core.moe_comm import MoECommConfig, MoEDispatcher
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_test_mesh

#: width of the EP model axis; the rest of the devices form the data axis
EP_SIZE = 4


def hot_spot_counts(n: int, max_chunks: int, hotspot: float) -> np.ndarray:
    """[n, n] chunk counts: each source sends ``hotspot`` of its chunks to
    one hot destination (device 0; device 1 for source 0) and splits the
    rest evenly over the others (paper Fig. 7)."""
    counts = np.zeros((n, n), dtype=np.int32)
    for s in range(n):
        hd = 0 if s != 0 else 1
        counts[s, hd] = int(round(max_chunks * hotspot))
        others = [d for d in range(n) if d not in (s, hd)]
        for d in others:
            counts[s, d] = int(max_chunks * (1 - hotspot) / len(others))
    return counts


def uniform_counts(n: int, max_chunks: int, seed: int = 0) -> np.ndarray:
    """[n, n] chunk counts drawn uniformly from [0, max_chunks]: arbitrary
    demand in which many pairs are full at once."""
    rng = np.random.default_rng(seed)
    return rng.integers(0, max_chunks + 1, size=(n, n)).astype(np.int32)


def _bits(a: np.ndarray) -> np.ndarray:
    """View an array as unsigned ints of its width, for bitwise equality."""
    return np.ascontiguousarray(a).view(f"u{a.dtype.itemsize}")


def check_dataplane(n: int, group_size: int, counts: np.ndarray,
                    max_chunks: int, chunk_elems: int, dtype=jnp.float32,
                    demand: str = "") -> bool:
    """All three dataplane modes and stock all_to_all vs the numpy oracle,
    for the [n, n] chunk ``counts`` (each at most ``max_chunks``)."""
    mesh = Mesh(np.array(jax.devices()[:n]), ("x",))
    C, E = max_chunks, chunk_elems
    counts = np.asarray(counts, dtype=np.int32)
    assert counts.shape == (n, n) and int(counts.max()) <= C
    rng = np.random.default_rng(0)
    x_all = rng.normal(size=(n, n, C, E)).astype(dtype)
    for s in range(n):
        for d in range(n):
            x_all[s, d, counts[s, d]:] = 0
    yref, rref = ref_all_to_allv(x_all, counts)
    x_in = jnp.asarray(x_all.reshape(n * n, C, E))
    c_in = jnp.asarray(counts.reshape(n * n))
    chunk_bytes = E * jnp.dtype(dtype).itemsize
    full = int((counts == C).sum())

    def stock(x, c):
        return (baseline_all_to_all(x, "x"),
                jax.lax.all_to_all(c, "x", 0, 0, tiled=True))

    fns = {}
    for mode in ["direct", "stripe", "nimble"]:
        fns[mode] = NimbleAllToAll("x", n, group_size, max_chunks=C,
                                   chunk_bytes=chunk_bytes, mode=mode)
    fns["all_to_all"] = stock
    ok = True
    for name, fn in fns.items():
        fm = jax.shard_map(fn, mesh=mesh, in_specs=(P("x"), P("x")),
                           out_specs=(P("x"), P("x")))
        y, r = jax.jit(fm)(x_in, c_in)
        y = np.asarray(y).reshape(n, n, C, E)
        r = np.asarray(r).reshape(n, n)
        good = (np.array_equal(_bits(y), _bits(yref))
                and np.array_equal(r, rref))
        print(f"[selftest] dataplane {name} n={n} G={group_size} "
              f"demand={demand} full_pairs={full} C={C} "
              f"chunk={chunk_bytes}B {jnp.dtype(dtype).name}: "
              f"{'bit-exact OK' if good else 'FAIL'}")
        ok &= good
    return ok


def check_moe_comm(n: int, tokens: int, d_model: int, top_k: int,
                   n_experts: int, chunk_tokens: int,
                   capacity_factor: float = 8.0) -> bool:
    """Dispatch -> per-expert scale -> combine vs the dense per-token
    reference, with half of all assignments sent to experts 0 and 1."""
    mesh = Mesh(np.array(jax.devices()[:n]), ("x",))
    N, k = n * tokens, top_k
    rng = np.random.default_rng(1)
    toks = rng.normal(size=(N, d_model)).astype(np.float32)
    eidx = rng.integers(0, n_experts, size=(N, k))
    hot = rng.random((N, k)) < 0.5
    eidx = np.where(hot, rng.integers(0, 2, size=(N, k)), eidx).astype(
        np.int32)
    gw = rng.random((N, k)).astype(np.float32)
    yref = np.zeros_like(toks)
    for j in range(k):
        yref += gw[:, j:j + 1] * toks * (eidx[:, j:j + 1] + 1.0)
    # f32 rounding of a k-term gated sum, relative to the output's scale
    # (a bf16 payload misses it by ~1e3x), and never looser than 1e-4
    tol = min(1e-4, 1e-5 * float(np.abs(yref).max()))
    ok = True
    for mode in ["direct", "nimble"]:
        cfg = MoECommConfig(n_devices=n, n_experts=n_experts, d_model=d_model,
                            chunk_tokens=chunk_tokens,
                            capacity_factor=capacity_factor,
                            group_size=max(1, n // 2), mode=mode)
        disp = MoEDispatcher("x", cfg)

        def f(tok, ei, w):
            rt, el, st = disp.dispatch(tok, ei)
            me = jax.lax.axis_index("x")
            scale = jnp.where(
                el >= 0,
                (el + me * cfg.experts_per_device + 1).astype(jnp.float32),
                0.0,
            )
            return (disp.combine(rt * scale[..., None], st, w),
                    st["dropped"][None])

        fm = jax.shard_map(f, mesh=mesh, in_specs=(P("x"),) * 3,
                           out_specs=(P("x"), P("x")))
        y, dropped = jax.jit(fm)(jnp.asarray(toks), jnp.asarray(eidx),
                                 jnp.asarray(gw))
        err = float(np.abs(np.asarray(y) - yref).max())
        n_drop = int(np.asarray(dropped).sum())
        good = err <= tol and n_drop == 0
        print(f"[selftest] moe_comm {mode} n={n} T={tokens} d={d_model} "
              f"k={k} E={n_experts}: max_err={err:.3g} tol={tol:.3g} "
              f"dropped={n_drop} {'OK' if good else 'FAIL'}")
        ok &= good
    return ok


def check_ep_train(cfg, batch: int, seq: int, steps: int = 1,
                   seed: int = 0) -> bool:
    """EP train steps (nimble) on a (data, model=EP_SIZE) mesh.

    The first step's loss must equal the single-device forward loss of the
    same params and batch, and the EP forward loss in ``direct`` mode.  The
    dispatch buffers hold every assignment (capacity factor EP_SIZE), so no
    token is dropped and the EP model computes the single-device function."""
    from repro.models.registry import build_model
    from repro.optim import adamw
    from repro.sharding.context import SINGLE, ParallelContext
    from repro.sharding.specs import build_param_shardings
    from repro.train.step import make_train_step

    cfg = dataclasses.replace(cfg, moe_capacity_factor=float(EP_SIZE))
    mesh = make_test_mesh(model=EP_SIZE)
    rng = np.random.default_rng(seed)
    data = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab, (batch, seq),
                                           dtype=np.int32)),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab, (batch, seq),
                                           dtype=np.int32)),
    }
    # single-device reference first, while the unsharded params are the only
    # copy on the chip
    model1 = build_model(cfg, SINGLE)
    params = jax.jit(model1.init)(jax.random.PRNGKey(seed))
    loss_1 = float(jax.jit(model1.loss)(params, data))

    def ctx_for(mode):
        return ParallelContext(mesh=mesh, data_axes=("data",),
                               ep_size=EP_SIZE, group_size=EP_SIZE // 2,
                               moe_mode=mode)

    with jax.set_mesh(mesh):
        params = jax.device_put(params, build_param_shardings(params,
                                                              ctx_for("nimble")))
        shards = params["blocks"]["wg"].addressable_shards
        n_dev = len({s.device for s in shards})
        loss_direct = float(jax.jit(build_model(cfg, ctx_for("direct")).loss)(
            params, data))
        model = build_model(cfg, ctx_for("nimble"))
        opt = adamw.init(params)
        step = jax.jit(make_train_step(model, adamw.AdamWConfig()),
                       donate_argnums=(0, 1))
        losses = []
        for _ in range(steps):
            params, opt, metrics = step(params, opt, data)
            losses.append(float(metrics["loss"]))
    loss_ep = losses[0]
    good = (all(np.isfinite(losses))
            # zeroing the expert layer moves it 7e-2, halving the batch
            # 6e-2 (reduced config on the CPU; PERF.md)
            and abs(loss_ep - loss_1) < 1e-3
            and abs(loss_ep - loss_direct) < 1e-4
            and n_dev == mesh.devices.size)
    print(f"[selftest] EP train mesh={dict(mesh.shape)} G={EP_SIZE // 2} "
          f"{cfg.name}: loss_ep={loss_ep:.6f} loss_direct={loss_direct:.6f} "
          f"loss_single={loss_1:.6f} steps={[round(x, 4) for x in losses]} "
          f"expert_shard_devices={n_dev} {'OK' if good else 'FAIL'}")
    return good


def main():
    from repro.configs.base import get_config

    enable_compile_cache()
    n = len(jax.devices())
    cfg = dataclasses.replace(get_config("granite-moe-1b-a400m").reduced(),
                              n_experts=8, top_k=2)
    G = max(1, n // 2)
    ok = check_dataplane(n, G, uniform_counts(n, 16), max_chunks=16,
                         chunk_elems=32, demand="uniform")
    ok &= check_dataplane(n, G, hot_spot_counts(n, 64, 0.9), max_chunks=64,
                          chunk_elems=32, demand="hotspot0.9")
    ok &= check_moe_comm(n, tokens=64, d_model=16, top_k=2, n_experts=16,
                         chunk_tokens=4)
    ok &= check_ep_train(cfg, batch=8, seq=32)
    print(f"[selftest] {'ALL OK' if ok else 'FAILURES'}")
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
