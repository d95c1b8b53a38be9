"""Execution-time planner — jittable multiplicative-weights MCF.

This is Algorithm 1 restructured for the TPU runtime: a **fixed-iteration,
vectorized** MWU loop in pure ``jnp`` so it can live inside a jitted train /
serve step and re-plan from the *live* demand matrix every invocation with
zero host round-trips and zero recompilation.

Differences from the faithful host implementation (``mcf.solve_mwu``),
recorded per DESIGN.md §2:

  * all pairs route a λ-fraction **simultaneously** each iteration (parallel
    MWU) instead of sequentially — required for vectorization; with the same
    geometric demand decay the fixed point is the same min-max balance, and
    tests cross-check the two implementations;
  * iteration count ``T`` is static (compile-time); residual demand after
    T iterations is dumped on the k=0 (least-hop) path, which is also the
    correct degenerate behaviour for small messages (size-threshold policy).

The planner itself is a few thousand FLOPs on a [n², K] problem — Table I of
the paper measures the GPU version at ~0.03–0.05 ms; ours is benchmarked in
``benchmarks/bench_algo_overhead.py``.

Data layout: all path pricing/charging runs against the per-pair candidate
rows of the shared :class:`~repro.core.incidence.PathIncidence` (cached per
topology fingerprint, DESIGN.md §2).  The gather/scatter indexing is
precomputed once per table build, so the ``fori_loop`` body is pure dense
ops: one gather of live costs, a masked max, an argmin, a one-hot flow
update, and a segment-sum load accumulation.  ``plan_flows_batch`` /
``plan_chunks_batch_jit`` vmap the same loop over a batch of demand
matrices for multi-tenant planning.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .cost import CostModel
from .schedule import PlannerTables

_BIG = 1e30
# price tiers above any real path cost: a small-message-gated relay path is
# preferable to a *down* path, which is preferable to K-padding.  On a
# healthy fabric nothing is down, and the tiering reduces to the original
# single-_BIG mask (argmin tie-break picks k=0), so plans are unchanged.
_BIG_DOWN = 1e32
_BIG_INVALID = 1e34
#: paths whose bottleneck capacity falls below this are treated as down
#: (see topology.DOWN_CAP); no real interconnect link is below 1 B/s
_DEAD_PATH_CAP = 1.0


@dataclasses.dataclass(frozen=True)
class PlannerConfig:
    lam: float = 0.25            # λ — fraction of residual routed per visit
    n_iters: int = 24            # T — static MWU iterations
    chunk_bytes: float = float(1 << 20)  # ε — quantization granularity
    split_threshold: float = float(1 << 20)  # paper: <=1 MB never splits
    hysteresis: float = 0.5


def planner_provenance(cfg: PlannerConfig) -> dict:
    """Solver-parameter fingerprint recorded in plan-provenance records
    and ``solve`` trace spans (DESIGN.md §11).

    ``engine`` identifies the planning discipline — today always the MWU
    sweep; the ROADMAP's ``PlanEngine`` zoo (BvN / FAST schedulers) will
    key audit records on it.
    """
    return {
        "engine": "mwu",
        "lam": float(cfg.lam),
        "n_iters": int(cfg.n_iters),
        "chunk_bytes": float(cfg.chunk_bytes),
        "hysteresis": float(cfg.hysteresis),
    }


def plan_flows(
    demand_bytes: jnp.ndarray,        # [n, n] float32, zero diagonal
    tables: PlannerTables,
    cfg: PlannerConfig = PlannerConfig(),
    prev_loads: jnp.ndarray | None = None,
    ext_loads: jnp.ndarray | None = None,  # [n_resources] external prices
    vary_axis: str | None = None,     # set when called inside shard_map
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (flows [n, n, K] bytes, resource loads [n_resources]).

    ``prev_loads`` is this job's previous load vector, folded through the
    EMA (``cfg.hysteresis``) into the returned loads.  ``ext_loads`` is
    other tenants' committed load (the fabric arbiter's exported prices):
    it raises resource costs during the solve but is **not** carried into
    the returned loads, and is never EMA-smoothed.
    """
    n, K = tables.n, tables.K
    caps = jnp.asarray(tables.caps, dtype=jnp.float32)
    # All gather/scatter indexing is precomputed per pair on the incidence
    # tables (DESIGN.md §2.3) — the loop body below is pure dense ops.
    pcand = tables.pair_candidates
    cand_rids = jnp.asarray(pcand.rids)                # [n*n, K, MC]
    cand_mult = jnp.asarray(pcand.mult)                # [n*n, K, MC]
    cand_mask = jnp.asarray(pcand.mask, dtype=jnp.float32)
    cand_pen = jnp.asarray(pcand.penalty)              # [n*n, K]

    D = demand_bytes.astype(jnp.float32).reshape(-1)   # [n*n]
    msg = D                                            # per-pair message size
    eps = jnp.float32(cfg.chunk_bytes)
    lam = jnp.float32(cfg.lam)

    loads0 = jnp.zeros(tables.n_resources, dtype=jnp.float32)
    if prev_loads is not None:
        loads0 = jnp.float32(cfg.hysteresis) * prev_loads
    # trace-time branch: ext_loads=None keeps the cost expression (and the
    # compiled program) bit-identical to the unarbitrated planner
    ext = None if ext_loads is None else ext_loads.astype(jnp.float32)

    # static price-out tiers: relay paths for small messages (_BIG), down
    # paths — bottleneck capacity below _DEAD_PATH_CAP after a link event —
    # (_BIG_DOWN), K-padding (_BIG_INVALID)
    small = jnp.asarray(pcand.relay) & (msg[:, None] <= cfg.split_threshold)
    down_np = pcand.valid & (pcand.min_cap < _DEAD_PATH_CAP)  # [n*n, K]
    invalid = jnp.asarray(~pcand.valid)
    down = jnp.asarray(down_np)

    def body(_, state):
        flows, res, loads = state
        priced = loads if ext is None else loads + ext
        costs = priced / caps                                       # [R]
        pcK = (
            jnp.max(costs[cand_rids] * cand_mask, axis=-1) + cand_pen
        )                                                           # [n*n, K]
        pcK = jnp.where(small, _BIG, pcK)
        pcK = jnp.where(down, _BIG_DOWN, pcK)
        pcK = jnp.where(invalid, _BIG_INVALID, pcK)
        best_k = jnp.argmin(pcK, axis=-1)                           # [n*n]
        # Algorithm 1 lines 24-28: quantized λ-fraction of the residual
        f = jnp.where(
            res < eps, res, jnp.floor(res * lam / eps) * eps
        )
        f = jnp.where((res >= eps) & (f <= 0), jnp.minimum(eps, res), f)
        f = jnp.maximum(f, 0.0)
        onehot = jax.nn.one_hot(best_k, K, dtype=flows.dtype)       # [n*n, K]
        flows = flows + f[:, None] * onehot
        sel = best_k[:, None, None]
        rids = jnp.take_along_axis(cand_rids, sel, axis=1)[:, 0]    # [n*n, MC]
        mult = jnp.take_along_axis(cand_mult, sel, axis=1)[:, 0]    # [n*n, MC]
        loads = loads + jax.ops.segment_sum(
            (f[:, None] * mult).reshape(-1),
            rids.reshape(-1),
            num_segments=tables.n_resources,
        )
        res = res - f
        return flows, res, loads

    flows = jnp.zeros((n * n, K), dtype=jnp.float32)
    if vary_axis is not None:
        # inside shard_map the demand is axis-varying; the loop carries must
        # match or lax.fori_loop rejects the body signature.
        flows = jax.lax.pcast(flows, vary_axis, to="varying")
        loads0 = jax.lax.pcast(loads0, vary_axis, to="varying")
    flows, res, loads = jax.lax.fori_loop(
        0, cfg.n_iters, body, (flows, D, loads0)
    )
    # residual after T iterations -> least-hop *alive* path (k=0 on a
    # healthy fabric; the first non-down candidate after a link event)
    alive = pcand.valid & ~down_np
    k_dump = np.where(alive.any(-1), np.argmax(alive, axis=-1), 0)
    if (k_dump == 0).all():
        flows = flows.at[:, 0].add(res)
    else:
        flows = flows.at[jnp.arange(n * n), jnp.asarray(k_dump)].add(res)
    return flows.reshape(n, n, K), loads


def plan_flows_batch(
    demand_bytes: jnp.ndarray,        # [B, n, n]
    tables: PlannerTables,
    cfg: PlannerConfig = PlannerConfig(),
    prev_loads: jnp.ndarray | None = None,  # [B, n_resources] or None
    ext_loads: jnp.ndarray | None = None,   # [B, n_resources] or None
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Plan a batch of demand matrices in one call via ``jax.vmap``.

    Multi-tenant / per-expert entry point: B independent demand matrices
    (tenants, MoE layers, microbatches) are planned against the same cached
    incidence tables in a single jit-compiled vectorized MWU, instead of B
    sequential ``plan_flows`` dispatches.  ``ext_loads`` carries per-entry
    external prices (see :func:`plan_flows`).  Returns ``(flows
    [B, n, n, K], loads [B, n_resources])``.
    """
    if prev_loads is None and ext_loads is None:
        return jax.vmap(lambda d: plan_flows(d, tables, cfg))(demand_bytes)
    if prev_loads is None:
        return jax.vmap(
            lambda d, e: plan_flows(d, tables, cfg, ext_loads=e)
        )(demand_bytes, ext_loads)
    if ext_loads is None:
        return jax.vmap(
            lambda d, p: plan_flows(d, tables, cfg, prev_loads=p)
        )(demand_bytes, prev_loads)
    return jax.vmap(
        lambda d, p, e: plan_flows(d, tables, cfg, prev_loads=p, ext_loads=e)
    )(demand_bytes, prev_loads, ext_loads)


def quantize_chunks(
    flows: jnp.ndarray,        # [n, n, K] bytes
    demand_chunks: jnp.ndarray,  # [n, n] int32 — exact chunk counts
    slot_caps: np.ndarray,     # [n_rel, K] static slot capacities
    rel_of_pair: np.ndarray,   # [n, n] static rel id (-1 on diagonal)
    chunk_bytes: float,
) -> jnp.ndarray:
    """Round flows to integer chunks: alternates floor+clamp, direct absorbs.

    Guarantees sum_k chunks[s,d,k] == demand_chunks[s,d] and
    chunks[s,d,k] <= S[rel(s,d),k], so the dataplane never overflows a slot
    segment (k=0 capacity is C >= any per-destination demand by layout).
    """
    K = flows.shape[-1]
    caps = jnp.asarray(slot_caps, dtype=jnp.int32)[
        jnp.maximum(jnp.asarray(rel_of_pair), 0)
    ]  # [n, n, K]
    remaining = demand_chunks.astype(jnp.int32)
    out = []
    for k in range(K - 1, 0, -1):  # alternates, highest k first
        want = jnp.floor(flows[..., k] / chunk_bytes).astype(jnp.int32)
        got = jnp.minimum(jnp.minimum(want, caps[..., k]), remaining)
        out.append(got)
        remaining = remaining - got
    chunks = jnp.stack([remaining] + out[::-1], axis=-1)  # k=0 absorbs rest
    return chunks


@functools.partial(jax.jit, static_argnums=(1, 2))
def plan_chunks_jit(
    demand_chunks: jnp.ndarray,   # [n, n] int32
    tables: "PlannerTablesHashable",
    cfg: PlannerConfig,
) -> jnp.ndarray:
    """demand (chunks) -> per-path chunk assignment [n, n, K]."""
    t = tables.tables
    D = demand_chunks.astype(jnp.float32) * cfg.chunk_bytes
    flows, _ = plan_flows(D, t, cfg)
    return quantize_chunks(
        flows, demand_chunks, tables.slot_caps, tables.rel_of_pair,
        cfg.chunk_bytes,
    )


@functools.partial(jax.jit, static_argnums=(1, 2))
def plan_chunks_batch_jit(
    demand_chunks: jnp.ndarray,   # [B, n, n] int32
    tables: "PlannerTablesHashable",
    cfg: PlannerConfig,
) -> jnp.ndarray:
    """Batched multi-tenant planning: [B, n, n] -> [B, n, n, K] chunks.

    One jit call plans every tenant/layer demand matrix against the shared
    incidence tables (vectorized MWU under ``vmap``) and quantizes each to
    slot capacities.
    """
    t = tables.tables
    D = demand_chunks.astype(jnp.float32) * cfg.chunk_bytes
    flows, _ = plan_flows_batch(D, t, cfg)
    return jax.vmap(
        lambda f, dc: quantize_chunks(
            f, dc, tables.slot_caps, tables.rel_of_pair, cfg.chunk_bytes
        )
    )(flows, demand_chunks.astype(jnp.int32))


class PlannerTablesHashable:
    """Static wrapper so tables can be a jit static arg (hash by identity)."""

    def __init__(self, tables: PlannerTables, slot_caps: np.ndarray,
                 rel_of_pair: np.ndarray):
        self.tables = tables
        self.slot_caps = slot_caps
        self.rel_of_pair = rel_of_pair

    def __hash__(self) -> int:
        return id(self)

    def __eq__(self, other) -> bool:
        return self is other
