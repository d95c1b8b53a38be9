"""NIMBLE dataplane — scheduled multi-path All-to-Allv under shard_map.

The executable counterpart of the paper's "Kernel Scatter & Buffer Pipeline"
(§IV-C/D), adapted to TPU/XLA SPMD:

  * the *structure* (slots, rounds, permutations) is static — built once from
    the topology by ``schedule.build_schedule``;
  * the *flow amounts* are dynamic — each invocation all-gathers the live
    per-destination chunk counts (the demand matrix), runs the jittable MWU
    planner identically on every device (endpoint-driven: no coordinator),
    and fills slots accordingly;
  * each round is one ``lax.ppermute`` per hop-permutation, moving only the
    slot subset whose path uses that hop; relay chunks live in the same flat
    state array, so a device forwards by construction (the analogue of the
    paper's peer-exclusive channels + P2P staging buffers);
  * per-destination reassembly (ordering, §IV "reassembly queues") falls out
    of the deterministic slot -> chunk index mapping that both sender and
    receiver compute from the replicated plan.

Also provides the two baselines of §II-B over the *same* slot machinery
(``mode="direct"`` = NCCL/PXN static least-hop; ``mode="stripe"`` = UCX-style
even multirail striping), plus ``baseline_all_to_all`` (stock XLA).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .cost import CostModel
from .planner import PlannerConfig, plan_flows, plan_flows_batch, quantize_chunks
from .schedule import (
    CommSchedule,
    PlannerTables,
    build_planner_tables,
    build_schedule,
    enumerate_relations,
)
from .topology import Topology

#: trace scopes of the stages this module owns (see ``models/moe.py``)
PLAN = "nimble.plan"
ROUNDS = "nimble.rounds"
REASSEMBLE = "nimble.reassemble"


def rel_id_of(m: int, dq: int, G: int) -> int:
    """rel enumeration order: m-major, (0,0) skipped."""
    return m * G + dq - 1


def build_rel_of_pair(n: int, G: int) -> np.ndarray:
    """[n, n] rel id for every ordered pair (-1 on the diagonal)."""
    NG = n // G
    out = np.full((n, n), -1, dtype=np.int32)
    for s in range(n):
        g, p = divmod(s, G)
        for d in range(n):
            if s == d:
                continue
            gd, q = divmod(d, G)
            m = (gd - g) % NG
            dq = (q - p) % G
            out[s, d] = rel_id_of(m, dq, G)
    return out


class NimbleAllToAll:
    """Callable all-to-allv over one mesh axis with execution-time planning.

    Use inside ``shard_map``:  ``y, recv = comm(x, send_chunks)`` where
    ``x: [n, C, chunk_elems]`` are padded per-destination send buffers and
    ``send_chunks: [n] int32`` the live chunk counts.  ``y`` has the same
    layout indexed by source.
    """

    def __init__(
        self,
        axis_name: str,
        n_devices: int,
        group_size: int = 4,
        *,
        max_chunks: int,
        chunk_bytes: float,
        alt_frac: float = 0.5,
        planner_cfg: Optional[PlannerConfig] = None,
        cost_model: Optional[CostModel] = None,
        mode: str = "nimble",  # nimble | direct | stripe
        topo: Optional[Topology] = None,
    ):
        if mode not in ("nimble", "direct", "stripe"):
            raise ValueError(f"unknown mode {mode!r}")
        self.axis_name = axis_name
        self.mode = mode
        # ``topo`` lets a Session (or any caller with a non-default fabric:
        # custom caps, pods, degraded links) supply the exact Topology the
        # planner should price; geometry must match the dataplane axis
        if topo is not None:
            if (topo.n_devices, topo.group_size) != (n_devices, group_size):
                raise ValueError(
                    f"topology geometry ({topo.n_devices}, "
                    f"{topo.group_size}) != dataplane geometry "
                    f"({n_devices}, {group_size})"
                )
            self.topo = topo
        else:
            self.topo = Topology(n_devices, group_size)
        # direct (NCCL/PXN-like) routes everything on k=0, so it provisions
        # no alternate slots: slots it never fills would still be moved every
        # round, charging the static baseline NIMBLE's wire padding
        if mode == "direct":
            alt_frac = 0.0
        self.sched: CommSchedule = build_schedule(self.topo, max_chunks, alt_frac)
        self.tables: PlannerTables = build_planner_tables(self.topo, cost_model)
        self.cfg = planner_cfg or PlannerConfig(chunk_bytes=chunk_bytes)
        if self.cfg.chunk_bytes != chunk_bytes:
            self.cfg = dataclasses.replace(self.cfg, chunk_bytes=chunk_bytes)
        self.rel_of_pair = build_rel_of_pair(n_devices, group_size)
        # optional execution-time telemetry sink (runtime.LinkTelemetry):
        # host-driven plan_batch calls harvest planned resource loads into it
        self.telemetry = None

        n, G = n_devices, group_size
        rels = self.sched.rels
        self._rel_m = np.array([r.m for r in rels])
        self._rel_dq = np.array([r.dq for r in rels])
        self.n_rel = len(rels)
        self.K = self.sched.K
        self.C = max_chunks

        # §Perf C2: static segment layout.  Slots are ordered by (rel, k,
        # pos), so every (rel, k) run is contiguous; rounds move whole
        # segments via slice+concat+ppermute instead of fancy gather +
        # full-state scatter (whose autodiff re-reads the full slot state
        # per round — the dominant memory-term component on the MoE pair).
        sr, sk = self.sched.slot_rel, self.sched.slot_k
        segs = []                                    # (rel, k, start, end)
        start = 0
        for i in range(1, len(sr) + 1):
            if i == len(sr) or (sr[i], sk[i]) != (sr[start], sk[start]):
                segs.append((int(sr[start]), int(sk[start]), start, i))
                start = i
        self._segments = segs
        # per round: hop -> ordered list of segment ids
        self._round_groups = []
        for rnd in self.sched.rounds:
            sel_of_hop = {hop: set(sel.tolist()) for hop, sel in rnd}
            groups = {}
            for hop, slot_set in sel_of_hop.items():
                ids = [si for si, (_, _, s, e) in enumerate(segs)
                       if s in slot_set]
                groups[hop] = ids
            self._round_groups.append(groups)

    @classmethod
    def from_session(
        cls,
        session,
        axis_name: str,
        *,
        max_chunks: int,
        chunk_bytes: float,
        alt_frac: float = 0.5,
        mode: str = "nimble",
        planner_cfg: Optional[PlannerConfig] = None,
    ) -> "NimbleAllToAll":
        """Session-wired endpoint (DESIGN.md §5).

        Topology, cost model, and planner defaults come from the session
        (duck-typed: ``.topo``, ``.cost_model``, ``.spec.planner``,
        ``.runtime`` — this module never imports ``repro.api``); when the
        session runs an orchestration runtime, the endpoint's telemetry is
        attached so host-driven ``plan_batch`` calls feed its monitor
        stage.  With an all-default session this is constructor-equivalent
        to hand-wiring ``NimbleAllToAll(...)`` — bit-identical plans.
        """
        topo = session.topo
        comm = cls(
            axis_name,
            topo.n_devices,
            topo.group_size,
            max_chunks=max_chunks,
            chunk_bytes=chunk_bytes,
            alt_frac=alt_frac,
            planner_cfg=(
                planner_cfg if planner_cfg is not None else session.spec.planner
            ),
            cost_model=session.cost_model,
            mode=mode,
            topo=topo,
        )
        runtime = getattr(session, "runtime", None)
        if runtime is not None:
            comm.attach_telemetry(runtime.telemetry)
        return comm

    # -- plan -------------------------------------------------------------------
    def _plan(self, demand_chunks: jnp.ndarray) -> jnp.ndarray:
        """[n, n] chunk demand -> [n, n, K] per-path chunk assignment."""
        n, K = self.topo.n_devices, self.K
        if self.mode == "direct":
            # static least-hop: everything on k=0 (PXN destination-rail path)
            z = jnp.zeros((n, n, K), dtype=jnp.int32)
            return z.at[..., 0].set(demand_chunks.astype(jnp.int32))
        if self.mode == "stripe":
            # UCX-style: even split across candidates, remainder on k=0
            caps = jnp.asarray(self.sched.S, dtype=jnp.int32)[
                jnp.maximum(jnp.asarray(self.rel_of_pair), 0)
            ]  # [n,n,K]
            kvalid = (caps > 0).astype(jnp.int32)
            nk = jnp.maximum(kvalid.sum(-1), 1)
            share = (demand_chunks.astype(jnp.int32)[..., None] // nk[..., None])
            share = jnp.minimum(share * kvalid, caps)
            rem = demand_chunks.astype(jnp.int32) - share.sum(-1)
            return share.at[..., 0].add(rem)
        D = demand_chunks.astype(jnp.float32) * jnp.float32(self.cfg.chunk_bytes)
        flows, _ = plan_flows(D, self.tables, self.cfg, vary_axis=self.axis_name)
        return quantize_chunks(
            flows,
            demand_chunks.astype(jnp.int32),
            self.sched.S,
            self.rel_of_pair,
            self.cfg.chunk_bytes,
        )

    def attach_telemetry(self, sink) -> None:
        """Attach a ``runtime.LinkTelemetry`` (or duck-typed) sink.

        Subsequent host-driven :meth:`plan_batch` calls record each planned
        demand matrix and its per-resource loads via ``sink.record_loads``
        (self-numbered windows), feeding the orchestration runtime's
        monitor stage from real plan executions without touching the jitted
        dataplane path.  Only ``mode="nimble"`` produces a load vector —
        the static baselines plan elementwise and record nothing.
        """
        self.telemetry = sink

    def plan_batch(self, demand_chunks: jnp.ndarray) -> jnp.ndarray:
        """Plan a batch of demand matrices in one call: [B, n, n] -> [B, n, n, K].

        Multi-tenant / per-layer entry point (host-driven, outside
        shard_map): every batch entry is planned by the vmapped MWU against
        the same cached incidence tables and quantized to slot capacities.
        Only meaningful for ``mode="nimble"``; static modes broadcast their
        elementwise rules over the batch via the same ``_plan`` math.
        """
        if self.mode != "nimble":
            return jax.vmap(self._plan)(demand_chunks)
        D = demand_chunks.astype(jnp.float32) * jnp.float32(self.cfg.chunk_bytes)
        flows, loads = plan_flows_batch(D, self.tables, self.cfg)
        if self.telemetry is not None and not isinstance(D, jax.core.Tracer):
            # strip the trailing dummy resource the planner pads with
            loads_np = np.asarray(loads)[:, :-1]
            D_np = np.asarray(D)
            for b in range(loads_np.shape[0]):
                self.telemetry.record_loads(None, loads_np[b],
                                            pair_bytes=D_np[b])
        return jax.vmap(
            lambda f, dc: quantize_chunks(
                f, dc, self.sched.S, self.rel_of_pair, self.cfg.chunk_bytes
            )
        )(flows, demand_chunks.astype(jnp.int32))

    # -- execution ----------------------------------------------------------------
    def plan_from_counts(self, send_chunks: jnp.ndarray) -> jnp.ndarray:
        """All-gather live counts and plan (endpoint-driven, replicated)."""
        with jax.named_scope(PLAN):
            D = jax.lax.all_gather(send_chunks, self.axis_name)   # [n, n]
            return self._plan(D)                              # [n, n, K]

    def __call__(
        self, x: jnp.ndarray, send_chunks: jnp.ndarray
    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """x: [n, C, E] per-destination buffers; send_chunks: [n] int32."""
        chunks = self.plan_from_counts(send_chunks)
        y = self.execute(x, chunks)
        recv_chunks = chunks[:, jax.lax.axis_index(self.axis_name)].sum(-1)
        recv_chunks = recv_chunks.astype(send_chunks.dtype)
        me = jax.lax.axis_index(self.axis_name)
        recv_chunks = recv_chunks.at[me].set(send_chunks[me])
        return y, recv_chunks

    def execute(self, x: jnp.ndarray, chunks: jnp.ndarray) -> jnp.ndarray:
        """Move data according to a (replicated) per-path chunk plan."""
        n = self.topo.n_devices
        G, NG = self.topo.group_size, self.topo.n_groups
        sched = self.sched
        axis = self.axis_name

        with jax.named_scope(ROUNDS):
            me = jax.lax.axis_index(axis)
            g, p = me // G, me % G
            rel_m = jnp.asarray(self._rel_m)
            rel_dq = jnp.asarray(self._rel_dq)
            dest = ((g + rel_m) % NG) * G + (p + rel_dq) % G  # [n_rel]
            src = ((g - rel_m) % NG) * G + (p - rel_dq) % G   # [n_rel]

            my_rel_chunks = chunks[me][dest]                  # [n_rel, K]
            start = jnp.cumsum(my_rel_chunks, axis=-1) - my_rel_chunks

            slot_rel = jnp.asarray(sched.slot_rel)
            slot_k = jnp.asarray(sched.slot_k)
            slot_pos = jnp.asarray(sched.slot_pos)

            chunk_idx = start[slot_rel, slot_k] + slot_pos    # [n_slots]
            valid = slot_pos < my_rel_chunks[slot_rel, slot_k]
            x_rel = x[dest]                                   # [n_rel, C, E]
            state = (
                x_rel[slot_rel, jnp.clip(chunk_idx, 0, self.C - 1)]
                * valid[:, None].astype(x.dtype)
            )                                                 # [n_slots, E]

            # three normalized rounds of uniform hop permutations (§Perf C2:
            # per-(rel,k) segments move as contiguous slices — no full-state
            # gather/scatter per round)
            segs = self._segments
            state_segs = [
                jax.lax.slice_in_dim(state, s, e, axis=0)
                for (_, _, s, e) in segs
            ]
            for t in range(len(sched.rounds)):
                for hop, seg_ids in sorted(self._round_groups[t].items()):
                    sub = jnp.concatenate([state_segs[i] for i in seg_ids],
                                          axis=0)
                    sub = jax.lax.ppermute(sub, axis, sched.perm_pairs(hop))
                    off = 0
                    for i in seg_ids:
                        ln = segs[i][3] - segs[i][2]
                        state_segs[i] = jax.lax.slice_in_dim(
                            sub, off, off + ln, axis=0)
                        off += ln
            state = jnp.concatenate(state_segs, axis=0)
        with jax.named_scope(REASSEMBLE):
            # per-destination reassembly using the source's (replicated) plan
            src_rel_chunks = chunks[src, me]                  # [n_rel, K]
            rstart = jnp.cumsum(src_rel_chunks, axis=-1) - src_rel_chunks
            recv_idx = rstart[slot_rel, slot_k] + slot_pos
            rvalid = slot_pos < src_rel_chunks[slot_rel, slot_k]
            y_rel = jnp.zeros((self.n_rel, self.C, x.shape[-1]), dtype=x.dtype)
            y_rel = y_rel.at[slot_rel, jnp.clip(recv_idx, 0, self.C - 1)].add(
                state * rvalid[:, None].astype(x.dtype)
            )
            y = jnp.zeros_like(x).at[src].set(y_rel)
            y = y.at[me].set(x[me])                           # local traffic
            return y


def baseline_all_to_all(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """Stock XLA all-to-all over the same [n, C, E] layout (inside shard_map)."""
    return jax.lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0, tiled=True)


# -- host-side oracle -----------------------------------------------------------


def ref_all_to_allv(
    x_all: np.ndarray, counts_all: np.ndarray
) -> Tuple[np.ndarray, np.ndarray]:
    """Pure-numpy oracle: x_all [n, n, C, E], counts_all [n, n] -> (y, recv).

    y[d, s, c] = x_all[s, d, c] for c < counts_all[s, d], else 0.
    """
    n, _, C, E = x_all.shape
    y = np.zeros_like(x_all)
    recv = np.zeros((n, n), dtype=counts_all.dtype)
    for s in range(n):
        for d in range(n):
            c = int(counts_all[s, d])
            y[d, s, :c] = x_all[s, d, :c]
            recv[d, s] = c
    return y, recv
