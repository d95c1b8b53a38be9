"""Expert-parallel dispatch / combine over the NIMBLE dataplane (paper §V-D).

The paper's headline workload: MoE token routing is a skewed All-to-Allv
(dispatch) followed by expert FFN compute and the transposed All-to-Allv
(combine).  This module implements the full endpoint-driven pipeline:

  1. tokens are assigned to experts (top-k gating, done by the model);
  2. assignments are packed into per-destination-device chunk buffers
     ("Kernel Scatter": a stable sort by destination and a jnp scatter);
  3. the live demand matrix is planned + executed by
     :class:`~repro.core.dataplane.NimbleAllToAll` — tokens ride a bf16/f32
     payload, the per-token expert id rides a tiny f32 sideband on the SAME
     plan (so routing stays consistent);
  4. expert FFN runs on received tokens (``grouped_ffn`` kernel / ref);
  5. outputs return in-place through the transposed plan and are
     scatter-combined into the original token order with gate weights.

Ordering/determinism: chunk -> slot maps are derived from the replicated plan
on both sides (paper's per-destination reassembly queues).  Capacity: the
static per-destination buffer implements a capacity factor; overflow tokens
are dropped with a counter (the paper's no-drop deployments correspond to a
large enough factor, see configs).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .cost import CostModel
from .dataplane import NimbleAllToAll
from .planner import PlannerConfig
from .topology import Topology

#: trace scopes of the stages this module owns (see ``models/moe.py``)
PACK = "nimble.pack"
COMBINE = "nimble.combine"


@dataclasses.dataclass
class MoECommConfig:
    n_devices: int                 # EP group size (model-axis)
    n_experts: int
    d_model: int
    chunk_tokens: int = 16         # ε in tokens — planner chunk granularity
    capacity_factor: float = 2.0   # per-destination buffer vs uniform share
    group_size: int = 4            # chips per "node" on the NIMBLE axis
    alt_frac: float = 0.5
    mode: str = "nimble"           # nimble | direct | stripe
    payload_dtype: jnp.dtype = jnp.float32

    @property
    def experts_per_device(self) -> int:
        assert self.n_experts % self.n_devices == 0
        return self.n_experts // self.n_devices


class MoEDispatcher:
    """Stateless (per-shape) dispatch/combine helper.  Use inside shard_map.

    ``runtime`` optionally routes dispatch planning through an
    :class:`~repro.runtime.controller.OrchestrationRuntime`: host-driven
    batched plans feed its telemetry/estimator (via the dataplane's
    telemetry sink and ``runtime.observe_dispatch``), so drifting expert
    popularity shows up in the runtime's replan loop.  The jitted
    per-invocation dispatch path is unchanged — the runtime observes from
    the host side only.
    """

    def __init__(self, axis_name: str, cfg: MoECommConfig,
                 planner_cfg: Optional[PlannerConfig] = None,
                 runtime=None,
                 cost_model: Optional[CostModel] = None,
                 topo: Optional[Topology] = None):
        self.axis = axis_name
        self.cfg = cfg
        self._comms = {}
        self._planner_cfg = planner_cfg
        self.runtime = runtime
        # non-default fabric description for the underlying dataplane
        # endpoints (Session-supplied; None keeps the historical behavior
        # of deriving a default Topology from the comm geometry)
        self._cost_model = cost_model
        self._topo = topo

    @classmethod
    def from_session(cls, session, axis_name: str, cfg: MoECommConfig,
                     planner_cfg: Optional[PlannerConfig] = None
                     ) -> "MoEDispatcher":
        """Session-wired dispatcher (DESIGN.md §5).

        The session (duck-typed — this module never imports ``repro.api``)
        supplies the fabric topology, cost model, planner defaults, and —
        when it runs one — the orchestration runtime, so expert-parallel
        dispatch demand feeds the runtime's telemetry/estimator without
        any per-application ``attach_telemetry`` wiring.  The comm
        geometry in ``cfg`` must match the session's fabric.
        """
        topo = session.topo
        if (cfg.n_devices, cfg.group_size) != (topo.n_devices,
                                               topo.group_size):
            raise ValueError(
                f"MoE comm geometry ({cfg.n_devices}, {cfg.group_size}) != "
                f"session fabric ({topo.n_devices}, {topo.group_size})"
            )
        return cls(
            axis_name,
            cfg,
            planner_cfg=(
                planner_cfg if planner_cfg is not None else session.spec.planner
            ),
            runtime=getattr(session, "runtime", None),
            cost_model=session.cost_model,
            topo=topo,
        )

    # -- static geometry -------------------------------------------------------
    def capacity_tokens(self, n_assign: int) -> int:
        cfg = self.cfg
        per_dest = int(np.ceil(n_assign / cfg.n_devices * cfg.capacity_factor))
        ct = cfg.chunk_tokens
        return int(np.ceil(per_dest / ct)) * ct

    def _comm(self, n_chunks: int, elems: int) -> NimbleAllToAll:
        key = (n_chunks, elems)
        if key not in self._comms:
            chunk_bytes = float(
                self.cfg.chunk_tokens * self.cfg.d_model
                * jnp.dtype(self.cfg.payload_dtype).itemsize
            )
            comm = NimbleAllToAll(
                self.axis,
                self.cfg.n_devices,
                self.cfg.group_size,
                max_chunks=n_chunks,
                chunk_bytes=chunk_bytes,
                alt_frac=self.cfg.alt_frac,
                planner_cfg=self._planner_cfg,
                cost_model=self._cost_model,
                mode=self.cfg.mode,
                topo=self._topo,
            )
            if self.runtime is not None:
                comm.attach_telemetry(self.runtime.telemetry)
            self._comms[key] = comm
        return self._comms[key]

    def plan_batched(
        self, demand_chunks: jnp.ndarray, n_assign: int
    ) -> jnp.ndarray:
        """Plan B dispatch rounds in one jit call: [B, n, n] -> [B, n, n, K].

        Multi-tenant / pipelined entry point: the demand matrices of
        several MoE layers (or microbatches, or co-located tenants) are
        planned together by the vmapped MWU over the shared cached
        incidence tables, instead of B sequential planner dispatches.
        ``n_assign`` is the per-round assignment count (T*k), as in
        :meth:`dispatch`, and fixes the chunk capacity C.
        """
        cfg = self.cfg
        cap_tok = self.capacity_tokens(n_assign)
        C = cap_tok // cfg.chunk_tokens
        comm = self._comm(C, cfg.chunk_tokens * cfg.d_model)
        if self.runtime is not None and not isinstance(
            demand_chunks, jax.core.Tracer
        ):
            # feed the dispatch demand into the runtime's estimator so MoE
            # expert-popularity drift participates in its replan decisions;
            # one update per batch entry, matching the per-window records
            # the telemetry sink takes in plan_batch
            D = np.asarray(demand_chunks, dtype=np.float64) * float(
                comm.cfg.chunk_bytes
            )
            for b in range(D.shape[0]):
                self.runtime.estimator.update(D[b])
        return comm.plan_batch(demand_chunks)

    # -- dispatch ----------------------------------------------------------------
    def dispatch(
        self,
        tokens: jnp.ndarray,     # [T, d] local tokens
        expert_idx: jnp.ndarray,  # [T, k] int32 global expert ids
        token_valid: Optional[jnp.ndarray] = None,  # [T] bool ownership mask
    ):
        """Route token copies to expert-owning devices.

        Returns (recv_tokens [n, C, ct, d], recv_expert [n, C, ct] local ids
        with -1 padding, state) where ``state`` carries everything combine
        needs (plan, slot maps, dropped-token mask).
        """
        cfg = self.cfg
        n, ct, d = cfg.n_devices, cfg.chunk_tokens, cfg.d_model
        T, k = expert_idx.shape
        A = T * k
        cap_tok = self.capacity_tokens(A)
        C = cap_tok // ct
        comm = self._comm(C, ct * d)

        with jax.named_scope(PACK):
            dest = (expert_idx // cfg.experts_per_device).reshape(A)  # [A]
            if token_valid is not None:
                # unowned tokens (replicated-token mode, DESIGN.md §8):
                # route to a sentinel so they never enter any send buffer.
                avalid = jnp.repeat(token_valid, k)
                dest = jnp.where(avalid, dest, n)                  # sentinel
            # stable pack: position of each assignment within its destination
            order = jnp.argsort(dest, stable=True)                # [A]
            dest_sorted = dest[order]
            counts = jnp.bincount(dest, length=n)                 # tokens/dest
            offsets = jnp.cumsum(counts) - counts
            slot_sorted = (jnp.arange(A)
                           - offsets[jnp.minimum(dest_sorted, n - 1)])
            # kept: within capacity and owned
            kept_sorted = (slot_sorted < cap_tok) & (dest_sorted < n)
            # scatter assignment a=order[r] -> (dest, slot)
            slot = jnp.zeros((A,), jnp.int32).at[order].set(
                slot_sorted.astype(jnp.int32))
            kept = jnp.zeros((A,), bool).at[order].set(kept_sorted)

            tok_flat = jnp.repeat(tokens, k, axis=0)              # [A, d]
            x = jnp.zeros((n, C * ct, d), cfg.payload_dtype)
            x = x.at[dest, jnp.minimum(slot, cap_tok - 1)].add(
                jnp.where(kept[:, None], tok_flat.astype(cfg.payload_dtype), 0)
            )
            e_side = jnp.full((n, C * ct, 1), -1.0, jnp.float32)
            e_side = e_side.at[dest, jnp.minimum(slot, cap_tok - 1), 0].set(
                jnp.where(kept, expert_idx.reshape(A).astype(jnp.float32),
                          -1.0)
            )

            send_chunks = jnp.ceil(
                jnp.minimum(counts, cap_tok) / ct
            ).astype(jnp.int32)                                   # [n]
        plan = comm.plan_from_counts(send_chunks)                 # [n, n, K]

        y = comm.execute(x.reshape(n, C, ct * d), plan)
        e_comm = self._comm(C, ct)  # sideband shares schedule shape
        ey = e_comm.execute(e_side.reshape(n, C, ct), plan)

        me = jax.lax.axis_index(self.axis)
        recv_tokens = y.reshape(n, C, ct, d)
        recv_tokens = recv_tokens.at[me].set(x.reshape(n, C, ct, d)[me])
        e_recv = ey.reshape(n, C, ct)
        e_recv = e_recv.at[me].set(e_side.reshape(n, C, ct)[me])
        # decode sideband: pad slots stay -1 (zeros arriving decode to 0 but
        # only within planned chunk counts; out-of-plan slots were zero-filled
        # -> mark them invalid via the per-source chunk counts)
        recv_chunk_counts = plan[:, me].sum(-1)                   # [n]
        recv_chunk_counts = recv_chunk_counts.at[me].set(send_chunks[me])
        cidx = jnp.arange(C)[None, :]
        chunk_valid = cidx < recv_chunk_counts[:, None]           # [n, C]
        expert_global = jnp.where(
            chunk_valid[..., None], jnp.round(e_recv).astype(jnp.int32), -1
        )
        expert_local = jnp.where(
            expert_global >= 0,
            expert_global - me * cfg.experts_per_device,
            -1,
        )
        # guard: mis-routed ids (shouldn't happen) masked out
        expert_local = jnp.where(
            (expert_local >= 0) & (expert_local < cfg.experts_per_device),
            expert_local,
            -1,
        )
        state = dict(
            plan=plan,
            dest=dest,
            slot=slot,
            kept=kept,
            send_chunks=send_chunks,
            C=C,
            dropped=(~kept).sum(),
        )
        return recv_tokens, expert_local, state

    # -- combine -----------------------------------------------------------------
    def combine(
        self,
        expert_out: jnp.ndarray,   # [n, C, ct, d] outputs in recv layout
        state,
        gate_w: jnp.ndarray,       # [T, k] float gate weights
    ) -> jnp.ndarray:
        """Return expert outputs to token owners and gate-combine: [T, d]."""
        cfg = self.cfg
        n, ct, d = cfg.n_devices, cfg.chunk_tokens, cfg.d_model
        T, k = gate_w.shape
        C = state["C"]
        comm = self._comm(C, ct * d)

        with jax.named_scope(COMBINE):
            # transpose plan: what I received per source is what I send back
            plan_T = jnp.swapaxes(state["plan"], 0, 1)
            y = comm.execute(
                expert_out.reshape(n, C, ct * d).astype(cfg.payload_dtype),
                plan_T,
            )
            me = jax.lax.axis_index(self.axis)
            y = y.reshape(n, C, ct, d)
            y = y.at[me].set(expert_out[me].astype(cfg.payload_dtype))
            # gather each assignment's processed token from (dest, slot)
            flat = y.reshape(n, C * ct, d)
            a_out = flat[state["dest"], jnp.minimum(state["slot"], C * ct - 1)]
            a_out = jnp.where(state["kept"][:, None], a_out, 0)
            w = gate_w.reshape(T * k, 1).astype(a_out.dtype)
            out = (a_out * w).reshape(T, k, d).sum(axis=1)
            return out
