"""Parallelism context threaded through the model zoo.

Carries the mesh + axis roles so model code stays declarative:

  * ``data_axes`` — axes sharding batch/tokens (includes "pod": the pod axis
    is pure data-parallel, DESIGN.md §8);
  * ``model_axis`` — tensor/expert-parallel axis; this is also the NIMBLE
    orchestration axis (the paper's technique rides the EP all-to-all);
  * ``ep_size``/``moe_mode``/``group_size`` — expert-parallel group geometry
    for :class:`repro.core.MoEDispatcher` (group_size chips = one "node").

``ParallelContext(None)`` (default) means single-device execution — used by
CPU smoke tests; the MoE layer then computes experts locally.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ParallelContext:
    mesh: Optional[object] = None          # jax.sharding.Mesh
    data_axes: Tuple[str, ...] = ("data",)
    model_axis: str = "model"
    ep_size: int = 1
    group_size: int = 4
    moe_mode: str = "nimble"               # nimble | direct | stripe
    moe_chunk_tokens: int = 16
    moe_alt_frac: float = 0.5
    param_dtype: jnp.dtype = jnp.float32
    compute_dtype: jnp.dtype = jnp.float32
    remat: bool = False                    # activation checkpoint per block
    # optional repro.api.Session supplying ready-wired MoE dispatchers
    # (cost model, planner config, runtime telemetry); None keeps the
    # historical hand-wired MoEDispatcher construction (DESIGN.md §5)
    session: Optional[object] = None

    @property
    def token_axes(self) -> Tuple[str, ...]:
        """All axes across which flattened tokens are sharded for EP."""
        return tuple(self.data_axes) + (self.model_axis,)


def constrain_tokens(x, ctx: "ParallelContext"):
    """Pin a [B, S, D] activation's batch dim to the data axes.

    XLA's sharding propagation sometimes trades the batch sharding away to
    shard attention heads instead — replicating the FULL global batch per
    device (observed on zamba2's shared-attention block).  A no-op without
    a mesh.
    """
    if ctx.mesh is None:
        return x
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    spec = P(tuple(ctx.data_axes), None, None)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, spec))


SINGLE = ParallelContext()
