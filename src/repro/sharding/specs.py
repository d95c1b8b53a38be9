"""Partition-spec rules: FSDP over "data", tensor/expert parallel over "model".

Rules are keyed by parameter leaf name (path suffix) with rank templates;
stacked-layer leading axes get ``None`` prefixes automatically.  Any dim
whose size is smaller than its assigned axis falls back to replication (so
reduced smoke configs and ragged dims never fault).

The "pod" axis never appears in param specs — pods are pure data-parallel
replicas (DESIGN.md §8): parameters are replicated across pods and gradient
all-reduce crosses the DCI, which is the balanced-collective regime the
paper leaves to stock ring/tree (§IV-E).
"""

from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.sharding.context import ParallelContext

# leaf-name -> spec template (rightmost dims; missing leading dims -> None)
_RULES = {
    # embeddings / heads
    "embed": ("*", "model"),
    "lm_head": ("*", "model"),
    "dec_pos": ("*", "model"),
    # attention (col-parallel in, row-parallel out)
    "wq": ("data", "model"),
    "wk": ("data", "model"),
    "wv": ("data", "model"),
    "wo": ("model", "data"),
    "bq": ("model",),
    "bk": ("model",),
    "bv": ("model",),
    # dense mlp
    "wg": ("data", "model"),
    "wu": ("data", "model"),
    "wd": ("model", "data"),
    "w1": ("data", "model"),
    "b1": ("model",),
    "w2": ("model", "data"),
    "b2": ("*",),
    "up": ("data", "model"),
    "down": ("model", "data"),
    # router (small, replicated)
    "router": ("*", "*"),
    # mamba
    "in_proj": ("data", "model"),
    "out_proj": ("model", "data"),
    "conv_w": ("*", "model"),
    "conv_b": ("model",),
    "A_log": ("*",),
    "D": ("*",),
    "dt_bias": ("*",),
    "gate_norm": ("model",),
    # xlstm gates
    "wi": ("data", "model"),
    "wf": ("data", "model"),
    "wz": ("data", "model"),
    "wo_gate": ("data", "model"),
    "wg_x": ("data", "model"),
    "bi": ("*",),
    "bf": ("*",),
}

# MoE expert tensors: leading expert dim -> model axis (expert parallelism).
_MOE_EXPERT_LEAVES = {"wg", "wu", "wd"}


def _axis_size(ctx: ParallelContext, axis: str) -> int:
    if ctx.mesh is None:
        return 1
    return dict(zip(ctx.mesh.axis_names, ctx.mesh.devices.shape))[axis]


def spec_for_path(path: Tuple, leaf, ctx: ParallelContext) -> P:
    names = [getattr(k, "key", getattr(k, "idx", None)) for k in path]
    leaf_name = str(names[-1])
    shape = leaf.shape
    rank = len(shape)

    template = _RULES.get(leaf_name)
    if template is None:
        return P()  # norms, scalars, unknown leaves -> replicate

    # MoE experts: [L, E, D, F]-shaped leaves (layer-stacked + expert dim)
    is_expert = (
        leaf_name in _MOE_EXPERT_LEAVES
        and any(str(n) == "blocks" for n in names)
        and rank - len(template) >= 2
    )
    if is_expert:
        # [L, E, ...]: expert dim gets the model axis, inner dims get fsdp
        inner = ["data" if i == 0 else None for i in range(len(template))]
        spec = [None] * (rank - len(template) - 1) + ["model"] + inner
    else:
        spec = [None] * (rank - len(template)) + [
            None if a == "*" else a for a in template
        ]

    # drop axes that don't divide the dim exactly (jit enforces divisibility)
    out = []
    for dim, axis in zip(shape, spec):
        if axis is None or dim % _axis_size(ctx, axis) != 0:
            out.append(None)
        else:
            out.append(axis)
    return P(*out)


def build_param_specs(params, ctx: ParallelContext):
    """Pytree of PartitionSpec matching ``params``."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: spec_for_path(path, leaf, ctx), params
    )


def build_param_shardings(params, ctx: ParallelContext):
    specs = build_param_specs(params, ctx)
    return jax.tree.map(lambda s: NamedSharding(ctx.mesh, s), specs)

