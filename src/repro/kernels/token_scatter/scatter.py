"""Pallas TPU kernel: row gather — the "Kernel Scatter" pack stage (§IV-A).

Packing tokens into per-destination contiguous send buffers is a permutation,
so on TPU we express it as a *gather*: the output buffer is written in order
while the input row index comes from a scalar-prefetched index vector (the
same sorted-by-destination order the dispatcher computes).  Using the index
inside the BlockSpec ``index_map`` means the DMA engine fetches exactly the
needed row per grid step — the Pallas/TPU analogue of NCCL's kernel-driven
scatter thread blocks.

Block layout: one (1, D) row per grid step in VMEM; the per-row validity
mask rides as a (1, 1) block multiplied in-kernel (invalid rows fetch row 0
and are zeroed).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _kernel(idx_ref, x_ref, mask_ref, o_ref):
    o_ref[...] = x_ref[...] * mask_ref[0, 0].astype(x_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def token_gather(x: jnp.ndarray, idx: jnp.ndarray, *, interpret: bool | None = None):
    """out[i] = x[idx[i]] (idx < 0 -> zeros).  x: [N, D], idx: [M] int32."""
    n, d = x.shape
    m = idx.shape[0]
    safe = jnp.clip(idx, 0, n - 1).astype(jnp.int32)
    mask = (idx >= 0).astype(x.dtype).reshape(m, 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m,),
        in_specs=[
            pl.BlockSpec((1, d), lambda i, idx_ref: (idx_ref[i], 0)),
            pl.BlockSpec((1, 1), lambda i, idx_ref: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, d), lambda i, idx_ref: (i, 0)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, d), x.dtype),
        interpret=resolve_interpret(interpret),
    )(safe, x, mask)
