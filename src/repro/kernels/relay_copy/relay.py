"""Pallas TPU kernel: double-buffered staged chunk relay (§IV-C analogue).

The paper's relay GPUs stream data through small P2P staging buffers,
overlapping receive of chunk j+1 with forward of chunk j (counter-based
flow control).  On TPU the inter-chip movement itself is a ppermute in the
scheduled dataplane; what remains kernel-shaped is the *staging discipline*:
move a large buffer through a small VMEM window, chunk by chunk, with two
slots alternating so the inbound DMA of the next chunk overlaps the
outbound store of the current one.

The staging-slot schedule is runtime **data**, not a trace-time constant
(ROADMAP item 2, the CUDA-graphs idiom of arxiv 2604.22228): the slot for
each grid step is read out of a scalar-prefetched ``slot_map`` array, so
a swapped plan re-targets relay slots without recompiling the kernel —
``relay_copy`` traces once per geometry and every slot schedule reuses
that executable.  The plan owns slot assignment; baking ``program_id % 2``
into the jaxpr (the previous revision) froze one schedule per trace and
is exactly the PLAN_DEPENDENT hazard ``repro.analysis``'s
``retrace-provenance`` rule now rejects.

Pallas double-buffers the HBM->VMEM block fetches automatically; the
explicit scratch models the relay's fixed-size P2P buffer pool
(10 MB/thread-block in the paper's setup) and is what a fused relay
(recv-compute-send) kernel would build on.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

N_SLOTS = 2


def _kernel(slot_ref, x_ref, o_ref, buf):
    i = pl.program_id(0)
    slot = slot_ref[i]              # runtime slot target, not a constant
    buf[slot] = x_ref[...]          # "receive" into the staging slot
    o_ref[...] = buf[slot]          # "forward" out of the staging slot


def parity_slot_map(n_chunks: int) -> jnp.ndarray:
    """The default double-buffer schedule: slot = chunk parity."""
    return jnp.arange(n_chunks, dtype=jnp.int32) % N_SLOTS


@functools.partial(jax.jit, static_argnames=("block_chunk", "interpret"))
def relay_copy(
    x: jnp.ndarray,
    slot_map: jnp.ndarray | None = None,
    *,
    block_chunk: int = 256,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Identity copy of [N, D] through a 2-slot VMEM staging pipeline.

    ``slot_map`` maps grid step -> staging slot (default: parity).  It is
    scalar-prefetched, so swapping schedules costs a parameter update,
    not a retrace — pinned by ``tests/test_kernels.py`` via
    ``relay_copy._cache_size()``.
    """
    n, d = x.shape
    bc = min(block_chunk, n)
    assert n % bc == 0
    n_chunks = n // bc
    if slot_map is None:
        slot_map = parity_slot_map(n_chunks)
    assert slot_map.shape == (n_chunks,)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_chunks,),
        in_specs=[pl.BlockSpec((bc, d), lambda i, s: (i, 0))],
        out_specs=pl.BlockSpec((bc, d), lambda i, s: (i, 0)),
        scratch_shapes=[pltpu.VMEM((N_SLOTS, bc, d), x.dtype)],
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=resolve_interpret(interpret),
    )(slot_map.astype(jnp.int32), x)
