"""Pallas TPU kernel: blocked per-expert SwiGLU FFN (megablox-style).

The MoE compute hot-spot.  Tokens arrive sorted by expert and padded so each
(bm)-row block is expert-homogeneous; the block's expert id is scalar-
prefetched and selects the weight slices directly in the BlockSpec
``index_map`` — no gather of full weight matrices into registers.

Grid = (token_blocks, ffn_blocks); the ffn dimension is the innermost
(sequential) axis so the (bm, D) output block accumulates partial
``(act(x·Wg) * (x·Wu)) · Wd`` contributions across F-slices in f32.

VMEM per step is the double-buffered tiles, 2·(bm·D·x + 3·D·bf·w + bm·D·4)
bytes, plus the f32 temporaries.  With f32 weights at the paper's §V-D block
(D=4096) and bf=128 the three weight tiles alone take 2·3·4096·128·4 B =
12 MiB, so the tiles do not fit the compiler's default 16 MiB scoped limit;
the kernel asks for ``VMEM_LIMIT_BYTES`` of v5e's 128 MiB instead.  bf must
be a multiple of 128 (the lane width), bm a multiple of 8.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

#: scoped VMEM the kernel may claim (v5e holds 128 MiB); room for the f32
#: §V-D tiles with the rest left to the compiler
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def _kernel(eid_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref):
    fb = pl.program_id(1)

    @pl.when(fb == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)
    g = jnp.dot(x, wg_ref[0].astype(jnp.float32),
                preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu_ref[0].astype(jnp.float32),
                preferred_element_type=jnp.float32)
    h = jax.nn.silu(g) * u
    o_ref[...] += jnp.dot(h, wd_ref[0].astype(jnp.float32),
                          preferred_element_type=jnp.float32)


@functools.partial(
    jax.jit, static_argnames=("block_tokens", "block_ffn", "interpret")
)
def grouped_ffn_blocked(
    x: jnp.ndarray,           # [M, D] sorted+padded tokens (block-homogeneous)
    block_expert: jnp.ndarray,  # [M // block_tokens] int32
    wg: jnp.ndarray,          # [E, D, F]
    wu: jnp.ndarray,          # [E, D, F]
    wd: jnp.ndarray,          # [E, F, D]
    *,
    block_tokens: int = 128,
    block_ffn: int = 128,
    interpret: bool | None = None,
) -> jnp.ndarray:
    m, d = x.shape
    e, _, f = wg.shape
    assert m % block_tokens == 0 and f % block_ffn == 0
    grid = (m // block_tokens, f // block_ffn)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_tokens, d), lambda i, fb, eid: (i, 0)),
            pl.BlockSpec((1, d, block_ffn), lambda i, fb, eid: (eid[i], 0, fb)),
            pl.BlockSpec((1, d, block_ffn), lambda i, fb, eid: (eid[i], 0, fb)),
            pl.BlockSpec((1, block_ffn, d), lambda i, fb, eid: (eid[i], fb, 0)),
        ],
        out_specs=pl.BlockSpec((block_tokens, d), lambda i, fb, eid: (i, 0)),
    )
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, d), jnp.float32),
        name="grouped_ffn",
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=resolve_interpret(interpret),
    )(block_expert.astype(jnp.int32), x, wg, wu, wd)
    return out.astype(x.dtype)
