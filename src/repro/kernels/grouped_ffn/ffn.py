"""Pallas TPU kernel: blocked per-expert SwiGLU FFN (megablox-style).

The MoE compute hot-spot.  Tokens arrive sorted by expert and padded so each
(bm)-row tile is expert-homogeneous; the tile's expert id is scalar-
prefetched and selects the weight slices directly in the BlockSpec
``index_map`` — no gather of full weight matrices into registers.

Grid = (row_tiles, ffn_blocks); the ffn dimension is the innermost
(sequential) axis so the (bm, D) output tile accumulates partial
``(act(x·Wg) * (x·Wu)) · Wd`` contributions across F-slices in f32.  Each
expert's weights therefore stream from HBM once per row tile, so the tile
is as tall as the input allows (``row_tile``): up to 512 rows, at most a
2E-th of the rows so padding stays a small share of the work.

Skipping: each tile's count of valid 128-row sub-tiles (the v5e MXU height)
and the number of tiles holding any row are scalar-prefetched too.  The three
dots run per sub-tile in a ``fori_loop`` over the tile's valid sub-tiles, so
padding inside a tile costs no MXU time.  (A loop and not one ``pl.when``
per sub-tile: four unrolled copies of the f32 dots made a 512-row tile take
189 ms on v5e where the loop takes 108.)  Tiles past the last real one clamp
their block indices to that tile's final step: the pipeline sees no new
block, copies nothing, and the body is skipped.  Their output rows are never
written; the caller reads them only at masked positions.

VMEM is the double-buffered tiles, 2·(bm·D·x + bm·D·4 + 3·D·bf·w) bytes,
plus the f32 temporaries of one sub-tile, sub·D·4·2 + 3·D·bf·4 + 3·sub·bf·4.
With f32 weights at the paper's §V-D block (D=4096, bf=128) and bm=512 the
tiles take 44 MiB, past the compiler's default 16 MiB scoped limit; the
kernel asks for ``VMEM_LIMIT_BYTES`` of v5e's 128 MiB instead.  bf must be a
multiple of 128 (the lane width), bm a multiple of 8.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

#: scoped VMEM the kernel may claim (v5e holds 128 MiB); room for the f32
#: §V-D tiles with the rest left to the compiler
VMEM_LIMIT_BYTES = 64 * 1024 * 1024
#: rows of one sub-tile, the unit of skipped padding (v5e's MXU height)
SUB_ROWS = 128
#: tallest row tile; past it the weights' re-reads are already cheap
MAX_ROWS = 512


def sub_rows(bm: int) -> int:
    """Rows of the sub-tiles of a ``bm``-row tile."""
    return math.gcd(bm, SUB_ROWS)


def _vmem_bytes(bm: int, d: int, block_ffn: int, x_itemsize: int,
               w_itemsize: int) -> int:
    """VMEM the kernel needs at row tile ``bm``: double-buffered x, out and
    weight tiles plus one sub-tile's f32 temporaries."""
    sub = sub_rows(bm)
    tiles = 2 * (bm * d * x_itemsize + bm * d * 4
                 + 3 * d * block_ffn * w_itemsize)
    temps = sub * d * 4 * 2 + 3 * d * block_ffn * 4 + 3 * sub * block_ffn * 4
    return tiles + temps


def row_tile(m: int, n_experts: int, d: int, block_ffn: int,
             x_itemsize: int, w_itemsize: int, floor: int = 64) -> int:
    """Row tile for ``m`` routed rows over ``n_experts``: the tallest of
    floor·2^k ≤ ``MAX_ROWS`` with ``bm ≤ m / (2E)`` whose VMEM fits
    ``VMEM_LIMIT_BYTES``; ``floor`` when none does (decode-sized inputs)."""
    bm = floor
    while (2 * bm <= MAX_ROWS and 2 * n_experts * 2 * bm <= m
           and _vmem_bytes(2 * bm, d, block_ffn, x_itemsize,
                          w_itemsize) <= VMEM_LIMIT_BYTES):
        bm *= 2
    return bm


def _kernel(eid_ref, nsub_ref, ntiles_ref, x_ref, wg_ref, wu_ref, wd_ref,
            o_ref, *, sub):
    i, fb = pl.program_id(0), pl.program_id(1)

    @pl.when(i < ntiles_ref[0])
    def _tile():
        @pl.when(fb == 0)
        def _init():
            o_ref[...] = jnp.zeros_like(o_ref)

        def _sub(s, carry):
            rows = pl.ds(pl.multiple_of(s * sub, sub), sub)
            x = x_ref[rows, :].astype(jnp.float32)
            g = jnp.dot(x, wg_ref[0].astype(jnp.float32),
                        preferred_element_type=jnp.float32)
            u = jnp.dot(x, wu_ref[0].astype(jnp.float32),
                        preferred_element_type=jnp.float32)
            h = jax.nn.silu(g) * u
            o_ref[rows, :] += jnp.dot(h, wd_ref[0].astype(jnp.float32),
                                      preferred_element_type=jnp.float32)
            return carry

        jax.lax.fori_loop(0, nsub_ref[i], _sub, 0)


@functools.partial(
    jax.jit, static_argnames=("block_tokens", "block_ffn", "interpret")
)
def grouped_ffn_blocked(
    x: jnp.ndarray,           # [M, D] sorted+padded tokens (tile-homogeneous)
    tile_expert: jnp.ndarray,  # [M // block_tokens] int32
    tile_rows: jnp.ndarray,   # [M // block_tokens] valid rows; real tiles first
    wg: jnp.ndarray,          # [E, D, F]
    wu: jnp.ndarray,          # [E, D, F]
    wd: jnp.ndarray,          # [E, F, D]
    *,
    block_tokens: int = 128,
    block_ffn: int = 128,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Rows of tiles that hold no valid row, and rows past a tile's last
    valid sub-tile, come back unwritten."""
    m, d = x.shape
    e, _, f = wg.shape
    assert m % block_tokens == 0 and f % block_ffn == 0
    sub = sub_rows(block_tokens)
    nf = f // block_ffn
    tile_rows = tile_rows.astype(jnp.int32)
    n_sub = -(-tile_rows // sub)
    n_tiles = jnp.sum(tile_rows > 0, dtype=jnp.int32)[None]

    def at(i, fb, nt):
        """Tiles past the last real one stay on its final block."""
        live = i < nt[0]
        return (jnp.where(live, i, jnp.maximum(nt[0] - 1, 0)),
                jnp.where(live, fb, nf - 1))

    def x_map(i, fb, eid, ns, nt):
        return at(i, fb, nt)[0], 0

    def w_in_map(i, fb, eid, ns, nt):
        t, k = at(i, fb, nt)
        return eid[t], 0, k

    def w_out_map(i, fb, eid, ns, nt):
        t, k = at(i, fb, nt)
        return eid[t], k, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(m // block_tokens, nf),
        in_specs=[
            pl.BlockSpec((block_tokens, d), x_map),
            pl.BlockSpec((1, d, block_ffn), w_in_map),
            pl.BlockSpec((1, d, block_ffn), w_in_map),
            pl.BlockSpec((1, block_ffn, d), w_out_map),
        ],
        out_specs=pl.BlockSpec((block_tokens, d), x_map),
    )
    out = pl.pallas_call(
        functools.partial(_kernel, sub=sub),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, d), jnp.float32),
        name="grouped_ffn",
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=resolve_interpret(interpret),
    )(tile_expert.astype(jnp.int32), n_sub, n_tiles, x, wg, wu, wd)
    return out.astype(x.dtype)
