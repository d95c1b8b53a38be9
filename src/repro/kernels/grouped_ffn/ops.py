"""Public grouped-FFN op: ragged tokens -> sort/pad -> blocked kernel.

``grouped_ffn(x, expert_id, wg, wu, wd)`` accepts tokens in arbitrary order
with ``expert_id[i] in [0, E)`` or ``-1`` for padding rows, or slot-major
top-k ids ``expert_id[j, i]``, token ``i``'s j-th expert (each token routed
k times); either way it returns one row per id, in the order of
``expert_id.reshape(-1)``.  It

  1. sorts the routed rows by expert (stable, int keys only),
  2. derives from the sort, with no row moved, the routed row of every
     padded row and the padded row of every routed row; each expert's
     segment is padded to a multiple of the row tile ``bm`` (``ffn.row_tile``:
     the tallest the shape allows, ``block_tokens`` at least; static
     worst-case buffer of ``R + E*bm`` rows for ``R`` routed rows),
  3. gathers the kernel's input rows straight from the tokens (padding
     rows take any token's row: the kernel computes each row alone, and no
     padding row of its output is read),
  4. runs the Pallas blocked kernel with per-tile expert ids and valid-row
     counts (``tile_plan`` says what it streams and computes),
  5. gathers each routed row's result back, cast to ``x.dtype``.

Rows move by those two gathers only: no row is repeated or scattered, and
only the gathered rows of the kernel's f32 output are converted.  It is one
path on every backend: off the TPU the same kernel runs in the Pallas
interpreter (``resolve_interpret``).  Gradients flow through a
jnp-reference VJP (the sort/pad is a permutation; the FFN backward reuses
the same grouping).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp

from .ffn import grouped_ffn_blocked, row_tile, sub_rows
from .ref import grouped_ffn_ref

#: trace scope of the row arrangement around the kernel (the sort, the index
#: maps and the two row gathers); the kernel stays in its caller's scope.
#: ``bench/scopes.py`` reads it.
ARRANGE = "nimble.arrange"


class TilePlan(NamedTuple):
    bm: int            # row tile; one expert's weights stream once per tile
    tiles: int         # tiles holding a valid row (the rest stream nothing)
    sub_tiles: int     # sub-tiles computed (the rest cost no MXU time)
    rows: int          # rows computed, valid and padding
    weight_bytes: int  # expert weight bytes read from HBM


def tile_plan(counts: Sequence[int], m: int, E: int, d: int, f: int,
              itemsize: int) -> TilePlan:
    """What the kernel streams and computes for ``m`` routed rows whose
    per-expert ``counts`` are known on the host, with the row tile the
    kernel itself picks (``row_tile``) at the model's blocks: a 64-row
    floor and 128-wide ffn slices."""
    bm = row_tile(m, E, d, 128, itemsize, itemsize, floor=64)
    sub = sub_rows(bm)
    tiles = sum(-(-int(c) // bm) for c in counts)
    sub_tiles = sum(-(-int(c) // sub) for c in counts)
    return TilePlan(bm, tiles, sub_tiles, sub_tiles * sub,
                    tiles * 3 * d * f * itemsize)


def _arrange(expert_id: jnp.ndarray, n_experts: int, block: int):
    """Maps between routed rows and the kernel's padded, expert-sorted rows,
    from the int ids alone (no row is moved here); tiles holding rows come
    first.

    Returns ``(row_of, pos, blk_expert, blk_rows, m_pad)``: ``row_of[p]`` is
    the routed row in padded row ``p`` (-1 in padding) and ``pos[j]`` the
    padded row of routed row ``j`` (for an invalid row the last one, which
    no live tile holds)."""
    n = expert_id.shape[0]
    m_pad = (-(-n // block) + n_experts) * block  # block-aligned worst case
    key = jnp.where(expert_id < 0, n_experts, expert_id)
    order = jnp.argsort(key, stable=True)                       # sorted rows
    counts = jnp.bincount(jnp.clip(key, 0, n_experts), length=n_experts + 1)
    aligned = (jnp.ceil(counts[:-1] / block) * block).astype(jnp.int32)
    aligned_off = jnp.cumsum(aligned) - aligned                 # [E]
    seg_off = jnp.cumsum(counts[:-1]) - counts[:-1]             # [E]
    # block -> expert (blocks past the last segment clamp to E-1, all-zero)
    blk_start = jnp.arange(m_pad // block) * block
    blk_expert = jnp.sum(
        aligned_off[None, :] <= blk_start[:, None], axis=1
    ) - 1
    blk_expert = jnp.clip(blk_expert, 0, n_experts - 1)
    blk_rows = counts[blk_expert] - (blk_start - aligned_off[blk_expert])
    blk_rows = jnp.clip(blk_rows, 0, block).astype(jnp.int32)
    # padded row -> its rank in its block's expert -> the sorted row
    e = blk_expert[jnp.arange(m_pad) // block]
    rank = jnp.arange(m_pad) - aligned_off[e]
    row_of = jnp.where(rank < counts[e],
                       order[jnp.minimum(seg_off[e] + rank, n - 1)], -1)
    # routed row -> its place in the sort (an int scatter) -> padded row
    sorted_at = jnp.zeros((n,), jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32), unique_indices=True)
    kc = jnp.clip(key, 0, n_experts - 1)
    pos = aligned_off[kc] + sorted_at - seg_off[kc]
    pos = jnp.where(key >= n_experts, m_pad - 1, pos)
    return row_of, pos, blk_expert, blk_rows, m_pad


def grouped_ffn(
    x: jnp.ndarray,
    expert_id: jnp.ndarray,
    wg: jnp.ndarray,
    wu: jnp.ndarray,
    wd: jnp.ndarray,
    *,
    block_tokens: int = 128,
    block_ffn: int = 128,
) -> jnp.ndarray:
    """``x`` [N, d] with ``expert_id`` [N] gives [N, d]; with slot-major
    top-k ids [k, N] it gives [k*N, d], row ``j*N + i`` token ``i`` through
    expert ``expert_id[j, i]``.  ``block_tokens`` is the kernel's least row
    tile; the kernel grows its tile from the shape (``ffn.row_tile``)."""
    return _grouped_ffn(x, expert_id, wg, wu, wd, block_tokens, block_ffn)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _grouped_ffn(x, expert_id, wg, wu, wd, block_tokens, block_ffn):
    E = wg.shape[0]
    n, d = x.shape
    ids = expert_id.reshape(-1)
    bm = row_tile(ids.shape[0], E, d, block_ffn, x.dtype.itemsize,
                  wg.dtype.itemsize, floor=block_tokens)
    with jax.named_scope(ARRANGE):
        row_of, pos, blk_expert, blk_rows, m_pad = _arrange(ids, E, bm)
        # routed row j is token j % n; padding rows (-1) read token n - 1
        x_pad = x[row_of % n]
    y_pad = grouped_ffn_blocked(
        x_pad, blk_expert, blk_rows, wg, wu, wd,
        block_tokens=bm, block_ffn=block_ffn,
    )
    with jax.named_scope(ARRANGE):
        # invalid rows read the last padded row, which the kernel never writes
        y = y_pad[pos].astype(x.dtype)
        return jnp.where((ids >= 0)[:, None], y, 0)


def _fwd(x, expert_id, wg, wu, wd, block_tokens, block_ffn):
    y = _grouped_ffn(x, expert_id, wg, wu, wd, block_tokens, block_ffn)
    return y, (x, expert_id, wg, wu, wd)


def _bwd(block_tokens, block_ffn, res, g):
    x, expert_id, wg, wu, wd = res
    k = expert_id.shape[0] if expert_id.ndim == 2 else 1
    # backward via the reference formulation (einsum over expert one-hots)
    # on the tokens tiled once per slot; exact for the same f32
    # accumulation, and the tile's VJP sums gx over a token's k rows.
    def f(x, wg, wu, wd):
        return grouped_ffn_ref(jnp.tile(x, (k, 1)), expert_id.reshape(-1),
                               wg, wu, wd)

    _, vjp = jax.vjp(f, x, wg, wu, wd)
    gx, gwg, gwu, gwd = vjp(g)
    return gx, None, gwg, gwu, gwd


_grouped_ffn.defvjp(_fwd, _bwd)

__all__ = ["ARRANGE", "TilePlan", "grouped_ffn", "grouped_ffn_ref",
           "tile_plan"]
