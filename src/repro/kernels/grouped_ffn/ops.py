"""Public grouped-FFN op: ragged tokens -> sort/pad -> blocked kernel.

``grouped_ffn(x, expert_id, wg, wu, wd)`` accepts tokens in arbitrary order
with ``expert_id[i] in [0, E)`` or ``-1`` for padding rows.  It

  1. sorts tokens by expert (stable),
  2. pads each expert's segment to a multiple of the row tile ``bm``
     (``ffn.row_tile``: the tallest the shape allows, ``block_tokens`` at
     least; static worst-case buffer of ``N + E*bm`` rows),
  3. runs the Pallas blocked kernel with per-tile expert ids and valid-row
     counts (``tile_plan`` says what it streams and computes),
  4. scatters results back to the original order.

It is one path on every backend: off the TPU the same kernel runs in the
Pallas interpreter (``resolve_interpret``).  Gradients flow through a
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
    """Compute padded positions, per-block experts and per-block valid rows
    for ragged grouping; blocks holding rows come first."""
    n = expert_id.shape[0]
    m_pad = (-(-n // block) + n_experts) * block  # block-aligned worst case
    key = jnp.where(expert_id < 0, n_experts, expert_id)
    order = jnp.argsort(key, stable=True)                       # sorted rows
    counts = jnp.bincount(jnp.clip(key, 0, n_experts), length=n_experts + 1)
    aligned = (jnp.ceil(counts[:-1] / block) * block).astype(jnp.int32)
    aligned_off = jnp.cumsum(aligned) - aligned                 # [E]
    # rank of each sorted row within its expert
    seg_off = jnp.cumsum(counts[:-1]) - counts[:-1]
    rank = jnp.arange(n) - seg_off[jnp.clip(key[order], 0, n_experts - 1)]
    pos_sorted = aligned_off[jnp.clip(key[order], 0, n_experts - 1)] + rank
    pos_sorted = jnp.where(key[order] >= n_experts, m_pad - 1, pos_sorted)
    # block -> expert (blocks past the last segment clamp to E-1, all-zero)
    blk_start = jnp.arange(m_pad // block) * block
    blk_expert = jnp.sum(
        aligned_off[None, :] <= blk_start[:, None], axis=1
    ) - 1
    blk_expert = jnp.clip(blk_expert, 0, n_experts - 1)
    blk_rows = counts[blk_expert] - (blk_start - aligned_off[blk_expert])
    blk_rows = jnp.clip(blk_rows, 0, block).astype(jnp.int32)
    return order, pos_sorted, blk_expert, blk_rows, m_pad


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
    """``block_tokens`` is the kernel's least row tile; the kernel grows its
    tile from the shape (``ffn.row_tile``)."""
    return _grouped_ffn(x, expert_id, wg, wu, wd, block_tokens, block_ffn)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _grouped_ffn(x, expert_id, wg, wu, wd, block_tokens, block_ffn):
    E = wg.shape[0]
    n, d = x.shape
    bm = row_tile(n, E, d, block_ffn, x.dtype.itemsize, wg.dtype.itemsize,
                  floor=block_tokens)
    order, pos, blk_expert, blk_rows, m_pad = _arrange(expert_id, E, bm)
    x_pad = jnp.zeros((m_pad, d), x.dtype).at[pos].set(x[order])
    y_pad = grouped_ffn_blocked(
        x_pad, blk_expert, blk_rows, wg, wu, wd,
        block_tokens=bm, block_ffn=block_ffn,
    )
    # invalid rows read the last tile, which the kernel never writes
    y = jnp.zeros((n, d), x.dtype).at[order].set(y_pad[pos])
    return jnp.where((expert_id >= 0)[:, None], y, 0)


def _fwd(x, expert_id, wg, wu, wd, block_tokens, block_ffn):
    y = _grouped_ffn(x, expert_id, wg, wu, wd, block_tokens, block_ffn)
    return y, (x, expert_id, wg, wu, wd)


def _bwd(block_tokens, block_ffn, res, g):
    x, expert_id, wg, wu, wd = res
    # backward via the reference formulation (einsum over expert one-hots);
    # exact for the same f32 accumulation.
    def f(x, wg, wu, wd):
        return grouped_ffn_ref(x, expert_id, wg, wu, wd)

    _, vjp = jax.vjp(f, x, wg, wu, wd)
    gx, gwg, gwu, gwd = vjp(g)
    return gx, None, gwg, gwu, gwd


_grouped_ffn.defvjp(_fwd, _bwd)

__all__ = ["TilePlan", "grouped_ffn", "grouped_ffn_ref", "tile_plan"]
