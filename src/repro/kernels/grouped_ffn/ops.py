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

Gradients flow through a jnp-reference VJP (the sort/pad is a permutation;
the FFN backward reuses the same grouping).
"""

from __future__ import annotations

import functools
import os
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


def grouped_ffn_scan(
    x: jnp.ndarray,
    expert_id: jnp.ndarray,
    wg: jnp.ndarray,
    wu: jnp.ndarray,
    wd: jnp.ndarray,
    *,
    block_tokens: int = 128,
) -> jnp.ndarray:
    """Non-TPU large-shape path: same sort/pad arrangement, but the blocked
    matmuls run as a ``lax.scan`` over token blocks with a dynamic gather of
    the block's expert weights.  FLOPs identical to the Pallas kernel (so
    dry-run rooflines are faithful); native autodiff."""
    E = wg.shape[0]
    n, d = x.shape
    order, pos, blk_expert, _, m_pad = _arrange(expert_id, E, block_tokens)
    x_pad = jnp.zeros((m_pad, d), x.dtype).at[pos].set(x[order])
    xb = x_pad.reshape(-1, block_tokens, d)

    def step(_, inp):
        xi, e = inp
        g = jax.nn.silu(xi.astype(jnp.float32) @ wg[e].astype(jnp.float32))
        u = xi.astype(jnp.float32) @ wu[e].astype(jnp.float32)
        return None, ((g * u) @ wd[e].astype(jnp.float32)).astype(x.dtype)

    _, yb = jax.lax.scan(step, None, (xb, blk_expert))
    y_pad = yb.reshape(m_pad, d)
    y = jnp.zeros((n, d), x.dtype).at[order].set(y_pad[pos])
    return jnp.where((expert_id >= 0)[:, None], y, 0)


def grouped_ffn_dense(
    x: jnp.ndarray,
    expert_id: jnp.ndarray,
    wg: jnp.ndarray,
    wu: jnp.ndarray,
    wd: jnp.ndarray,
    *,
    cap_factor: float = 2.0,
    block_tokens: int = 64,
) -> jnp.ndarray:
    """Static-capacity segment einsum (§Perf iteration C1).

    The block-scan path reads one expert's weights per 64-token block —
    ~128x more weight traffic than necessary (1024 blocks vs 8 experts on
    the qwen3-moe dry-run, dominating its memory roofline term).  Here
    tokens are packed into a [E, cap, d] buffer and each expert's weights
    are read ONCE by three dense einsums.

    Capacity semantics match the dispatcher's buffers (paper §IV policies):
    rows beyond ``cap = ceil(N * cap_factor / E)`` (block-aligned) are
    dropped (output 0).  With a balanced-enough routing (or cap_factor
    sized like the dispatch capacity) the result equals the reference.
    """
    E = wg.shape[0]
    n, d = x.shape
    cap = max(int(-(-n * cap_factor // (E * block_tokens))), 1) * block_tokens
    key = jnp.where(expert_id < 0, E, expert_id)
    order = jnp.argsort(key, stable=True)
    counts = jnp.bincount(jnp.clip(key, 0, E), length=E + 1)
    seg_off = jnp.cumsum(counts[:-1]) - counts[:-1]
    rank_sorted = jnp.arange(n) - seg_off[jnp.clip(key[order], 0, E - 1)]
    rank = jnp.zeros((n,), jnp.int32).at[order].set(
        rank_sorted.astype(jnp.int32))
    kept = (rank < cap) & (expert_id >= 0)
    e_c = jnp.clip(expert_id, 0, E - 1)
    r_c = jnp.minimum(rank, cap - 1)
    buf = jnp.zeros((E, cap, d), x.dtype).at[e_c, r_c].add(
        jnp.where(kept[:, None], x, 0)
    )
    bf = buf.astype(jnp.float32)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", bf, wg.astype(jnp.float32)))
    u = jnp.einsum("ecd,edf->ecf", bf, wu.astype(jnp.float32))
    yb = jnp.einsum("ecf,efd->ecd", h * u, wd.astype(jnp.float32))
    y = yb[e_c, r_c].astype(x.dtype)
    return jnp.where(kept[:, None], y, 0)


def grouped_ffn(
    x: jnp.ndarray,
    expert_id: jnp.ndarray,
    wg: jnp.ndarray,
    wu: jnp.ndarray,
    wd: jnp.ndarray,
    *,
    block_tokens: int = 128,
    block_ffn: int = 128,
    cap_factor: float = 2.0,
) -> jnp.ndarray:
    """``block_tokens`` is the CPU paths' block and the kernel's least row
    tile; the kernel grows its tile from the shape (``ffn.row_tile``)."""
    if jax.default_backend() != "tpu" and x.shape[0] > 4 * block_tokens:
        # §Perf C1: dense segment einsum by default; the block-scan baseline
        # stays selectable for before/after measurement.  Dense wins when
        # the saved per-block weight re-reads outweigh capacity padding —
        # i.e. when there are substantially more token blocks than experts;
        # tiny decode batches keep the scan path (fixes the 0.87-0.97x
        # MoE-decode regressions in EXPERIMENTS.md §Perf).
        E = wg.shape[0]
        dense_worthwhile = x.shape[0] >= 2 * E * block_tokens
        if (os.environ.get("NIMBLE_FFN_IMPL", "dense") == "scan"
                or not dense_worthwhile):
            return grouped_ffn_scan(x, expert_id, wg, wu, wd,
                                    block_tokens=block_tokens)
        return grouped_ffn_dense(x, expert_id, wg, wu, wd,
                                 cap_factor=cap_factor,
                                 block_tokens=block_tokens)
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

__all__ = ["TilePlan", "grouped_ffn", "grouped_ffn_dense", "grouped_ffn_ref",
           "tile_plan"]
