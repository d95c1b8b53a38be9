"""Public grouped-FFN op: ragged tokens -> sort/pad -> blocked kernel.

``grouped_ffn(x, expert_id, wg, wu, wd)`` accepts tokens in arbitrary order
with ``expert_id[i] in [0, E)`` or ``-1`` for padding rows.  It

  1. sorts tokens by expert (stable),
  2. pads each expert's segment to a multiple of ``block_tokens`` (static
     worst-case buffer of ``N + E*block_tokens`` rows),
  3. runs the Pallas blocked kernel with per-block expert ids,
  4. scatters results back to the original order.

Gradients flow through a jnp-reference VJP (the sort/pad is a permutation;
the FFN backward reuses the same grouping).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from .ffn import grouped_ffn_blocked
from .ref import grouped_ffn_ref


def _arrange(expert_id: jnp.ndarray, n_experts: int, block: int):
    """Compute padded positions + per-block experts for ragged grouping."""
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
    return order, pos_sorted, blk_expert, m_pad


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
    order, pos, blk_expert, m_pad = _arrange(expert_id, E, block_tokens)
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
    order, pos, blk_expert, m_pad = _arrange(expert_id, E, block_tokens)
    x_pad = jnp.zeros((m_pad, d), x.dtype).at[pos].set(x[order])
    y_pad = grouped_ffn_blocked(
        x_pad, blk_expert, wg, wu, wd,
        block_tokens=block_tokens, block_ffn=block_ffn,
    )
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

__all__ = ["grouped_ffn", "grouped_ffn_dense", "grouped_ffn_ref"]
