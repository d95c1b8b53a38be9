"""Operations and bytes that each cell's work requires, from its shapes and
routing alone.  Nothing here reads the compiled program, so a roofline share
reads the same work whatever implements it.
"""

from __future__ import annotations

import numpy as np


def a2av_useful_bytes(counts: np.ndarray, chunk_bytes: int) -> int:
    """Live off-diagonal chunks of one call times the chunk size."""
    c = np.asarray(counts, dtype=np.int64)
    return int((c.sum() - np.trace(c)) * chunk_bytes)


def a2av_ingress_bytes(counts: np.ndarray, chunk_bytes: int, dest: int) -> int:
    """Bytes that destination ``dest`` must take in from the other chips."""
    c = np.asarray(counts, dtype=np.int64)
    return int((c[:, dest].sum() - c[dest, dest]) * chunk_bytes)


def ffn_flops(rows: int, d: int, f: int) -> int:
    """SwiGLU expert FFN over ``rows`` valid rows: gate, up and down."""
    return 3 * 2 * rows * d * f


def ffn_bytes(held_experts: int, rows: int, d: int, f: int,
              itemsize: int) -> int:
    """The held experts' weights read once, and the rows read and written."""
    return (3 * held_experts * d * f + 2 * rows * d) * itemsize


def moe_fwd_flops_per_token(d: int, f: int, n_experts: int, top_k: int) -> int:
    """Router logits plus ``top_k`` expert FFNs."""
    return 2 * d * n_experts + top_k * ffn_flops(1, d, f)


def causal_attn_flops(batch: int, heads: int, seq: int, head_dim: int) -> int:
    """QK^T and PV over the causal triangle (diagonal included)."""
    pairs = seq * (seq + 1) // 2
    return 2 * 2 * batch * heads * pairs * head_dim


def attn_bytes(batch: int, heads: int, kv_heads: int, seq: int,
               head_dim: int, itemsize: int) -> int:
    """q, k and v read once and the output written once."""
    return (2 * heads + 2 * kv_heads) * batch * seq * head_dim * itemsize


def lm_fwd_flops_per_token(d: int, heads: int, kv_heads: int, head_dim: int,
                           f: int, n_experts: int, top_k: int, vocab: int,
                           n_layers: int, seq: int) -> float:
    """Forward operations per token of the MoE language model: projections,
    causal attention averaged over the positions, router, the routed experts
    and the output head.  Embedding lookup and norms are not counted."""
    proj = 2 * d * (2 * heads * head_dim + 2 * kv_heads * head_dim)
    attn = causal_attn_flops(1, heads, seq, head_dim) / seq
    layer = proj + attn + moe_fwd_flops_per_token(d, f, n_experts, top_k)
    return n_layers * layer + 2 * d * vocab


def lm_train_flops_per_token(**kw) -> float:
    """Forward and backward (twice the forward), with no recompute."""
    return 3 * lm_fwd_flops_per_token(**kw)
