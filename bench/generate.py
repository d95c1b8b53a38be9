"""The one traffic generator.

Every traffic mix under ``bench/traffic/`` is a data file of parameters whose
``pattern`` names one of the functions below.  The seed decides payloads and
order only: every seed of a mix gets the same sizes and the same counts, so
runs with different seeds do the same work.
"""

from __future__ import annotations

import numpy as np


def hot_spot_counts(n: int, max_chunks: int, hotspot: float,
                    hot: int = 0) -> np.ndarray:
    """[n, n] chunk counts: each source sends ``hotspot`` of its chunks to the
    hot destination ``hot`` (``hot + 1`` for the hot source itself) and splits
    the rest evenly over the others (paper Fig. 7).

    Copied from ``repro.launch.selftest.hot_spot_counts``, which fixes ``hot``
    at 0."""
    counts = np.zeros((n, n), dtype=np.int32)
    for s in range(n):
        hd = hot if s != hot else (hot + 1) % n
        counts[s, hd] = int(round(max_chunks * hotspot))
        others = [d for d in range(n) if d not in (s, hd)]
        for d in others:
            counts[s, d] = int(max_chunks * (1 - hotspot) / len(others))
    return counts


def hot_expert_pairs(n_tokens: int, n_experts: int, hot: float,
                     rng: np.random.Generator,
                     hot_expert: int = 0) -> np.ndarray:
    """[T, 2] top-2 expert choices with the expected counts of
    ``benchmarks/bench_moe_e2e.route_tokens``, held exactly.

    ``route_tokens`` draws a token's first expert as the hot one with
    probability ``hot`` (uniform over the rest otherwise) and its second
    uniform over the experts other than the first.  Here ``round(hot * T)``
    tokens take the hot expert first, the others take the remaining experts
    first in turn, and each first expert's tokens cycle through the other
    experts for their second choice.  The seed only shuffles the tokens."""
    others = np.array([e for e in range(n_experts) if e != hot_expert])
    n_hot = int(round(hot * n_tokens))
    first = np.concatenate([
        np.full(n_hot, hot_expert),
        others[np.arange(n_tokens - n_hot) % len(others)],
    ])
    second = np.empty_like(first)
    for e in range(n_experts):
        idx = np.nonzero(first == e)[0]
        offset = 1 + np.arange(len(idx)) % (n_experts - 1)
        second[idx] = (e + offset) % n_experts
    pairs = np.stack([first, second], axis=1)
    return pairs[rng.permutation(n_tokens)].astype(np.int32)


def lm_batches(n_batches: int, batch: int, seq: int, vocab: int,
               rng: np.random.Generator) -> tuple[np.ndarray, np.ndarray]:
    """Token and label ids [n_batches, batch, seq], uniform over the
    vocabulary; a label is the next token, and the last one is drawn too."""
    ids = rng.integers(0, vocab, size=(n_batches, batch, seq + 1),
                       dtype=np.int32)
    return ids[..., :-1], ids[..., 1:]
