"""Operations, bytes and traffic of the benchmark's cells against counts made
by hand."""

import numpy as np
import pytest

from bench import generate, work
from repro.launch.selftest import hot_spot_counts as selftest_hot_spot


def test_a2av_bytes():
    counts = np.array([[5, 1, 2], [3, 4, 0], [6, 0, 7]])
    # off-diagonal live chunks: 1 + 2 + 3 + 0 + 6 + 0 = 12
    assert work.a2av_useful_bytes(counts, 10) == 120
    # destination 0 takes 3 + 6 chunks from the others
    assert work.a2av_ingress_bytes(counts, 10, 0) == 90


def test_hot_spot_counts_match_the_selftest_and_move_the_hot_chip():
    assert np.array_equal(generate.hot_spot_counts(4, 64, 0.9, hot=0),
                          selftest_hot_spot(4, 64, 0.9))
    c = generate.hot_spot_counts(4, 64, 0.9, hot=2)
    # 58 chunks to the hot chip (to chip 3 from the hot chip), 3 to others
    assert c[0, 2] == c[1, 2] == c[3, 2] == 58 and c[2, 3] == 58
    assert (np.diag(c) == 0).all() and (c.sum(1) == 64).all()
    # the cell's hot destination takes 3 x 58 MiB of 1 MiB chunks
    assert work.a2av_ingress_bytes(c, 1 << 20, 2) == 174 << 20


def test_hot_expert_pairs_hold_the_counts_and_the_seed_only_orders():
    a = generate.hot_expert_pairs(4096, 8, 0.9, np.random.default_rng(1))
    b = generate.hot_expert_pairs(4096, 8, 0.9, np.random.default_rng(2))
    assert a.shape == (4096, 2) and (a[:, 0] != a[:, 1]).all()
    assert (a[:, 0] == 0).sum() == round(0.9 * 4096)
    assert np.array_equal(np.bincount(a.reshape(-1), minlength=8),
                          np.bincount(b.reshape(-1), minlength=8))
    assert not np.array_equal(a, b)
    # second choices of the hot expert's tokens spread evenly over the rest
    second = np.bincount(a[a[:, 0] == 0, 1], minlength=8)[1:]
    assert second.max() - second.min() <= 1


def test_lm_batches_shift_labels():
    t, l = generate.lm_batches(2, 1, 16, 50, np.random.default_rng(0))
    assert t.shape == l.shape == (2, 1, 16)
    assert np.array_equal(t[:, :, 1:], l[:, :, :-1])


def test_ffn_and_moe_flops():
    # gate and up: 2*d*f each per row, down 2*f*d: 6*d*f per row
    assert work.ffn_flops(3, 4, 5) == 3 * 6 * 4 * 5
    assert work.ffn_bytes(2, 3, 4, 5, 4) == (3 * 2 * 4 * 5 + 2 * 3 * 4) * 4
    # paper block: router 2*4096*8, two experts of 6*4096*16384
    assert work.moe_fwd_flops_per_token(4096, 16384, 8, 2) == (
        65536 + 2 * 6 * 4096 * 16384)


def test_attention_flops_and_bytes():
    # S=3: 6 (q, k) pairs on or below the diagonal; QK and PV 2*dh each
    assert work.causal_attn_flops(1, 2, 3, 4) == 2 * 6 * 2 * 2 * 4
    assert work.attn_bytes(1, 2, 1, 3, 4, 4) == (2 * 2 + 2 * 1) * 3 * 4 * 4


def test_lm_flops_per_token():
    kw = dict(d=8, heads=2, kv_heads=1, head_dim=4, f=3, n_experts=4,
              top_k=2, vocab=10, n_layers=2, seq=3)
    proj = 2 * 8 * (2 * 2 * 4 + 2 * 1 * 4)
    attn = 2 * 2 * 2 * 6 * 4 / 3
    moe = 2 * 8 * 4 + 2 * 6 * 8 * 3
    fwd = 2 * (proj + attn + moe) + 2 * 8 * 10
    assert work.lm_fwd_flops_per_token(**kw) == pytest.approx(fwd)
    assert work.lm_train_flops_per_token(**kw) == pytest.approx(3 * fwd)
