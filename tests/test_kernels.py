"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.flash import (
    block_plan, flash_attention, live_pairs,
)
from repro.kernels.flash_attention.ops import attention, chunked_attention
from repro.kernels.flash_attention.ref import mha_ref
from repro.kernels.grouped_ffn.ffn import grouped_ffn_blocked
from repro.kernels.grouped_ffn.ops import grouped_ffn, tile_plan
from repro.kernels.grouped_ffn.ref import grouped_ffn_ref
from repro.kernels.relay_copy.relay import relay_copy
from repro.kernels.token_scatter.ops import token_gather
from repro.kernels.token_scatter.ref import token_gather_ref

RNG = np.random.default_rng(0)


def test_interpret_mode_only_on_cpu(monkeypatch):
    from repro.kernels import resolve_interpret

    assert resolve_interpret() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_interpret() is False
    assert resolve_interpret(False) is False
    with pytest.raises(ValueError, match="interpret"):
        resolve_interpret(True)


# --------------------------------------------------------------------------- #
# token gather (kernel scatter)
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("dtype", [np.float32, np.float16])
@pytest.mark.parametrize("n,m,d", [(64, 100, 32), (16, 16, 128), (128, 7, 8)])
def test_token_gather(n, m, d, dtype):
    x = RNG.normal(size=(n, d)).astype(dtype)
    idx = RNG.integers(-1, n, size=(m,)).astype(np.int32)
    out = token_gather(jnp.asarray(x), jnp.asarray(idx))
    ref = token_gather_ref(jnp.asarray(x), jnp.asarray(idx))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref))


def test_token_gather_grad_is_scatter_add():
    x = RNG.normal(size=(32, 8)).astype(np.float32)
    idx = np.array([0, 0, 1, 5, 31, -1], np.int32)
    g = jax.grad(lambda x: token_gather(x, jnp.asarray(idx)).sum())(
        jnp.asarray(x)
    )
    expect = np.zeros_like(x)
    for i in idx:
        if i >= 0:
            expect[i] += 1
    np.testing.assert_allclose(np.asarray(g), expect)


# --------------------------------------------------------------------------- #
# grouped FFN
# --------------------------------------------------------------------------- #


def _ffn_inputs(N, D, F, E, dtype=np.float32):
    x = (RNG.normal(size=(N, D)) * 0.1).astype(dtype)
    eid = RNG.integers(-1, E, size=(N,)).astype(np.int32)
    wg = (RNG.normal(size=(E, D, F)) * 0.05).astype(dtype)
    wu = (RNG.normal(size=(E, D, F)) * 0.05).astype(dtype)
    wd = (RNG.normal(size=(E, F, D)) * 0.05).astype(dtype)
    return map(jnp.asarray, (x, eid, wg, wu, wd))


def _routing(kind, N, E):
    """Expert ids for ``N`` rows: random (some ``-1``), or a named skew."""
    if kind == "random":
        return RNG.integers(-1, E, size=(N,))
    if kind == "empty_expert":      # expert 1 gets no row
        return RNG.choice([e for e in range(E) if e != 1], size=(N,))
    if kind == "one_expert":
        return np.full((N,), E - 1)
    if kind == "all_invalid":
        return np.full((N,), -1)
    if kind == "eighth_invalid":    # every row routed but the first eighth
        eid = RNG.integers(0, E, size=(N,))
        eid[: N // 8] = -1
        return eid
    if kind == "ragged":            # counts off the 128-row sub-tile
        eid = np.full((N,), -1)
        eid[:300], eid[300:301], eid[301:901] = 0, E - 1, 1
        return RNG.permutation(eid)
    raise ValueError(kind)


@pytest.mark.parametrize("N,D,F,E,bt,bf,routing", [
    (128, 32, 64, 2, 32, 32, "random"),
    (200, 64, 128, 4, 32, 64, "random"),
    (64, 16, 32, 8, 16, 16, "random"),
    (200, 32, 64, 4, 16, 32, "empty_expert"),
    (256, 32, 64, 4, 16, 32, "one_expert"),
    (1536, 16, 32, 3, 32, 32, "ragged"),
    (96, 32, 64, 2, 16, 32, "all_invalid"),
    (2048, 16, 32, 2, 64, 32, "random"),
    (256, 16, 32, 8, 16, 32, "eighth_invalid"),
    (130, 8, 8, 4, 16, 8, "eighth_invalid"),
    (700, 32, 64, 4, 64, 64, "random"),
])
def test_grouped_ffn_pallas(N, D, F, E, bt, bf, routing):
    """The public op (row tile, sub-tile and empty-tile skipping) runs the
    kernel in interpret mode at every size."""
    x, _, wg, wu, wd = _ffn_inputs(N, D, F, E)
    eid = jnp.asarray(_routing(routing, N, E), jnp.int32)
    y = grouped_ffn(x, eid, wg, wu, wd, block_tokens=bt, block_ffn=bf)
    ref = grouped_ffn_ref(x, eid, wg, wu, wd)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


def test_tile_plan():
    """The one-chip cell's exact routing (4096 tokens, top-2, hot ratio 0.9)
    at the §V-D widths, f32: 512-row tiles, 22 streamed of 24, 65 sub-tiles;
    a 32-row decode call keeps the 64-row floor."""
    counts = [3744, 635, 635, 636, 637, 635, 635, 635]
    plan = tile_plan(counts, 8192, 8, 4096, 16384, 4)
    assert plan.bm == 512
    assert plan.tiles == 22
    assert plan.rows == 8320 and plan.sub_tiles == 65
    assert plan.weight_bytes == 22 * 3 * 4096 * 16384 * 4   # 17.7 GB
    decode = tile_plan([20, 12], 32, 8, 4096, 16384, 4)
    assert decode.bm == 64 and decode.tiles == 2 and decode.rows == 128


def test_grouped_ffn_grad_matches_ref():
    """Gradients through the public op's VJP equal the reference's."""
    N, D, F, E = 96, 8, 8, 4
    x, _, wg, wu, wd = _ffn_inputs(N, D, F, E)
    eid = jnp.asarray(RNG.integers(0, E, size=(N,)), jnp.int32)

    def loss(fn, x, wg, wu, wd):
        return jnp.sum(fn(x, eid, wg, wu, wd) ** 2)

    op = functools.partial(grouped_ffn, block_tokens=16, block_ffn=8)
    grad = functools.partial(jax.grad, argnums=(1, 2, 3, 4))
    g = grad(loss)(op, x, wg, wu, wd)
    gr = grad(loss)(grouped_ffn_ref, x, wg, wu, wd)
    for a, b in zip(g, gr):
        assert bool(jnp.isfinite(a).all())
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-4)


def test_grouped_ffn_bf16():
    x, eid, wg, wu, wd = _ffn_inputs(96, 32, 64, 2, np.float32)
    x = x.astype(jnp.bfloat16)
    y = grouped_ffn(x, eid, wg, wu, wd, block_tokens=32, block_ffn=32)
    ref = grouped_ffn_ref(x, eid, wg, wu, wd)
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(ref, np.float32),
        rtol=2e-2, atol=2e-3,
    )


# --------------------------------------------------------------------------- #
# flash attention
# --------------------------------------------------------------------------- #


_FLASH_SHAPES = [(2, 4, 2, 256, 64), (1, 2, 1, 128, 32), (1, 8, 8, 256, 16)]


@pytest.mark.parametrize("B,H,Hkv,S,Dh,window,extra", [
    *(pytest.param(*shape, window, {}, id="-".join(map(str, (*shape, window))))
      for shape in _FLASH_SHAPES for window in (None, 96, 256)),
    # cases that leave dead or partial block pairs, and one that leaves none
    pytest.param(1, 2, 1, 512, 32, None, {}, id="s512-6-dead-of-16"),
    pytest.param(1, 2, 1, 512, 32, None, {"bq": 256}, id="bq256-bk128"),
    pytest.param(1, 2, 1, 256, 48, None, {"dv": 32}, id="dh48-dv32"),
    pytest.param(1, 2, 1, 256, 32, None, {"sk": 512, "q_offset": 256},
                 id="sq256-sk512-q_offset256"),
    pytest.param(1, 2, 1, 512, 32, 128, {}, id="window-kills-below"),
    pytest.param(1, 2, 1, 256, 32, None, {"causal": False}, id="non-causal"),
])
def test_flash_vs_ref(B, H, Hkv, S, Dh, window, extra):
    Sk, Dv = extra.get("sk", S), extra.get("dv", Dh)
    causal, q_offset = extra.get("causal", True), extra.get("q_offset", 0)
    q = (RNG.normal(size=(B, H, S, Dh)) * 0.3).astype(np.float32)
    k = (RNG.normal(size=(B, Hkv, Sk, Dh)) * 0.3).astype(np.float32)
    v = (RNG.normal(size=(B, Hkv, Sk, Dv)) * 0.3).astype(np.float32)
    o = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                        causal=causal, window=window, q_offset=q_offset,
                        bq=extra.get("bq", 128), bk=128, interpret=True)
    r = mha_ref(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                causal=causal, window=window, q_offset=q_offset)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                               rtol=2e-5, atol=2e-6)


def test_block_plan_moonlight_cell():
    """S 4096 in 128 x 128 blocks, causal: 528 pairs visited per (batch,
    head), the 32 on the diagonal masked, 496 left out of the grid."""
    assert block_plan(4096, 4096, 128, 128, True, None, 0) == (528, 32, 496)


@pytest.mark.parametrize("sq,sk", [(512, 512), (4096, 4096), (256, 1024)])
def test_block_plan_non_causal_keeps_full_grid(sq, sk):
    n = (sq // 128) * (sk // 128)
    assert block_plan(sq, sk, 128, 128, False, None, 0) == (n, 0, 0)
    assert list(zip(*live_pairs(sq, sk, 128, 128, False, None, 0))) == [
        (i, j) for i in range(sq // 128) for j in range(sk // 128)]


@pytest.mark.parametrize("sq,sk,bq,bk,causal,window,q_offset", [
    (512, 512, 128, 128, True, 128, 0),
    (512, 512, 128, 64, True, 200, 0),
    (1024, 1024, 256, 128, True, 300, 0),
    (256, 512, 128, 128, True, None, 256),
    (128, 512, 64, 128, True, 100, 384),
    (512, 512, 128, 128, False, 256, 0),
])
def test_block_plan_matches_brute_force(sq, sk, bq, bk, causal, window,
                                        q_offset):
    """Pairs kept and masked agree with the mask evaluated at every
    (query, key) position; steps run row by row, kv blocks in order."""
    qpos = np.arange(sq)[:, None] + q_offset
    kpos = np.arange(sk)[None, :]
    keep = np.ones((sq, sk), bool)
    if causal:
        keep &= kpos <= qpos
    if window is not None:
        keep &= kpos > qpos - window
    blocks = keep.reshape(sq // bq, bq, sk // bk, bk).transpose(0, 2, 1, 3)
    live = blocks.any(axis=(2, 3))
    partial = live & ~blocks.all(axis=(2, 3))
    assert block_plan(sq, sk, bq, bk, causal, window, q_offset) == (
        live.sum(), partial.sum(), (~live).sum())
    q_block, kv_block = live_pairs(sq, sk, bq, bk, causal, window, q_offset)
    rows, cols = np.nonzero(live)
    np.testing.assert_array_equal(q_block, rows)
    np.testing.assert_array_equal(kv_block, cols)


@pytest.mark.parametrize("window", [None, 100])
def test_chunked_attention_vs_ref(window):
    B, H, Hkv, Sq, Sk, Dh = 1, 4, 2, 64, 384, 32
    q = (RNG.normal(size=(B, H, Sq, Dh)) * 0.3).astype(np.float32)
    k = (RNG.normal(size=(B, Hkv, Sk, Dh)) * 0.3).astype(np.float32)
    v = (RNG.normal(size=(B, Hkv, Sk, Dh)) * 0.3).astype(np.float32)
    o = chunked_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                          causal=False, window=window, chunk=100)
    r = mha_ref(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                causal=False, window=window)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                               rtol=2e-5, atol=2e-6)


def test_flash_decode_offset():
    """q_offset (decode position) shifts causal masking correctly."""
    B, H, S, Dh = 1, 2, 128, 32
    q = (RNG.normal(size=(B, H, 8, Dh)) * 0.3).astype(np.float32)
    k = (RNG.normal(size=(B, H, S, Dh)) * 0.3).astype(np.float32)
    v = (RNG.normal(size=(B, H, S, Dh)) * 0.3).astype(np.float32)
    o = chunked_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                          causal=True, q_offset=64, chunk=64)
    r = mha_ref(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                causal=True, q_offset=64)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("H,Hkv,Sq,Sk,window,q_offset,grad", [
    pytest.param(4, 2, 256, 256, None, 0, False, id="causal-gqa-s256"),
    pytest.param(4, 2, 256, 256, 96, 0, False, id="window96"),
    pytest.param(2, 1, 256, 512, None, 256, False, id="sq256-sk512-offset"),
    pytest.param(2, 2, 64, 256, None, 192, False, id="sq64-mha-ref"),
    pytest.param(2, 1, 8, 4608, None, 4600, False, id="sq8-long-cache"),
    pytest.param(4, 2, 256, 256, None, 0, True, id="grad-causal-gqa"),
    pytest.param(2, 1, 256, 256, 96, 0, True, id="grad-window96"),
])
def test_attention_op_matches_ref(H, Hkv, Sq, Sk, window, q_offset, grad):
    """The public op on the CPU: queries of 128 rows or more take the flash
    kernel (interpret mode) and its VJP, shorter ones the chunked path
    (cache over two chunks) or the reference."""
    Dh = 32
    q, k, v = (jnp.asarray((RNG.normal(size=(1, h, s, Dh)) * 0.3)
                           .astype(np.float32))
               for h, s in ((H, Sq), (Hkv, Sk), (Hkv, Sk)))
    op = functools.partial(attention, causal=True, window=window,
                           q_offset=q_offset)
    ref = functools.partial(mha_ref, causal=True, window=window,
                            q_offset=q_offset)
    if not grad:
        np.testing.assert_allclose(np.asarray(op(q, k, v)),
                                   np.asarray(ref(q, k, v)),
                                   rtol=2e-5, atol=2e-6)
        return
    w = jnp.asarray(RNG.normal(size=(1, H, Sq, Dh)).astype(np.float32))
    g = jax.grad(lambda *a: jnp.sum(op(*a) * w), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(ref(*a) * w), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-5)


# --------------------------------------------------------------------------- #
# relay copy
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("n,d,bc", [(1024, 64, 256), (512, 128, 64),
                                    (256, 32, 256)])
@pytest.mark.parametrize("dtype", [np.float32, np.int32])
def test_relay_copy(n, d, bc, dtype):
    if dtype == np.int32:
        x = RNG.integers(-100, 100, size=(n, d)).astype(dtype)
    else:
        x = RNG.normal(size=(n, d)).astype(dtype)
    out = relay_copy(jnp.asarray(x), block_chunk=bc, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), x)


def test_relay_copy_slot_map_bit_exact():
    # ISSUE 10 satellite: the slot schedule is runtime data.  Any valid
    # schedule — parity, reversed parity, constant-slot — must produce a
    # bit-identical copy, because the slot only selects *which* staging
    # buffer the chunk passes through, never the data path.
    from repro.kernels.relay_copy.relay import parity_slot_map

    x = jnp.asarray(RNG.normal(size=(1024, 64)).astype(np.float32))
    n_chunks = 1024 // 256
    default = relay_copy(x, block_chunk=256, interpret=True)
    for slot_map in (
        parity_slot_map(n_chunks),
        1 - parity_slot_map(n_chunks),          # swapped slot assignment
        jnp.zeros((n_chunks,), dtype=jnp.int32),  # degenerate single slot
    ):
        out = relay_copy(x, slot_map, block_chunk=256, interpret=True)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(x))
        np.testing.assert_array_equal(np.asarray(out), np.asarray(default))


def test_relay_copy_slot_swap_does_not_retrace():
    # the point of the scalar-prefetched slot map: re-targeting staging
    # slots is a parameter update, not a recompile — one jit cache entry
    # serves every schedule of the same geometry (ROADMAP item 2)
    from repro.kernels.relay_copy.relay import (
        parity_slot_map,
        relay_copy as relay_jit,
    )

    relay_jit._clear_cache()
    x = jnp.asarray(RNG.normal(size=(512, 32)).astype(np.float32))
    n_chunks = 512 // 256
    relay_jit(x, parity_slot_map(n_chunks), block_chunk=256, interpret=True)
    relay_jit(x, 1 - parity_slot_map(n_chunks), block_chunk=256,
              interpret=True)
    relay_jit(x, jnp.ones((n_chunks,), dtype=jnp.int32), block_chunk=256,
              interpret=True)
    assert relay_jit._cache_size() == 1
