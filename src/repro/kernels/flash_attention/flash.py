"""Pallas TPU kernel: flash attention with causal + sliding-window masking.

Online-softmax blocked attention for the dense architectures' prefill and
training paths, and — with ``window`` set — the sub-quadratic variant that
makes ``long_500k`` runnable for full-attention models (DESIGN.md §7).

Grid = (batch, heads, live steps).  A (q-block, kv-block) pair is *dead*
when the mask removes every position in it (above the causal diagonal, or
wholly behind the window).  ``live_pairs`` lists the other pairs from the
static shapes, ``causal``, ``window`` and ``q_offset``, row by row with each
row's kv blocks in order, and their two tables (q-block and kv-block of
each step) are scalar-prefetched into the BlockSpec index maps: dead pairs
cost no fetch, no dot and no grid step.  The running (m, l, acc)
statistics live in VMEM scratch across a row's steps; its first step resets
them and its last writes the output.  Only *partial* pairs, which the
diagonal or the window edge crosses, build the iota mask.  ``block_plan``
counts the three kinds: a causal call at S 4096 in 128 x 128 blocks visits
528 of 1024 pairs per (batch, head), 32 of them partial; a non-causal,
unwindowed one visits all.  The tables take 8 bytes a step of the chip's
1 MiB of SMEM: on v5e a causal call without a window compiles up to
S 32768 (32896 steps), not at 65536.

GQA is expressed in the index maps (query head h reads kv head h // g) —
no repeated KV in HBM.  Values may be narrower than queries and keys
(latent attention's 128 against 192).  Block shapes default to (128, 128),
MXU-aligned.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

_NEG_INF = -1e30


def _dead(qi, ki, *, causal, window, q_offset, bq, bk):
    """The mask removes every position of block pair (qi, ki)."""
    dead = False
    if causal:
        dead = dead | (ki * bk > qi * bq + bq - 1 + q_offset)
    if window is not None:
        dead = dead | (ki * bk + bk - 1 <= qi * bq + q_offset - window)
    return dead


def _cut(qi, ki, *, causal, window, q_offset, bq, bk):
    """The mask removes some position of block pair (qi, ki).  Plain
    arithmetic, so it runs on the host's ints and arrays and on the
    kernel's traced scalars alike."""
    cut = False
    if causal:
        cut = cut | (ki * bk + bk - 1 > qi * bq + q_offset)
    if window is not None:
        cut = cut | (ki * bk <= qi * bq + bq - 1 + q_offset - window)
    return cut


def live_pairs(sq: int, sk: int, bq: int, bk: int, causal: bool,
               window: int | None, q_offset: int):
    """The grid's steps for one (batch, head): the q-block and kv-block of
    each (q-block, kv-block) pair the mask leaves live, row by row and kv
    blocks in order, as two int32 arrays.

    A q-row that no key reaches keeps its first kv block, masked whole, so
    its output is still written."""
    nq, nk = sq // bq, sk // bk
    live = ~np.broadcast_to(
        _dead(np.arange(nq)[:, None], np.arange(nk)[None, :], causal=causal,
              window=window, q_offset=q_offset, bq=bq, bk=bk), (nq, nk))
    live[~live.any(axis=1), 0] = True
    return tuple(a.astype(np.int32) for a in np.nonzero(live))


class BlockPlan(NamedTuple):
    live: int      # pairs visited per (batch, head): grid steps
    partial: int   # of those, pairs the mask cuts: only these build it
    dead: int      # pairs left out of the grid


def block_plan(sq: int, sk: int, bq: int, bk: int, causal: bool,
               window: int | None, q_offset: int) -> BlockPlan:
    """What ``flash_attention`` visits per (batch, head), counted from the
    same steps and the same test for a cut pair that the kernel runs."""
    q_block, kv_block = live_pairs(sq, sk, bq, bk, causal, window, q_offset)
    partial = np.broadcast_to(
        _cut(q_block, kv_block, causal=causal, window=window,
             q_offset=q_offset, bq=bq, bk=bk), q_block.shape)
    n = len(q_block)
    return BlockPlan(n, int(partial.sum()), (sq // bq) * (sk // bk) - n)


def _kernel(qtab, ktab, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
            *, scale, causal, window, q_offset, bq, bk, n_steps):
    step = pl.program_id(2)
    qi, ki = qtab[step], ktab[step]

    @pl.when((step == 0) | (qtab[jnp.maximum(step - 1, 0)] != qi))
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _accumulate(masked):
        q = q_ref[0, 0].astype(jnp.float32)          # [bq, dh]
        k = k_ref[0, 0].astype(jnp.float32)          # [bk, dh]
        v = v_ref[0, 0].astype(jnp.float32)          # [bk, dv]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                  # [bq, bk]

        if masked:
            qpos = (qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
                    + q_offset)
            kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            keep = jnp.ones((bq, bk), bool)
            if causal:
                keep &= kpos <= qpos
            if window is not None:
                keep &= kpos > qpos - window
            s = jnp.where(keep, s, _NEG_INF)

        m_prev = m_scr[...]                        # [bq, 1]
        m_new = jnp.maximum(m_prev[:, 0], s.max(axis=-1))[:, None]
        p = jnp.exp(s - m_new)                     # [bq, bk]
        alpha = jnp.exp(m_prev - m_new)            # [bq, 1]
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1)[:, None]
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    cut = _cut(qi, ki, causal=causal, window=window, q_offset=q_offset,
               bq=bq, bk=bk)
    if cut is False:                 # neither causal nor windowed
        _accumulate(False)
    else:
        pl.when(cut)(lambda: _accumulate(True))
        pl.when(jnp.logical_not(cut))(lambda: _accumulate(False))

    @pl.when((step == n_steps - 1)
             | (qtab[jnp.minimum(step + 1, n_steps - 1)] != qi))
    def _done():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "q_offset", "bq", "bk", "interpret"),
)
def flash_attention(
    q: jnp.ndarray,   # [B, H, Sq, Dh]
    k: jnp.ndarray,   # [B, Hkv, Sk, Dh]
    v: jnp.ndarray,   # [B, Hkv, Sk, Dv]
    *,
    causal: bool = True,
    window: int | None = None,
    q_offset: int = 0,
    bq: int = 128,
    bk: int = 128,
    interpret: bool | None = None,
) -> jnp.ndarray:
    b, h, sq, dh = q.shape
    hkv, sk, dv = k.shape[1], k.shape[2], v.shape[3]
    g = h // hkv
    bq = min(bq, sq)
    bk = min(bk, sk)
    assert sq % bq == 0 and sk % bk == 0
    q_block, kv_block = live_pairs(sq, sk, bq, bk, causal, window, q_offset)
    n_steps = len(q_block)
    scale = 1.0 / (dh ** 0.5)

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        q_offset=q_offset, bq=bq, bk=bk, n_steps=n_steps,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, h, n_steps),
        in_specs=[
            pl.BlockSpec((1, 1, bq, dh),
                         lambda bi, hi, s, qt, kt: (bi, hi, qt[s], 0)),
            pl.BlockSpec((1, 1, bk, dh),
                         lambda bi, hi, s, qt, kt: (bi, hi // g, kt[s], 0)),
            pl.BlockSpec((1, 1, bk, dv),
                         lambda bi, hi, s, qt, kt: (bi, hi // g, kt[s], 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, bq, dv), lambda bi, hi, s, qt, kt: (bi, hi, qt[s], 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, dv), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, sq, dv), q.dtype),
        name="flash_attention",
        interpret=resolve_interpret(interpret),
    )(jnp.asarray(q_block), jnp.asarray(kv_block), q, k, v)
