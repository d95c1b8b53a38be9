"""Pallas TPU kernel: chunkwise-parallel mLSTM scan.

The carried (C, n, m) state of the chunked reformulation (models/xlstm.py)
lives in VMEM scratch across the sequential chunk dimension, so HBM sees
only q/k/v/gate inputs and the h output — one pass each way.

Grid = (batch, heads, chunks); chunks innermost/sequential.  Per step the
kernel computes the exact stabilized chunk recurrence of
``xlstm._mlstm_chunk_body`` (same math, same carry convention):

    Lf = cumsum(lf),  g = ig - Lf,  u_t = max(m_in, cummax g)
    W[t, j] = e^{g_j - u_t} (j <= t)
    h = (qk^T.W @ v + e^{m_in - u}.C_in^T q) / max(|den|, e^{-(Lf + u)})
    C' = e^{m_in - u_L} C + (w.k)^T v, ...

Cumulatives are computed with an in-register doubling scan (log2 L shifted
maximum/add steps) — no lax.cum* dependency inside the kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

_NEG = -1e30


def _doubling_scan(x, op, L):
    """Inclusive prefix scan along axis 0 of [L, ...] via doubling."""
    shift = 1
    while shift < L:
        rolled = jnp.concatenate(
            [jnp.full_like(x[:shift], 0.0 if op is jnp.add else _NEG),
             x[:-shift]], axis=0)
        x = op(x, rolled)
        shift *= 2
    return x


def _kernel(q_ref, k_ref, v_ref, ig_ref, lf_ref, o_ref,
            c_scr, n_scr, m_scr, *, L, dh, n_chunks):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        c_scr[...] = jnp.zeros_like(c_scr)
        n_scr[...] = jnp.zeros_like(n_scr)
        m_scr[...] = jnp.full_like(m_scr, -30.0)

    q = q_ref[0, 0].astype(jnp.float32)            # [L, dh]
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    ig = ig_ref[0, 0].astype(jnp.float32)          # [L, 1]
    lf = lf_ref[0, 0].astype(jnp.float32)          # [L, 1]

    m_in = m_scr[0, 0]
    Lf = _doubling_scan(lf, jnp.add, L)            # [L, 1]
    g = ig - Lf
    u = jnp.maximum(m_in, _doubling_scan(g, jnp.maximum, L))  # [L, 1]
    m = Lf + u

    # intra-chunk causal weights W[t, j] = e^{g_j - u_t}
    seg = g[None, :, 0] - u[:, None, 0]            # [Lt, Lj]
    ti = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    tj = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    seg = jnp.where(ti >= tj, seg, _NEG)
    W = jnp.exp(seg)

    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * W                                           # [Lt, Lj]
    num = jax.lax.dot_general(
        scores, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)         # [Lt, dh]
    den = scores.sum(axis=1, keepdims=True)         # [Lt, 1]

    # inter-chunk contribution from the carried state
    w_in = jnp.exp(m_in - u)                        # [L, 1]
    C_in = c_scr[...]                               # [dh(d), dh(p)]
    n_in = n_scr[...]                               # [1, dh]
    num += w_in * jax.lax.dot_general(
        q, C_in, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    den += w_in * jax.lax.dot_general(
        q, n_in, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    h = num / jnp.maximum(jnp.abs(den), jnp.exp(-m))
    o_ref[0, 0] = h.astype(o_ref.dtype)

    # carry out, stabilized at m_L = Lf_L + u_L (the cell convention)
    u_L = u[L - 1, 0]
    wj = jnp.exp(g - u_L)                           # [L, 1]
    decay = jnp.exp(m_in - u_L)
    c_scr[...] = decay * C_in + jax.lax.dot_general(
        k * wj, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    n_scr[...] = decay * n_in + (k * wj).sum(axis=0, keepdims=True)
    m_scr[0, 0] = Lf[L - 1, 0] + u_L


@functools.partial(
    jax.jit, static_argnames=("chunk", "interpret"),
)
def mlstm_scan(
    q: jnp.ndarray,    # [B, H, S, dh]  (pre-scaled as in _mlstm_qkvif)
    k: jnp.ndarray,    # [B, H, S, dh]
    v: jnp.ndarray,    # [B, H, S, dh]
    ig: jnp.ndarray,   # [B, H, S]
    lf: jnp.ndarray,   # [B, H, S]  log-sigmoid forget gate
    *,
    chunk: int = 64,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Returns h [B, H, S, dh]; state starts at the zero/m=-30 init."""
    b, hh, s, dh = q.shape
    L = min(chunk, s)
    assert s % L == 0, "sequence must divide the chunk size"
    nc = s // L
    grid = (b, hh, nc)
    kernel = functools.partial(_kernel, L=L, dh=dh, n_chunks=nc)
    spec3 = pl.BlockSpec((1, 1, L, dh), lambda bi, hi, ci: (bi, hi, ci, 0))
    spec1 = pl.BlockSpec((1, 1, L, 1), lambda bi, hi, ci: (bi, hi, ci, 0))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec3, spec3, spec3, spec1, spec1],
        out_specs=spec3,
        scratch_shapes=[
            pltpu.VMEM((dh, dh), jnp.float32),   # C
            pltpu.VMEM((1, dh), jnp.float32),    # n
            pltpu.VMEM((1, 1), jnp.float32),     # m
        ],
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=resolve_interpret(interpret),
    )(q, k, v, ig[..., None], lf[..., None])
