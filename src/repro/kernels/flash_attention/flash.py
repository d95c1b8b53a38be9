"""Pallas TPU kernel: flash attention with causal + sliding-window masking.

Online-softmax blocked attention for the dense architectures' prefill and
training paths, and — with ``window`` set — the sub-quadratic variant that
makes ``long_500k`` runnable for full-attention models (DESIGN.md §7).

Grid = (batch, heads, q_blocks, kv_blocks); kv is innermost/sequential so the
running (m, l, acc) statistics live in VMEM scratch across kv steps.  GQA is
expressed in the BlockSpec index_map (query head h reads kv head h // g) —
no repeated KV in HBM.  Values may be narrower than queries and keys (latent
attention's 128 against 192).  Block shapes default to (128, 128),
MXU-aligned.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

_NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
            *, scale, causal, window, q_offset, bq, bk, n_kv):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)          # [bq, dh]
    k = k_ref[0, 0].astype(jnp.float32)          # [bk, dh]
    v = v_ref[0, 0].astype(jnp.float32)          # [bk, dv]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                  # [bq, bk]

    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + q_offset
    kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_scr[...]                        # [bq, 1]
    m_new = jnp.maximum(m_prev[:, 0], s.max(axis=-1))[:, None]
    p = jnp.exp(s - m_new)                     # [bq, bk]
    alpha = jnp.exp(m_prev - m_new)            # [bq, 1]
    l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1)[:, None]
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_scr[...] = m_new

    @pl.when(ki == n_kv - 1)
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
    grid = (b, h, sq // bq, sk // bk)
    scale = 1.0 / (dh ** 0.5)

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        q_offset=q_offset, bq=bq, bk=bk, n_kv=sk // bk,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, dh), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bk, dh), lambda bi, hi, qi, ki: (bi, hi // g, ki, 0)),
            pl.BlockSpec((1, 1, bk, dv), lambda bi, hi, qi, ki: (bi, hi // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, bq, dv), lambda bi, hi, qi, ki: (bi, hi, qi, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, dv), jnp.float32),
        ],
        out_shape=jax.ShapeDtypeStruct((b, h, sq, dv), q.dtype),
        name="flash_attention",
        interpret=resolve_interpret(interpret),
    )(q, k, v)
