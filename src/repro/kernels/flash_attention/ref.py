"""Pure-jnp oracle for causal / sliding-window attention (GQA)."""

from __future__ import annotations

import jax.numpy as jnp


def mha_ref(
    q: jnp.ndarray,   # [B, H, Sq, Dh]
    k: jnp.ndarray,   # [B, Hkv, Sk, Dh]
    v: jnp.ndarray,   # [B, Hkv, Sk, Dv]
    *,
    causal: bool = True,
    window: int | None = None,   # attend to [pos-window+1, pos]
    q_offset: int = 0,           # absolute position of q[0] (decode)
) -> jnp.ndarray:
    b, h, sq, dh = q.shape
    hkv = k.shape[1]
    g = h // hkv
    kk = jnp.repeat(k, g, axis=1).astype(jnp.float32)
    vv = jnp.repeat(v, g, axis=1).astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kk)
    s = s / jnp.sqrt(dh).astype(jnp.float32)
    qpos = jnp.arange(sq)[:, None] + q_offset
    kpos = jnp.arange(k.shape[2])[None, :]
    mask = jnp.ones((sq, k.shape[2]), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jnp.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vv).astype(q.dtype)
