"""Public attention op: the Pallas flash kernel (``flash.py``) for queries of
128 rows or more, in interpret mode off the TPU (``resolve_interpret``).

Shorter queries (decode) take ``chunked_attention``, the same online-softmax
algorithm as a ``lax.scan`` over kv chunks, when the cache is longer than two
chunks, and the quadratic reference otherwise.  The split is chosen from the
shapes alone, the same on every backend.

Gradients: the flash path's VJP differentiates ``chunked_attention``; the
jnp paths differentiate natively.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .flash import flash_attention as _flash
from .ref import mha_ref

_CHUNK = 2048


def chunked_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
    causal: bool = True, window: Optional[int] = None, q_offset: int = 0,
    chunk: int = _CHUNK,
) -> jnp.ndarray:
    """Online-softmax over kv chunks (lax.scan) — flash semantics in jnp."""
    b, h, sq, dh = q.shape
    hkv, sk, dv = k.shape[1], k.shape[2], v.shape[3]
    g = h // hkv
    nc = -(-sk // chunk)
    pad = nc * chunk - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kc = k.reshape(b, hkv, nc, chunk, dh).transpose(2, 0, 1, 3, 4)
    vc = v.reshape(b, hkv, nc, chunk, dv).transpose(2, 0, 1, 3, 4)
    qf = q.astype(jnp.float32) / (dh ** 0.5)
    qpos = jnp.arange(sq) + q_offset

    def step(carry, inp):
        m, l, acc, ci = carry
        kb, vb = inp                                  # [b,hkv,chunk,dh]
        kb = jnp.repeat(kb, g, axis=1).astype(jnp.float32)
        vb = jnp.repeat(vb, g, axis=1).astype(jnp.float32)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kb)
        kpos = ci * chunk + jnp.arange(chunk)
        mask = kpos[None, :] < sk
        if causal:
            mask = mask & (kpos[None, :] <= qpos[:, None])
        if window is not None:
            mask = mask & (kpos[None, :] > qpos[:, None] - window)
        s = jnp.where(mask[None, None], s, -1e30)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, vb)
        return (m_new, l, acc, ci + 1), None

    m0 = jnp.full((b, h, sq), -1e30, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    a0 = jnp.zeros((b, h, sq, dv), jnp.float32)
    (m, l, acc, _), _ = jax.lax.scan(step, (m0, l0, a0, jnp.int32(0)), (kc, vc))
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _attention_flash(q, k, v, causal, window, q_offset):
    return _flash(q, k, v, causal=causal, window=window, q_offset=q_offset)


def _fwd(q, k, v, causal, window, q_offset):
    return _attention_flash(q, k, v, causal, window, q_offset), (q, k, v)


def _bwd(causal, window, q_offset, res, g):
    q, k, v = res

    def f(q, k, v):
        return chunked_attention(q, k, v, causal=causal, window=window,
                                 q_offset=q_offset)

    _, vjp = jax.vjp(f, q, k, v)
    return vjp(g)


_attention_flash.defvjp(_fwd, _bwd)


def _on_mesh(q, k, v, causal, window, q_offset):
    """The Pallas kernel under the ambient mesh (``jax.set_mesh``).

    XLA cannot partition a Mosaic kernel, so on a mesh of several devices the
    kernel runs per shard inside ``shard_map``: attention is independent per
    sequence and per group of heads, so the batch is split over the mesh axes
    it divides, the heads (q and kv in the same contiguous blocks, which keeps
    the GQA grouping) over those left.  An axis of size > 1 that divides
    neither is refused rather than left to repeat the kernel on each replica.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1:
        return _attention_flash(q, k, v, causal, window, q_offset)
    b_axes, h_axes = [], []
    b_div, h_div = q.shape[0], k.shape[1]
    for name, size in mesh.shape.items():
        if name in mesh.manual_axes:
            continue
        if b_div % size == 0:
            b_axes.append(name)
            b_div //= size
        elif h_div % size == 0:
            h_axes.append(name)
            h_div //= size
        else:
            raise ValueError(
                f"flash attention: mesh axis {name!r} ({size}) divides "
                f"neither the batch ({q.shape[0]}) nor the kv heads "
                f"({k.shape[1]}) left to split")
    spec = P(tuple(b_axes) or None, tuple(h_axes) or None, None, None)
    return jax.shard_map(
        lambda q, k, v: _attention_flash(q, k, v, causal, window, q_offset),
        mesh=mesh, in_specs=(spec,) * 3, out_specs=spec, check_vma=False,
    )(q, k, v)


def attention(q, k, v, causal=True, window=None, q_offset=0):
    """[B,H,Sq,Dh] x [B,Hkv,Sk,Dh] x [B,Hkv,Sk,Dv] -> [B,H,Sq,Dv]; GQA via
    Hkv | H."""
    if q.shape[2] >= 128:
        return _on_mesh(q, k, v, causal, window, q_offset)
    if k.shape[2] > 2 * _CHUNK:
        return chunked_attention(q, k, v, causal=causal, window=window,
                                 q_offset=q_offset)
    return mha_ref(q, k, v, causal=causal, window=window, q_offset=q_offset)


__all__ = ["attention", "chunked_attention", "mha_ref"]
