"""Shared model layers: norms, RoPE, GQA and latent attention (+caches),
SwiGLU.

Functional style: ``init_*(rng, ...) -> params`` (nested dicts of arrays)
and pure apply functions.  Layer stacks are scanned (stacked params with a
leading layer axis) so 94-layer configs lower to a single compiled block.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.ops import attention as flash_attention

Params = Dict[str, jnp.ndarray]

#: trace scope of the latent attention, all of it (``bench/scopes.py``)
ATTN = "nimble.attn"
#: RMSNorm epsilon of the latent kv (DeepSeek-V3's ``kv_a_layernorm``)
KV_NORM_EPS = 1e-6


# --------------------------------------------------------------------------- #
# init helpers
# --------------------------------------------------------------------------- #


def dense_init(rng, d_in: int, d_out: int, dtype, scale: float | None = None):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return (jax.random.normal(rng, (d_in, d_out)) * scale).astype(dtype)


def embed_init(rng, vocab: int, d: int, dtype):
    return (jax.random.normal(rng, (vocab, d)) * 0.02).astype(dtype)


# --------------------------------------------------------------------------- #
# norms
# --------------------------------------------------------------------------- #


def rms_norm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def layer_norm(x, w, b, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return y.astype(x.dtype) * w + b


# --------------------------------------------------------------------------- #
# RoPE
# --------------------------------------------------------------------------- #


def rope_freqs(dh: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))


def apply_rope(x: jnp.ndarray, pos: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: [..., S, dh]; pos: [S] (or [..., S]) absolute positions."""
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta)                       # [dh/2]
    ang = pos[..., :, None].astype(jnp.float32) * freqs  # [..., S, dh/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., ::2], x[..., 1::2]
    y1 = x1 * cos - x2 * sin
    y2 = x1 * sin + x2 * cos
    out = jnp.stack([y1, y2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------- #
# GQA attention
# --------------------------------------------------------------------------- #


def init_attention(rng, d_model: int, n_heads: int, n_kv: int, head_dim: int,
                   dtype, qkv_bias: bool = False) -> Params:
    ks = jax.random.split(rng, 4)
    p = {
        "wq": dense_init(ks[0], d_model, n_heads * head_dim, dtype),
        "wk": dense_init(ks[1], d_model, n_kv * head_dim, dtype),
        "wv": dense_init(ks[2], d_model, n_kv * head_dim, dtype),
        "wo": dense_init(ks[3], n_heads * head_dim, d_model, dtype),
    }
    if qkv_bias:
        p["bq"] = jnp.zeros((n_heads * head_dim,), dtype)
        p["bk"] = jnp.zeros((n_kv * head_dim,), dtype)
        p["bv"] = jnp.zeros((n_kv * head_dim,), dtype)
    return p


def _project_qkv(p: Params, x, n_heads, n_kv, head_dim):
    b, s, _ = x.shape
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, n_heads, head_dim).transpose(0, 2, 1, 3)
    k = k.reshape(b, s, n_kv, head_dim).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, n_kv, head_dim).transpose(0, 2, 1, 3)
    return q, k, v


def attention_forward(
    p: Params,
    x: jnp.ndarray,              # [B, S, D]
    *,
    n_heads: int,
    n_kv: int,
    head_dim: int,
    rope_theta: float | None,
    causal: bool = True,
    window: Optional[int] = None,
    pos_offset: int = 0,
) -> jnp.ndarray:
    """Full-sequence attention (training / prefill path, flash kernel)."""
    b, s, d = x.shape
    q, k, v = _project_qkv(p, x, n_heads, n_kv, head_dim)
    if rope_theta is not None:
        pos = jnp.arange(s) + pos_offset
        q = apply_rope(q, pos, rope_theta)
        k = apply_rope(k, pos, rope_theta)
    o = flash_attention(q, k, v, causal, window, pos_offset)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, n_heads * head_dim)
    return o @ p["wo"]


# -- KV caches ------------------------------------------------------------------


def init_kv_cache(batch: int, n_kv: int, cache_len: int, head_dim: int,
                  dtype, v_dim: Optional[int] = None) -> Params:
    """Ring-buffer KV cache.  ``cache_len`` = window for SWA, seq for full;
    values are ``v_dim`` wide where that differs from the keys."""
    return {
        "k": jnp.zeros((batch, n_kv, cache_len, head_dim), dtype),
        "v": jnp.zeros((batch, n_kv, cache_len, v_dim or head_dim), dtype),
        "slot_pos": jnp.full((cache_len,), -1, jnp.int32),  # absolute pos
    }


def attention_decode(
    p: Params,
    x: jnp.ndarray,              # [B, 1, D] current token
    cache: Params,
    pos: jnp.ndarray,            # scalar int32 absolute position
    *,
    n_heads: int,
    n_kv: int,
    head_dim: int,
    rope_theta: float | None,
) -> Tuple[jnp.ndarray, Params]:
    """One decode step against a ring-buffer cache (RoPE at write time)."""
    q, k, v = _project_qkv(p, x, n_heads, n_kv, head_dim)   # [B,H,1,dh]
    if rope_theta is not None:
        ppos = pos[None] if pos.ndim == 0 else pos
        q = apply_rope(q, ppos, rope_theta)
        k = apply_rope(k, ppos, rope_theta)
    o, cache = _cache_attend(q, k, v, cache, pos)
    return o @ p["wo"], cache


def _cache_attend(q, k, v, cache: Params, pos) -> Tuple[jnp.ndarray, Params]:
    """Write k, v [B, Hkv, 1, *] into the ring buffer at ``pos`` and attend
    q [B, H, 1, dk] over the cache: -> ([B, 1, H * dv], cache')."""
    b, n_heads = q.shape[:2]
    W = cache["k"].shape[2]
    slot = jnp.mod(pos, W)                                   # ring write
    ck = jax.lax.dynamic_update_slice(cache["k"], k, (0, 0, slot, 0))
    cv = jax.lax.dynamic_update_slice(cache["v"], v, (0, 0, slot, 0))
    spos = cache["slot_pos"].at[slot].set(pos.astype(jnp.int32))

    g = n_heads // ck.shape[1]
    kk = jnp.repeat(ck, g, axis=1).astype(jnp.float32)       # [B,H,W,dk]
    vv = jnp.repeat(cv, g, axis=1).astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kk)
    s = s / math.sqrt(q.shape[-1])
    valid = (spos >= 0) & (spos <= pos)                      # [W]
    s = jnp.where(valid[None, None, None, :], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", w, vv).astype(q.dtype)
    o = o.transpose(0, 2, 1, 3).reshape(b, 1, n_heads * vv.shape[-1])
    return o, {"k": ck, "v": cv, "slot_pos": spos}


# --------------------------------------------------------------------------- #
# multi-head latent attention (DeepSeek-V3; no query compression)
# --------------------------------------------------------------------------- #


def init_mla(rng, cfg, dtype) -> Params:
    """q [D, H*(nope+rope)]; kv_a [D, rank+rope] (the latent and the one
    rope key all heads share); kv_b [rank, H*(nope+v)]; o [H*v, D]."""
    H, rank = cfg.n_heads, cfg.kv_lora_rank
    nope, rope, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
    ks = jax.random.split(rng, 4)
    return {
        "wq": dense_init(ks[0], cfg.d_model, H * (nope + rope), dtype),
        "wkv_a": dense_init(ks[1], cfg.d_model, rank + rope, dtype),
        "kv_norm": jnp.ones((rank,), dtype),
        "wkv_b": dense_init(ks[2], rank, H * (nope + dv), dtype),
        "wo": dense_init(ks[3], H * dv, cfg.d_model, dtype),
    }


def _mla_qkv(p: Params, x, pos, cfg):
    """x [B, S, D] at positions ``pos`` [S] -> q, k [B, H, S, nope+rope] and
    v [B, H, S, v]: RoPE on the rope parts only, k's shared by all heads."""
    b, s, _ = x.shape
    H, rank = cfg.n_heads, cfg.kv_lora_rank
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    q = (x @ p["wq"]).reshape(b, s, H, nope + rope).transpose(0, 2, 1, 3)
    ckv = x @ p["wkv_a"]
    kv = rms_norm(ckv[..., :rank], p["kv_norm"], KV_NORM_EPS) @ p["wkv_b"]
    kv = kv.reshape(b, s, H, -1).transpose(0, 2, 1, 3)
    k_pe = apply_rope(ckv[:, None, :, rank:], pos, cfg.rope_theta)
    q = jnp.concatenate(
        [q[..., :nope], apply_rope(q[..., nope:], pos, cfg.rope_theta)], -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (b, H, s, rope))], -1)
    return q, k, kv[..., nope:]


def mla_forward(p: Params, x: jnp.ndarray, cfg, *,
                window: Optional[int] = None,
                pos_offset: int = 0) -> jnp.ndarray:
    """Full-sequence latent attention (training / prefill path, flash
    kernel with value width v, scores scaled by 1/sqrt(nope+rope))."""
    b, s, _ = x.shape
    with jax.named_scope(ATTN):
        q, k, v = _mla_qkv(p, x, jnp.arange(s) + pos_offset, cfg)
        o = flash_attention(q, k, v, True, window, pos_offset)
        o = o.transpose(0, 2, 1, 3).reshape(b, s, -1)
        return o @ p["wo"]


def mla_decode(p: Params, x: jnp.ndarray, cache: Params, pos: jnp.ndarray,
               cfg) -> Tuple[jnp.ndarray, Params]:
    """One decode step; the ring buffer holds each head's full k and v, not
    the latent."""
    with jax.named_scope(ATTN):
        q, k, v = _mla_qkv(p, x, pos[None] if pos.ndim == 0 else pos, cfg)
        o, cache = _cache_attend(q, k, v, cache, pos)
        return o @ p["wo"], cache


# --------------------------------------------------------------------------- #
# SwiGLU MLP
# --------------------------------------------------------------------------- #


def init_swiglu(rng, d_model: int, d_ff: int, dtype) -> Params:
    ks = jax.random.split(rng, 3)
    return {
        "wg": dense_init(ks[0], d_model, d_ff, dtype),
        "wu": dense_init(ks[1], d_model, d_ff, dtype),
        "wd": dense_init(ks[2], d_ff, d_model, dtype),
    }


def swiglu(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    return (jax.nn.silu(x @ p["wg"]) * (x @ p["wu"])) @ p["wd"]


# --------------------------------------------------------------------------- #
# GELU MLP (whisper-style)
# --------------------------------------------------------------------------- #


def init_mlp(rng, d_model: int, d_ff: int, dtype) -> Params:
    ks = jax.random.split(rng, 2)
    return {
        "w1": dense_init(ks[0], d_model, d_ff, dtype),
        "b1": jnp.zeros((d_ff,), dtype),
        "w2": dense_init(ks[1], d_ff, d_model, dtype),
        "b2": jnp.zeros((d_model,), dtype),
    }


def mlp(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    return jax.nn.gelu(x @ p["w1"] + p["b1"]) @ p["w2"] + p["b2"]


def sinusoidal_positions(n: int, d: int) -> jnp.ndarray:
    pos = jnp.arange(n)[:, None].astype(jnp.float32)
    i = jnp.arange(d // 2)[None, :].astype(jnp.float32)
    ang = pos / jnp.power(10000.0, 2 * i / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
