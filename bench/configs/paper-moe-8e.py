"""Plain reference of the paper's MoE block (section V-D): one token at a time
in meaning, every expert over every row in practice.

Router: logits = x W_r, softmax over the experts, the top-k kept and
renormalized.  Each chosen expert applies a SwiGLU FFN,
``(silu(x Wg) * (x Wu)) Wd``, and the token's output is the gate-weighted
sum.  Here every expert runs over every row and rows it was not chosen for
get gate 0, which is the same function written without routing.  Every matmul
goes through ``dot``: ``dot_highest`` (float32) for the reference,
``dot_high`` (three bf16 passes) for the control.  Nothing of the program is
used.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def dot_highest(a, b):
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def dot_high(a, b):
    """float32 matmul as three bf16 products (hi*hi + hi*lo + lo*hi) with
    float32 accumulation: the TPU's ``high`` precision, written out so that
    it means the same on every platform (``reduce_precision`` is kept by XLA
    where a round trip through bf16 may be optimised away)."""
    def split(v):
        hi = jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)
        lo = jax.lax.reduce_precision(v - hi, exponent_bits=8,
                                      mantissa_bits=7)
        return hi, lo

    (a_hi, a_lo), (b_hi, b_lo) = split(a), split(b)
    return (dot_highest(a_hi, b_hi)
            + (dot_highest(a_hi, b_lo) + dot_highest(a_lo, b_hi)))


def route(x, router, top_k: int, dot=dot_highest):
    """[T, d] -> (gates [T, E]): each row's renormalized top-k weights."""
    probs = jax.nn.softmax(dot(x, router), axis=-1)
    w, idx = jax.lax.top_k(probs, top_k)
    w = w / w.sum(-1, keepdims=True)
    n_experts = router.shape[1]
    return jnp.sum(jnp.where(idx[..., None] == jnp.arange(n_experts),
                             w[..., None], 0.0), axis=1)


def moe_block(p, x, top_k: int, dot=dot_highest):
    """x [T, d] -> [T, d] with params {router, wg, wu, wd}."""
    gates = route(x, p["router"], top_k, dot)
    y = jnp.zeros(x.shape, jnp.float32)
    for e in range(p["wg"].shape[0]):
        h = jax.nn.silu(dot(x, p["wg"][e])) * dot(x, p["wu"][e])
        y = y + gates[:, e:e + 1] * dot(h, p["wd"][e])
    return y
