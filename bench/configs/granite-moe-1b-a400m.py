"""Plain reference of the granite MoE language model as the program runs it,
its loss, its gradients and three AdamW steps, in jnp with no kernel.

Per layer: ``x += attn(rms_norm(x))``, ``x += moe(rms_norm(x))``.  Attention is
causal GQA with rotary positions on interleaved pairs of each head's
dimensions (the program's convention; the published model rotates halves,
which is the same model under a fixed permutation of the q and k columns).
The MoE router takes a softmax over all experts, keeps the top k and
renormalizes; every expert runs over every row and rows it was not chosen for
get gate 0.  The loss is the mean next-token negative log-likelihood plus
0.01 times the switch load-balance term (experts x sum(fraction routed x mean
probability)) averaged over layers.  Every matmul goes through ``mm``.
Granite's published embedding, attention, residual and logit multipliers are
not applied, because the program applies none (``PERF.md``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

#: query rows per block of the attention, so that a block's scores fit
Q_BLOCK = 512
HIGHEST = jax.lax.Precision.HIGHEST


def mm_highest(spec, a, b):
    """einsum in float32 (precision highest)."""
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def mm_high(spec, a, b):
    """einsum as three bf16 products (hi*hi + hi*lo + lo*hi), float32
    accumulation: the TPU's ``high`` precision, written out so that it means
    the same on every platform (``reduce_precision`` is kept by XLA where a
    round trip through bf16 may be optimised away)."""
    def split(v):
        hi = jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)
        lo = jax.lax.reduce_precision(v - hi, exponent_bits=8,
                                      mantissa_bits=7)
        return hi, lo

    (a_hi, a_lo), (b_hi, b_lo) = split(a), split(b)
    return (mm_highest(spec, a_hi, b_hi)
            + (mm_highest(spec, a_hi, b_lo) + mm_highest(spec, a_lo, b_hi)))


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta):
    """x [S, H, dh]: rotate each interleaved pair (2i, 2i+1) by pos * f_i."""
    s, _, dh = x.shape
    f = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * f
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     -1).reshape(x.shape)


def attention(p, x, cfg, mm):
    s = x.shape[0]
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg["hidden_size"] // H
    q = rope(mm("sd,de->se", x, p["wq"]).reshape(s, H, dh), cfg["rope_theta"])
    k = rope(mm("sd,de->se", x, p["wk"]).reshape(s, Hkv, dh),
             cfg["rope_theta"])
    v = mm("sd,de->se", x, p["wv"]).reshape(s, Hkv, dh)
    k = jnp.repeat(k, H // Hkv, axis=1)
    v = jnp.repeat(v, H // Hkv, axis=1)
    nq = min(Q_BLOCK, s)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * nq, nq)
        sc = mm("qhd,khd->hqk", qb, k) / math.sqrt(dh)
        qpos = i * nq + jnp.arange(nq)
        mask = jnp.arange(s)[None, :] <= qpos[:, None]
        w = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
        return mm("hqk,khd->qhd", w, v)

    o = jax.lax.map(block, jnp.arange(s // nq)).reshape(s, H * dh)
    return mm("se,ed->sd", o, p["wo"])


def moe(p, x, cfg, mm):
    E, k = cfg["num_local_experts"], cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(mm("nd,de->ne", x, p["router"]), axis=-1)
    w, idx = jax.lax.top_k(probs, k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    onehot = (idx[..., None] == jnp.arange(E)).astype(jnp.float32)   # [N,k,E]
    gates = jnp.sum(onehot * w[..., None], axis=1)                  # [N, E]
    frac = jnp.sum(onehot, axis=(0, 1)) / idx.size
    aux = E * jnp.sum(jax.lax.stop_gradient(frac) * probs.mean(0))
    h = jax.nn.silu(mm("nd,edf->nef", x, p["wg"])) * mm("nd,edf->nef", x,
                                                         p["wu"])
    y = mm("nef,efd->ned", h, p["wd"])
    return jnp.einsum("ned,ne->nd", y, gates, precision=HIGHEST), aux


def loss(params, tokens, labels, cfg, mm=mm_highest):
    """tokens, labels [S] (batch 1) -> scalar loss."""
    eps = cfg["rms_norm_eps"]

    @jax.checkpoint
    def layer(x, p):
        x = x + attention(p["attn"], rms_norm(x, p["ln1"], eps), cfg, mm)
        y, aux = moe(p, rms_norm(x, p["ln2"], eps), cfg, mm)
        return x + y, aux

    x, auxs = jax.lax.scan(layer, params["embed"][tokens], params["blocks"])
    x = rms_norm(x, params["final_norm"], eps)
    lp = jax.nn.log_softmax(mm("sd,dv->sv", x, params["lm_head"]), axis=-1)
    nll = -jnp.take_along_axis(lp, labels[:, None], axis=-1)[:, 0]
    return nll.mean() + 0.01 * jnp.mean(auxs)


def leaf_norms(tree) -> dict:
    return {jax.tree_util.keystr(path): jnp.sqrt(jnp.sum(jnp.square(a)))
            for path, a in jax.tree_util.tree_flatten_with_path(tree)[0]}


def adamw_steps(params, batches, cfg, mm=mm_highest):
    """AdamW (decoupled decay, bias correction, global-norm clipping, linear
    warm-up into a cosine) over ``batches``, as ``cfg['optimizer']`` states.

    Returns the losses, the leaf norms of the first clipped gradient, and the
    parameters after the last step."""
    o = cfg["optimizer"]
    b1, b2 = o["beta1"], o["beta2"]

    def lr_at(step):
        warm = min(step / max(o["warmup_steps"], 1), 1.0)
        t = min(max((step - o["warmup_steps"])
                    / max(o["total_steps"] - o["warmup_steps"], 1), 0.0), 1.0)
        frac = o["min_lr_frac"] + (1 - o["min_lr_frac"]) * 0.5 * (
            1 + math.cos(math.pi * t))
        return o["lr"] * warm * frac

    @jax.jit
    def grad_fn(p, tokens, labels):
        value, g = jax.value_and_grad(loss)(p, tokens, labels, cfg, mm)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                             for x in jax.tree.leaves(g)))
        scale = jnp.minimum(1.0, o["clip_norm"] / jnp.maximum(gnorm, 1e-9))
        return value, jax.tree.map(lambda x: x * scale, g)

    @jax.jit
    def update(p, g, m, v, lr, bc1, bc2):
        m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
        v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
        p = jax.tree.map(
            lambda p, m, v: p - lr * ((m / bc1) / (jnp.sqrt(v / bc2)
                                                   + o["eps"])
                                      + o["weight_decay"] * p), p, m, v)
        return p, m, v

    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, first = [], None
    for t, (tokens, labels) in enumerate(batches, start=1):
        value, g = grad_fn(params, tokens, labels)
        if first is None:
            first = {k: float(n) for k, n in
                     jax.jit(leaf_norms)(g).items()}
        params, m, v = update(params, g, m, v, lr_at(t), 1 - b1 ** t,
                              1 - b2 ** t)
        del g
        losses.append(float(value))
    return losses, first, params
