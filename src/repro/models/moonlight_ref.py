"""Plain reference of Moonlight-16B-A3B (DeepSeek-V3's layer), its forward
logits and the experts each MoE layer chooses, in jnp with no kernel.

It follows the published equations (config.json of
moonshotai/Moonlight-16B-A3B, ``model_type`` deepseek_v3) and reads the
program's parameter tree: ``dense_blocks`` (the leading dense layers), then
``blocks`` (the MoE layers), each stacked on a leading layer axis.

Per layer: ``x += attn(rms_norm(x))``, ``x += ffn(rms_norm(x))``.

Attention (latent, no query compression): q = x Wq split per head into
``nope`` and ``rope`` parts; [c, k_pe] = x Wkv_a; [k_nope, v] per head =
rms_norm(c) Wkv_b; one ``k_pe`` for all heads; rotary positions on q's rope
part and on k_pe only, on interleaved pairs (2i, 2i+1) of their dimensions;
causal softmax of q.k / sqrt(nope + rope); output (heads x v) Wo.  The
published code rotates the same pairs and writes them in half layout, a
fixed permutation of the rope columns of q and k that leaves every score as
it is.

FFN: the dense layers take one SwiGLU.  A MoE layer's router takes
s = sigmoid(h Wr) in float32, chooses the top k of s + bias, and weights
each chosen expert by its s, renormalized to sum 1 and times the routed
scaling factor: the bias chooses and never weights.  Every expert runs over
every row and rows it was not chosen for get gate 0.  The shared experts
are one SwiGLU over every row, added to the routed sum.  No sequence
auxiliary loss is computed.

Every matmul goes through ``mm``: ``mm_highest`` (float32) for the
reference, ``mm_fp8`` (inputs rounded to fp8's 3-bit mantissa) for the
control.  Nothing of the program is used.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

#: query rows per block of the attention, so that a block's scores fit
Q_BLOCK = 512
#: RMSNorm epsilon of the latent kv (``kv_a_layernorm``)
KV_NORM_EPS = 1e-6
HIGHEST = jax.lax.Precision.HIGHEST


def mm_highest(spec, a, b):
    """einsum in float32 (precision highest)."""
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def mm_fp8(spec, a, b):
    """einsum of the inputs rounded to a 3-bit mantissa (fp8 e4m3's, with
    float32's exponent range), float32 accumulation.  The rounded inputs are
    exact in bf16, so one bf16 pass gives every product exactly."""
    def r(v):
        return jax.lax.reduce_precision(v, exponent_bits=8,
                                        mantissa_bits=3).astype(jnp.bfloat16)

    return jnp.einsum(spec, r(a), r(b), preferred_element_type=jnp.float32)


def f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


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
    """x [S, D] -> [S, D]."""
    s = x.shape[0]
    H, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    theta = cfg["rope_theta"]
    q = mm("sd,de->se", x, p["wq"]).reshape(s, H, nope + dr)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], theta)], -1)
    ckv = mm("sd,de->se", x, p["wkv_a"])
    kv = mm("sr,re->se", rms_norm(ckv[:, :rank], p["kv_norm"], KV_NORM_EPS),
            p["wkv_b"]).reshape(s, H, -1)
    k_pe = rope(ckv[:, None, rank:], theta)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (s, H, dr))], -1)
    v = kv[..., nope:]
    nq = min(Q_BLOCK, s)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * nq, nq)
        sc = mm("qhd,khd->hqk", qb, k) / math.sqrt(nope + dr)
        qpos = i * nq + jnp.arange(nq)
        sc = jnp.where(jnp.arange(s)[None, :] <= qpos[:, None], sc, -jnp.inf)
        return mm("hqk,khd->qhd", jax.nn.softmax(sc, -1), v)

    o = jax.lax.map(block, jnp.arange(s // nq)).reshape(s, -1)
    return mm("se,ed->sd", o, p["wo"])


def swiglu(p, x, mm):
    h = jax.nn.silu(mm("sd,df->sf", x, p["wg"])) * mm("sd,df->sf", x,
                                                        p["wu"])
    return mm("sf,fd->sd", h, p["wd"])


def route(p, h, cfg, mm):
    """h [S, D] -> (gates [S, E], chosen experts [S, k])."""
    k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(mm("sd,de->se", h, p["router"]))
    _, chosen = jax.lax.top_k(s + p["router_bias"], k)
    w = jnp.take_along_axis(s, chosen, -1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * cfg["routed_scaling_factor"]
    gates = jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None],
                                 chosen].set(w)
    return gates, chosen


def moe(p, h, cfg, mm):
    """h [S, D] -> (routed sum + shared experts [S, D], chosen [S, k])."""
    gates, chosen = route(p, h, cfg, mm)

    def expert(y, e):
        wg, wu, wd, g = e
        return y + g[:, None] * swiglu({"wg": wg, "wu": wu, "wd": wd}, h,
                                       mm), None

    y, _ = jax.lax.scan(expert, jnp.zeros(h.shape, jnp.float32),
                        (p["wg"], p["wu"], p["wd"], gates.T))
    return y + swiglu(p["shared"], h, mm), chosen


def dense_layer(p, x, cfg, mm=mm_highest):
    """One leading dense layer; p in float32, x [S, D]."""
    eps = cfg["rms_norm_eps"]
    x = x + attention(p["attn"], rms_norm(x, p["ln1"], eps), cfg, mm)
    return x + swiglu(p["mlp"], rms_norm(x, p["ln2"], eps), mm)


def moe_layer(p, x, cfg, mm=mm_highest):
    """One MoE layer; p in float32, x [S, D] -> (x', chosen [S, k])."""
    eps = cfg["rms_norm_eps"]
    x = x + attention(p["attn"], rms_norm(x, p["ln1"], eps), cfg, mm)
    y, chosen = moe(p, rms_norm(x, p["ln2"], eps), cfg, mm)
    return x + y, chosen


def head(params, x, cfg, mm=mm_highest):
    """x [S, D] -> logits [S, V]."""
    x = rms_norm(x, params["final_norm"].astype(jnp.float32),
                 cfg["rms_norm_eps"])
    return mm("sd,dv->sv", x, params["lm_head"].astype(jnp.float32))


def layer(blocks, i):
    """Layer ``i`` of a stacked tree, in float32."""
    return f32(jax.tree.map(lambda a: a[i], blocks))


def forward(params, tokens, cfg, mm=mm_highest):
    """tokens [S] -> (logits [S, V], chosen experts [MoE layers, S, k]).
    Each layer's weights are cast to float32 as it is reached."""
    x = params["embed"][tokens].astype(jnp.float32)
    for i in range(cfg["first_k_dense_replace"]):
        x = dense_layer(layer(params["dense_blocks"], i), x, cfg, mm)
    chosen = []
    for i in range(cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]):
        x, c = moe_layer(layer(params["blocks"], i), x, cfg, mm)
        chosen.append(c)
    return head(params, x, cfg, mm), jnp.stack(chosen)
