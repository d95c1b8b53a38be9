"""MoE transformer (qwen3-moe / granite-moe / paper-moe-8e / moonlight).

Same GQA+RoPE skeleton as ``dense.py`` with the FFN replaced by a top-k
routed expert layer.  Where the config asks for them: latent attention
(``kv_lora_rank``), leading dense layers (``first_dense_layers``), shared
experts beside the routed ones (``n_shared_experts``) and DeepSeek-V3's
sigmoid router with a selection bias (``router_score="sigmoid"``).  Expert parallelism is where the paper's technique
lives: with ``ctx.ep_size > 1`` the dispatch/combine All-to-Allv runs
through :class:`repro.core.MoEDispatcher` (NIMBLE planner + scheduled
multi-path dataplane) inside ``shard_map`` over the model axis; single
device falls back to local grouped FFN (CPU smoke tests).

Router: softmax top-k with renormalized gates + switch-style load-balance
auxiliary loss; or sigmoid scores whose top-k after adding a per-expert bias
are chosen, weighted by their own scores renormalized and scaled, with no
auxiliary loss.  No capacity cap at the router (DeepSeek-style no-drop,
§V-D); the dispatcher's buffer capacity factor is the physical bound.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.moe_comm import COMBINE, MoECommConfig, MoEDispatcher
from repro.kernels.grouped_ffn.ops import grouped_ffn, grouped_ffn_ref
from repro.sharding.context import ParallelContext, SINGLE

from . import layers as L

#: trace scopes of the stages this module owns; each stage's ops carry the
#: name in their ``op_name`` metadata (``bench/scopes.py`` reads them)
ROUTE = "nimble.route"
DISPATCH = "nimble.dispatch"
FFN = "nimble.ffn"
SHARED = "nimble.shared"


def _init_attn(r, cfg: ModelConfig, dt):
    if cfg.kv_lora_rank:
        return L.init_mla(r, cfg, dt)
    return L.init_attention(r, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                            cfg.head_dim, dt, cfg.qkv_bias)


def init(rng, cfg: ModelConfig, ctx: ParallelContext = SINGLE):
    dt = ctx.param_dtype
    k_embed, k_blocks, k_head = jax.random.split(rng, 3)

    def init_block(r):
        r1, r2, r3 = jax.random.split(r, 3)
        ks = jax.random.split(r2, 3)
        p = {
            "ln1": jnp.ones((cfg.d_model,), dt),
            "attn": _init_attn(r1, cfg, dt),
            "ln2": jnp.ones((cfg.d_model,), dt),
            "router": L.dense_init(r3, cfg.d_model, cfg.n_experts, dt),
            "wg": jax.vmap(lambda k: L.dense_init(k, cfg.d_model, cfg.d_ff, dt))(
                jax.random.split(ks[0], cfg.n_experts)),
            "wu": jax.vmap(lambda k: L.dense_init(k, cfg.d_model, cfg.d_ff, dt))(
                jax.random.split(ks[1], cfg.n_experts)),
            "wd": jax.vmap(lambda k: L.dense_init(k, cfg.d_ff, cfg.d_model, dt))(
                jax.random.split(ks[2], cfg.n_experts)),
        }
        if cfg.router_score == "sigmoid":
            p["router_bias"] = jnp.zeros((cfg.n_experts,), jnp.float32)
        if cfg.n_shared_experts:
            p["shared"] = L.init_swiglu(jax.random.fold_in(r3, 1), cfg.d_model,
                                        cfg.n_shared_experts * cfg.d_ff, dt)
        return p

    def init_dense_block(r):
        r1, r2 = jax.random.split(r)
        return {
            "ln1": jnp.ones((cfg.d_model,), dt),
            "attn": _init_attn(r1, cfg, dt),
            "ln2": jnp.ones((cfg.d_model,), dt),
            "mlp": L.init_swiglu(r2, cfg.d_model, cfg.d_ff_dense, dt),
        }

    n_moe = cfg.n_layers - cfg.first_dense_layers
    params = {
        "embed": L.embed_init(k_embed, cfg.vocab, cfg.d_model, dt),
        "blocks": jax.vmap(init_block)(jax.random.split(k_blocks, n_moe)),
        "final_norm": jnp.ones((cfg.d_model,), dt),
        "lm_head": L.dense_init(k_head, cfg.d_model, cfg.vocab, dt),
    }
    if cfg.first_dense_layers:
        params["dense_blocks"] = jax.vmap(init_dense_block)(jax.random.split(
            jax.random.fold_in(k_blocks, 1), cfg.first_dense_layers))
    return params


def _router(p, xf: jnp.ndarray, cfg: ModelConfig):
    """xf [N, D] -> (top_idx [N,k], top_w [N,k], aux_loss scalar)."""
    with jax.named_scope(ROUTE):
        logits = (xf.astype(jnp.float32) @ p["router"].astype(jnp.float32))
        if cfg.router_score == "sigmoid":
            return _sigmoid_topk(p, logits, cfg)
        probs = jax.nn.softmax(logits, axis=-1)               # [N, E]
        top_w, top_idx = jax.lax.top_k(probs, cfg.top_k)
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
        # switch-style load-balance loss
        frac = jnp.zeros((cfg.n_experts,), jnp.float32).at[
            top_idx.reshape(-1)].add(1.0) / top_idx.size
        imp = probs.mean(0)
        aux = cfg.n_experts * jnp.sum(frac * imp)
        return top_idx.astype(jnp.int32), top_w, aux


def _sigmoid_topk(p, logits, cfg: ModelConfig):
    """DeepSeek-V3's router with one group (noaux_tc): the top-k of
    sigmoid(logits) + bias are chosen, and their sigmoid scores, renormalized
    to sum 1 and scaled by ``routed_scale``, weight them.  The bias chooses
    and never weights; there is no auxiliary loss."""
    scores = jax.nn.sigmoid(logits)                           # [N, E] f32
    _, top_idx = jax.lax.top_k(scores + p["router_bias"], cfg.top_k)
    top_w = jnp.take_along_axis(scores, top_idx, axis=-1)
    top_w = top_w / (top_w.sum(-1, keepdims=True) + 1e-20) * cfg.routed_scale
    return top_idx.astype(jnp.int32), top_w, jnp.float32(0.0)


def _shared_ffn(p, xf):
    """The shared experts: one SwiGLU of width ``n_shared_experts * d_ff``
    over every token."""
    with jax.named_scope(SHARED):
        return L.swiglu(p["shared"], xf)


def _moe_local(p, xf, top_idx, top_w, cfg: ModelConfig):
    """Single-device expert compute via the grouped FFN kernel."""
    n, d = xf.shape
    k = cfg.top_k
    with jax.named_scope(FFN):
        # slot-major ids [k, N]: a token's k rows are gathered, not repeated
        y = grouped_ffn(xf, top_idx.T, p["wg"], p["wu"], p["wd"],
                        block_tokens=64, block_ffn=min(128, cfg.d_ff))
    with jax.named_scope(COMBINE):
        y = (y.reshape(k, n, d) * top_w.T[..., None].astype(y.dtype)).sum(0)
    return y


def _moe_ep(p, xf, top_idx, top_w, cfg: ModelConfig,
            dispatcher: MoEDispatcher, token_valid=None):
    """Expert-parallel path (inside shard_map): NIMBLE dispatch/combine.
    ``token_valid`` marks the tokens this device owns (None: all)."""
    with jax.named_scope(DISPATCH):
        recv, e_local, state = dispatcher.dispatch(xf, top_idx,
                                                   token_valid=token_valid)
    n, C, ct, d = recv.shape
    with jax.named_scope(FFN):
        flat = recv.reshape(n * C * ct, d)
        eids = e_local.reshape(n * C * ct)
        y = grouped_ffn(flat, eids, p["wg"], p["wu"], p["wd"],
                        block_tokens=64, block_ffn=min(128, cfg.d_ff))
    out = dispatcher.combine(y.reshape(n, C, ct, d), state, top_w)
    return out


def make_moe_ffn(cfg: ModelConfig, ctx: ParallelContext):
    """Build the (possibly shard_mapped) MoE FFN apply function."""
    if ctx.ep_size <= 1:
        def apply(p, x):
            b, s, d = x.shape
            xf = x.reshape(-1, d)
            ti, tw, aux = _router(p, xf, cfg)
            y = _moe_local(p, xf, ti, tw, cfg)
            if cfg.n_shared_experts:
                y = y + _shared_ffn(p, xf)
            return y.reshape(b, s, d).astype(x.dtype), aux
        return apply

    comm_cfg = MoECommConfig(
        n_devices=ctx.ep_size,
        n_experts=cfg.n_experts,
        d_model=cfg.d_model,
        chunk_tokens=ctx.moe_chunk_tokens,
        capacity_factor=cfg.moe_capacity_factor,
        group_size=ctx.group_size,
        alt_frac=ctx.moe_alt_frac,
        mode=ctx.moe_mode,
        payload_dtype=ctx.compute_dtype,
    )
    if ctx.session is not None:
        # endpoint API: the session supplies cost model, planner config,
        # and (when adaptive) runtime telemetry wiring — see DESIGN.md §5
        dispatcher = ctx.session.moe_dispatcher(ctx.model_axis, comm_cfg)
    else:
        dispatcher = MoEDispatcher(ctx.model_axis, comm_cfg)
    from jax.sharding import PartitionSpec as P

    expert_spec = P(ctx.model_axis, None, None)
    mesh_sizes = dict(zip(ctx.mesh.axis_names, ctx.mesh.devices.shape))
    data_prod = 1
    for a in ctx.data_axes:
        data_prod *= mesh_sizes.get(a, 1)
    full_prod = data_prod * mesh_sizes.get(ctx.model_axis, 1)

    def _inner_full(wg, wu, wd, xf, ti, tw):
        pp = {"wg": wg, "wu": wu, "wd": wd}
        return _moe_ep(pp, xf, ti, tw, cfg, dispatcher)

    def _inner_masked(wg, wu, wd, xf, ti, tw):
        """Tokens replicated over the model axis (small decode batches):
        each model device owns a disjoint round-robin slice, routes only
        owned tokens, and the owned outputs are merged with a psum
        (DESIGN.md §8)."""
        pp = {"wg": wg, "wu": wu, "wd": wd}
        me = jax.lax.axis_index(ctx.model_axis)
        owned = (jnp.arange(xf.shape[0]) % ctx.ep_size) == me
        out = _moe_ep(pp, xf, ti, tw, cfg, dispatcher, token_valid=owned)
        return jax.lax.psum(out, ctx.model_axis)

    def apply(p, x):
        b, s, d = x.shape
        xf = x.reshape(-1, d)
        n_tok = b * s
        ti, tw, aux = _router(p, xf, cfg)
        if n_tok % full_prod == 0:
            tok_spec = P(ctx.token_axes, None)
            inner = _inner_full
        elif n_tok % data_prod == 0:
            tok_spec = P(tuple(ctx.data_axes), None)
            inner = _inner_masked
        else:
            tok_spec = P(None, None)     # tiny batches: fully replicated
            inner = _inner_masked
        y = jax.shard_map(
            inner,
            mesh=ctx.mesh,
            in_specs=(expert_spec, expert_spec, expert_spec,
                      tok_spec, tok_spec, tok_spec),
            out_specs=tok_spec,
            check_vma=False,
        )(p["wg"], p["wu"], p["wd"], xf, ti, tw)
        if cfg.n_shared_experts:    # on the token-sharded input, outside
            y = y + _shared_ffn(p, xf)
        return y.reshape(b, s, d).astype(x.dtype), aux

    return apply


def _attn_fwd(p, h, cfg: ModelConfig, window, pos_offset=0):
    if cfg.kv_lora_rank:
        return L.mla_forward(p, h, cfg, window=window, pos_offset=pos_offset)
    return L.attention_forward(
        p, h,
        n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim,
        rope_theta=cfg.rope_theta, causal=True, window=window,
        pos_offset=pos_offset,
    )


def _attn_decode(p, h, c, pos, cfg: ModelConfig):
    if cfg.kv_lora_rank:
        return L.mla_decode(p, h, c, pos, cfg)
    return L.attention_decode(
        p, h, c, pos,
        n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim,
        rope_theta=cfg.rope_theta,
    )


def _block_fwd(p, x, cfg: ModelConfig, ffn_apply, window, pos_offset=0):
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    x = x + _attn_fwd(p["attn"], h, cfg, window, pos_offset)
    h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    y, aux = ffn_apply(p, h)
    return x + y, aux


def _dense_ffn(p, h):
    """A leading dense layer's FFN, with the MoE apply's signature."""
    return L.swiglu(p["mlp"], h), jnp.float32(0.0)


def forward(
    params, tokens: jnp.ndarray, cfg: ModelConfig,
    ctx: ParallelContext = SINGLE, *, window=None, last_only: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """tokens [B, S] -> (logits [B, S, V], aux_loss scalar)."""
    x = params["embed"][tokens].astype(ctx.compute_dtype)
    moe_apply = make_moe_ffn(cfg, ctx)
    # no constrain_tokens here (dense.forward has one): the EP shard_map
    # shards tokens over data x model, and pinning the batch to the data
    # axes alone would reshard at every layer

    def scan_blocks(x, blocks, ffn_apply):
        def body(x, p):
            fn = _block_fwd
            if ctx.remat:
                fn = jax.checkpoint(fn, static_argnums=(2, 3, 4))
            return fn(p, x, cfg, ffn_apply, window)
        return jax.lax.scan(body, x, blocks)

    if cfg.first_dense_layers:
        x, _ = scan_blocks(x, params["dense_blocks"], _dense_ffn)
    x, auxs = scan_blocks(x, params["blocks"], moe_apply)
    if last_only:
        x = x[:, -1:]                    # §Perf B1: slice before lm_head
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x @ params["lm_head"], auxs.mean()


# -- serving ---------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, cache_len: int,
               ctx: ParallelContext = SINGLE):
    """One ring buffer per layer, dense layers first; latent attention keeps
    each head's full k and v."""
    def one(_):
        if cfg.kv_lora_rank:
            return L.init_kv_cache(
                batch, cfg.n_heads, cache_len,
                cfg.qk_nope_head_dim + cfg.qk_rope_head_dim,
                ctx.compute_dtype, v_dim=cfg.v_head_dim)
        return L.init_kv_cache(
            batch, cfg.n_kv_heads, cache_len, cfg.head_dim, ctx.compute_dtype
        )
    return jax.vmap(one)(jnp.arange(cfg.n_layers))


def decode_step(params, cache, token, pos, cfg: ModelConfig,
                ctx: ParallelContext = SINGLE):
    x = params["embed"][token][:, None, :].astype(ctx.compute_dtype)
    moe_apply = make_moe_ffn(cfg, ctx)
    k = cfg.first_dense_layers

    def decode_blocks(x, blocks, cache, ffn_apply):
        def body(x, pc):
            p, c = pc
            h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
            a, c = _attn_decode(p["attn"], h, c, pos, cfg)
            x = x + a
            h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
            y, _ = ffn_apply(p, h)
            return x + y, c
        return jax.lax.scan(body, x, (blocks, cache))

    if k:
        x, c_dense = decode_blocks(x, params["dense_blocks"],
                          jax.tree.map(lambda a: a[:k], cache), _dense_ffn)
        x, c_moe = decode_blocks(x, params["blocks"],
                        jax.tree.map(lambda a: a[k:], cache), moe_apply)
        cache = jax.tree.map(lambda a, b: jnp.concatenate([a, b]), c_dense,
                             c_moe)
    else:
        x, cache = decode_blocks(x, params["blocks"], cache, moe_apply)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return (x @ params["lm_head"])[:, 0], cache
