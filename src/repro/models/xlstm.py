"""xLSTM blocks [arXiv:2405.04517]: alternating sLSTM and mLSTM layers.

* **mLSTM** — per-head matrix memory C ∈ R^{dh×dh} with stabilized
  exponential input/forget gating:

      m_t = max(logsig(f_t) + m_{t-1}, i_t)
      C_t = e^{logsig(f)+m_{t-1}-m_t} C_{t-1} + e^{i_t-m_t} k_t v_tᵀ
      n_t = e^{logsig(f)+m_{t-1}-m_t} n_{t-1} + e^{i_t-m_t} k_t
      h_t = (C_tᵀ q_t) / max(|n_tᵀ q_t|, e^{-m_t})

* **sLSTM** — scalar memory per channel with exponential gating and the
  same max-stabilizer.

Both train via ``lax.scan`` over time (the recurrent cell IS the layer, so
decode parity is exact by construction); the recurrence is O(S·dh²) —
sub-quadratic in sequence length, which is what runs ``long_500k``.  The
chunkwise-parallel mLSTM (TFLA-style) is a §Perf candidate, not required
for correctness.

Attention-free: NIMBLE inapplicable (DESIGN.md §7); built without.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.sharding.context import ParallelContext, SINGLE

from . import layers as L


def _dims(cfg: ModelConfig):
    H = cfg.n_heads
    dh = cfg.d_model // H
    return H, dh


# --------------------------------------------------------------------------- #
# mLSTM
# --------------------------------------------------------------------------- #


def init_mlstm(rng, cfg: ModelConfig, dtype):
    H, dh = _dims(cfg)
    d = cfg.d_model
    ks = jax.random.split(rng, 7)
    return {
        "norm": jnp.ones((d,), dtype),
        "wq": L.dense_init(ks[0], d, d, dtype),
        "wk": L.dense_init(ks[1], d, d, dtype),
        "wv": L.dense_init(ks[2], d, d, dtype),
        "wi": L.dense_init(ks[3], d, H, dtype, scale=0.02),
        "wf": L.dense_init(ks[4], d, H, dtype, scale=0.02),
        "bi": jnp.zeros((H,), jnp.float32),
        "bf": jnp.full((H,), 3.0, jnp.float32),
        "wg": L.dense_init(ks[5], d, d, dtype),
        "gate_norm": jnp.ones((d,), dtype),
        "wo": L.dense_init(ks[6], d, d, dtype),
    }


def init_mlstm_state(cfg: ModelConfig, batch: int):
    H, dh = _dims(cfg)
    return {
        "C": jnp.zeros((batch, H, dh, dh), jnp.float32),
        "n": jnp.zeros((batch, H, dh), jnp.float32),
        "m": jnp.full((batch, H), -30.0, jnp.float32),
    }


def _mlstm_cell(state, qkvif):
    q, k, v, ig, fg = qkvif       # q,k,v: [B,H,dh]; ig,fg: [B,H]
    C, n, m = state["C"], state["n"], state["m"]
    lf = jax.nn.log_sigmoid(fg)
    m_new = jnp.maximum(lf + m, ig)
    a = jnp.exp(lf + m - m_new)                    # [B,H]
    b = jnp.exp(ig - m_new)
    C = C * a[..., None, None] + b[..., None, None] * (
        k[..., :, None] * v[..., None, :]
    )
    n = n * a[..., None] + b[..., None] * k
    num = jnp.einsum("bhdp,bhd->bhp", C, q)
    den = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", n, q)),
                      jnp.exp(-m_new))
    h = num / den[..., None]
    return {"C": C, "n": n, "m": m_new}, h


def _mlstm_qkvif(p, h, cfg: ModelConfig):
    H, dh = _dims(cfg)
    B_, S, D = h.shape
    q = (h @ p["wq"]).reshape(B_, S, H, dh).astype(jnp.float32) / (dh ** 0.5)
    k = (h @ p["wk"]).reshape(B_, S, H, dh).astype(jnp.float32) / (dh ** 0.25)
    v = (h @ p["wv"]).reshape(B_, S, H, dh).astype(jnp.float32)
    ig = (h @ p["wi"]).astype(jnp.float32) + p["bi"]
    fg = (h @ p["wf"]).astype(jnp.float32) + p["bf"]
    return q, k, v, ig, fg


def mlstm_forward(p, x, cfg: ModelConfig, state=None):
    B_, S, D = x.shape
    h = L.rms_norm(x, p["norm"], cfg.norm_eps)
    q, k, v, ig, fg = _mlstm_qkvif(p, h, cfg)
    st = state or init_mlstm_state(cfg, B_)

    def step(st, inp):
        return _mlstm_cell(st, inp)

    st, ys = jax.lax.scan(
        step, st,
        (q.transpose(1, 0, 2, 3), k.transpose(1, 0, 2, 3),
         v.transpose(1, 0, 2, 3), ig.transpose(1, 0, 2), fg.transpose(1, 0, 2)),
    )
    y = ys.transpose(1, 0, 2, 3).reshape(B_, S, D).astype(x.dtype)
    og = jax.nn.sigmoid(h @ p["wg"])
    y = L.rms_norm(y * og, p["gate_norm"], cfg.norm_eps)
    return y @ p["wo"], st


def _mlstm_chunk_body(carry, inp, L: int):
    """One chunk of the chunkwise-parallel mLSTM (TFLA-style).

    Exact (not approximate) reformulation of the per-step cell: the carried
    (C, n, m) state uses the SAME stabilized convention as ``_mlstm_cell``,
    so chunked-vs-scan equality is bitwise up to float associativity
    (asserted in tests).  Per chunk the matrix memory is read/written once
    instead of L times — the §Perf memory-term optimization.

    Derivation: with Lf_t = Σ_{r<=t} logsig(f_r) (within-chunk) and
    g_j = i_j - Lf_j, the running stabilizer is m_t = Lf_t + u_t where
    u_t = max(m_in, cummax_{j<=t} g_j), and

        C_t = e^{m_in - u_t} C_in + Σ_{j<=t} e^{g_j - u_t} k_j v_j^T
        h_t = (C_t^T q_t) / max(|n_t . q_t|, e^{-m_t})
    """
    q, k, v, ig, lf = inp          # q,k,v: [B,L,H,dh]; ig,lf: [B,L,H]
    C_in, n_in, m_in = carry["C"], carry["n"], carry["m"]
    Lf = jnp.cumsum(lf, axis=1)                        # [B,L,H]
    g = ig - Lf
    u = jnp.maximum(m_in[:, None], jax.lax.cummax(g, axis=1))
    m = Lf + u                                          # global m_t
    # intra-chunk causal weights  W[t, j] = e^{g_j - u_t}  (j <= t)
    seg = g[:, None, :] - u[:, :, None]                # [B,Lt,Lj,H]
    li = jnp.arange(L)
    causal = li[:, None] >= li[None, :]
    seg = jnp.where(causal[None, :, :, None], seg, -jnp.inf)  # mask pre-exp
    W = jnp.exp(seg)
    scores = jnp.einsum("bthd,bjhd->btjh", q, k) * W   # [B,Lt,Lj,H]
    num = jnp.einsum("btjh,bjhd->bthd", scores, v)
    den = scores.sum(axis=2)                           # [B,Lt,H]
    # inter-chunk contribution from the carried state
    w_in = jnp.exp(m_in[:, None] - u)                  # [B,L,H]
    num = num + w_in[..., None] * jnp.einsum("bhdp,bthd->bthp", C_in, q)
    den = den + w_in * jnp.einsum("bhd,bthd->bth", n_in, q)
    h = num / jnp.maximum(jnp.abs(den), jnp.exp(-m))[..., None]
    # carry out (stabilized at m_L = Lf_L + u_L, the cell's convention)
    u_L = u[:, -1]                                     # [B,H]
    wj = jnp.exp(g - u_L[:, None])                     # [B,L,H]
    C_out = (jnp.exp(m_in - u_L)[..., None, None] * C_in
             + jnp.einsum("bjh,bjhd,bjhp->bhdp", wj, k, v))
    n_out = jnp.exp(m_in - u_L)[..., None] * n_in \
        + jnp.einsum("bjh,bjhd->bhd", wj, k)
    m_out = Lf[:, -1] + u_L
    return {"C": C_out, "n": n_out, "m": m_out}, h


def mlstm_forward_chunked(p, x, cfg: ModelConfig, state=None, chunk: int = 64):
    """Chunkwise-parallel mLSTM forward — same result as ``mlstm_forward``."""
    B_, S, D = x.shape
    H, dh = _dims(cfg)
    h = L.rms_norm(x, p["norm"], cfg.norm_eps)
    q, k, v, ig, fg = _mlstm_qkvif(p, h, cfg)
    lf = jax.nn.log_sigmoid(fg)
    Lc = min(chunk, S)
    nc = -(-S // Lc)
    pad = nc * Lc - S
    if pad:
        # pad with f = -inf-ish decays? simpler: pad with neutral inputs
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        ig = jnp.pad(ig, ((0, 0), (0, pad), (0, 0)), constant_values=-1e30)
        lf = jnp.pad(lf, ((0, 0), (0, pad), (0, 0)))
    rc = lambda a: a.reshape((B_, nc, Lc) + a.shape[2:]).transpose(
        (1, 0, 2) + tuple(range(3, a.ndim + 1)))
    st = state or init_mlstm_state(cfg, B_)
    st, ys = jax.lax.scan(
        functools.partial(_mlstm_chunk_body, L=Lc), st,
        (rc(q), rc(k), rc(v), rc(ig), rc(lf)),
    )
    y = ys.transpose(1, 0, 2, 3, 4).reshape(B_, nc * Lc, H * dh)[:, :S]
    y = y.astype(x.dtype)
    og = jax.nn.sigmoid(h @ p["wg"])
    y = L.rms_norm(y * og, p["gate_norm"], cfg.norm_eps)
    return y @ p["wo"], st


# --------------------------------------------------------------------------- #
# sLSTM
# --------------------------------------------------------------------------- #


def init_slstm(rng, cfg: ModelConfig, dtype):
    d = cfg.d_model
    ks = jax.random.split(rng, 6)
    return {
        "norm": jnp.ones((d,), dtype),
        "wz": L.dense_init(ks[0], d, d, dtype),
        "wi": L.dense_init(ks[1], d, d, dtype, scale=0.02),
        "wf": L.dense_init(ks[2], d, d, dtype, scale=0.02),
        "wo_gate": L.dense_init(ks[3], d, d, dtype, scale=0.02),
        "bf": jnp.full((d,), 3.0, jnp.float32),
        "up": L.dense_init(ks[4], d, 2 * d, dtype),
        "down": L.dense_init(ks[5], d, d, dtype),
    }


def init_slstm_state(cfg: ModelConfig, batch: int):
    d = cfg.d_model
    z = jnp.zeros((batch, d), jnp.float32)
    return {"c": z, "n": z, "m": z - 30.0, "h": z}


def _slstm_cell(state, zifo):
    z, ig, fg, og = zifo          # all [B, D]
    c, n, m, _ = state["c"], state["n"], state["m"], state["h"]
    lf = jax.nn.log_sigmoid(fg)
    m_new = jnp.maximum(lf + m, ig)
    a = jnp.exp(lf + m - m_new)
    b = jnp.exp(ig - m_new)
    c = c * a + b * jnp.tanh(z)
    n = n * a + b
    h = jax.nn.sigmoid(og) * c / jnp.maximum(n, 1e-6)
    return {"c": c, "n": n, "m": m_new, "h": h}, h


def _lin_scan_raw(a, u):
    """Prefix of y_t = a_t * y_{t-1} + u_t along axis=1 (no custom grad)."""

    def comb(e1, e2):
        a1, u1 = e1
        a2, u2 = e2
        return a1 * a2, u1 * a2 + u2

    _, y = jax.lax.associative_scan(comb, (a, u), axis=1)
    return y


@jax.custom_vjp
def linear_prefix(a, u):
    """First-order linear recurrence with a hand-written adjoint.

    Differentiating *through* ``associative_scan`` emits per-level pad/slice
    traffic.  The adjoint
    of y_t = a_t y_{t-1} + u_t is itself a REVERSE linear recurrence
        c̄_t = ȳ_t + a_{t+1} c̄_{t+1},   ā_t = c̄_t y_{t-1},   ū_t = c̄_t,
    so backward is one more associative_scan instead of an unrolled
    differentiated tree (§Perf iteration A3).
    """
    return _lin_scan_raw(a, u)


def _linear_prefix_fwd(a, u):
    y = _lin_scan_raw(a, u)
    return y, (a, y)


def _linear_prefix_bwd(res, g):
    a, y = res
    a_next = jnp.concatenate([a[:, 1:], jnp.zeros_like(a[:, :1])], axis=1)
    cbar = jnp.flip(
        _lin_scan_raw(jnp.flip(a_next, axis=1), jnp.flip(g, axis=1)), axis=1
    )
    y_prev = jnp.concatenate([jnp.zeros_like(y[:, :1]), y[:, :-1]], axis=1)
    return cbar * y_prev, cbar


linear_prefix.defvjp(_linear_prefix_fwd, _linear_prefix_bwd)


def _maxplus_scan_raw(s, v):
    """Prefix of m_t = max(m_{t-1} + s_t, v_t) along axis=1."""

    def comb(e1, e2):
        s1, v1 = e1
        s2, v2 = e2
        return s1 + s2, jnp.maximum(v1 + s2, v2)

    _, m = jax.lax.associative_scan(comb, (s, v), axis=1)
    return m


@jax.custom_vjp
def maxplus_prefix(s, v):
    """Max-plus recurrence with a hand-written adjoint (§Perf iteration A4).

    Forward picks carry (m_{t-1}+s_t) or fresh (v_t) per step; the adjoint
    routes m̄ backward along the carry-selection chain:
        c̄_t = m̄_t + sel_{t+1} c̄_{t+1}
    (a reverse linear recurrence with binary coefficients), then
    s̄_t = sel_t c̄_t and v̄_t = (1 - sel_t) c̄_t.
    """
    return _maxplus_scan_raw(s, v)


def _maxplus_fwd(s, v):
    m = _maxplus_scan_raw(s, v)
    return m, (s, v, m)


def _maxplus_bwd(res, g):
    s, v, m = res
    m_prev = jnp.concatenate(
        [jnp.full_like(m[:, :1], -jnp.inf), m[:, :-1]], axis=1
    )
    sel = (m_prev + s >= v).astype(g.dtype)      # 1 = carry selected
    sel_next = jnp.concatenate([sel[:, 1:], jnp.zeros_like(sel[:, :1])],
                               axis=1)
    cbar = jnp.flip(
        _lin_scan_raw(jnp.flip(sel_next, axis=1), jnp.flip(g, axis=1)), axis=1
    )
    return sel * cbar, (1.0 - sel) * cbar


maxplus_prefix.defvjp(_maxplus_fwd, _maxplus_bwd)


def slstm_forward_assoc(p, x, cfg: ModelConfig, state=None):
    """sLSTM via two ``associative_scan``s (§Perf memory-term optimization).

    This implementation's sLSTM gates depend only on the layer input (no
    h-feedback), so the recurrence factors into
      1. a max-plus prefix  m_t = max(m_{t-1} + lf_t, ig_t)
         (elements (s, v) combine as (s1+s2, max(v1+s2, v2))), and
      2. two linear prefixes c_t = a_t c_{t-1} + u_t, n_t likewise
         (elements (a, u) combine as (a1*a2, u1*a2 + u2)),
    both log-depth — no 4096-trip while loop, ~two full-array passes of HBM
    traffic instead of thousands of per-step round-trips.  Exact up to float
    associativity (tests assert allclose vs the cell scan).
    """
    B_, S, D = x.shape
    hpre = L.rms_norm(x, p["norm"], cfg.norm_eps)
    z = (hpre @ p["wz"]).astype(jnp.float32)
    ig = (hpre @ p["wi"]).astype(jnp.float32)
    fg = (hpre @ p["wf"]).astype(jnp.float32) + p["bf"]
    og = (hpre @ p["wo_gate"]).astype(jnp.float32)
    st = state or init_slstm_state(cfg, B_)
    lf = jax.nn.log_sigmoid(fg)                       # [B,S,D]

    # 1. stabilizer prefix (seed the carried m as a virtual step 0)
    s_el = jnp.concatenate([jnp.zeros((B_, 1, D)), lf], axis=1)
    v_el = jnp.concatenate([st["m"][:, None], ig], axis=1)
    m_all = maxplus_prefix(s_el, v_el)
    m_prev, m = m_all[:, :-1], m_all[:, 1:]
    a = jnp.exp(lf + m_prev - m)                      # decay  (<= 1)
    b = jnp.exp(ig - m)                               # input weight

    # 2. linear prefixes for c and n (seed carried state as step 0: a=1)
    ones = jnp.ones((B_, 1, D))
    a_el = jnp.concatenate([ones, a], axis=1)
    c_el = jnp.concatenate([st["c"][:, None], b * jnp.tanh(z)], axis=1)
    n_el = jnp.concatenate([st["n"][:, None], b], axis=1)
    c = linear_prefix(a_el, c_el)[:, 1:]
    n = linear_prefix(a_el, n_el)[:, 1:]

    h = jax.nn.sigmoid(og) * c / jnp.maximum(n, 1e-6)
    new_state = {"c": c[:, -1], "n": n[:, -1], "m": m[:, -1], "h": h[:, -1]}
    y = h.astype(x.dtype)
    y = jax.nn.gelu(y @ p["up"][:, :D]) * (y @ p["up"][:, D:])
    return y @ p["down"], new_state


def slstm_forward(p, x, cfg: ModelConfig, state=None):
    B_, S, D = x.shape
    hpre = L.rms_norm(x, p["norm"], cfg.norm_eps)
    z = (hpre @ p["wz"]).astype(jnp.float32)
    ig = (hpre @ p["wi"]).astype(jnp.float32)
    fg = (hpre @ p["wf"]).astype(jnp.float32) + p["bf"]
    og = (hpre @ p["wo_gate"]).astype(jnp.float32)
    st = state or init_slstm_state(cfg, B_)
    st, ys = jax.lax.scan(
        _slstm_cell, st,
        (z.transpose(1, 0, 2), ig.transpose(1, 0, 2),
         fg.transpose(1, 0, 2), og.transpose(1, 0, 2)),
    )
    y = ys.transpose(1, 0, 2).astype(x.dtype)
    # post-projection (GEGLU-ish up/down as in the xLSTM block)
    y = jax.nn.gelu(y @ p["up"][:, :D]) * (y @ p["up"][:, D:])
    return y @ p["down"], st


# --------------------------------------------------------------------------- #
# full model
# --------------------------------------------------------------------------- #


def is_slstm_layer(cfg: ModelConfig, i: int) -> bool:
    per = max(cfg.slstm_every, 1)
    return (i % per) == (per - 1)


def init(rng, cfg: ModelConfig, ctx: ParallelContext = SINGLE):
    dt = ctx.param_dtype
    k_e, k_b, k_h = jax.random.split(rng, 3)
    ks = jax.random.split(k_b, cfg.n_layers)
    blocks = []
    for i in range(cfg.n_layers):
        if is_slstm_layer(cfg, i):
            blocks.append(init_slstm(ks[i], cfg, dt))
        else:
            blocks.append(init_mlstm(ks[i], cfg, dt))
    return {
        "embed": L.embed_init(k_e, cfg.vocab, cfg.d_model, dt),
        "blocks": blocks,
        "final_norm": jnp.ones((cfg.d_model,), dt),
        "lm_head": L.dense_init(k_h, cfg.d_model, cfg.vocab, dt),
    }


def forward(params, tokens, cfg: ModelConfig, ctx: ParallelContext = SINGLE,
            *, last_only: bool = False, **_):
    x = params["embed"][tokens].astype(ctx.compute_dtype)
    for i, p in enumerate(params["blocks"]):
        if is_slstm_layer(cfg, i):
            fwd = slstm_forward_assoc if cfg.slstm_assoc else slstm_forward
            y, _ = fwd(p, x, cfg)
        elif cfg.mlstm_chunk > 0:
            y, _ = mlstm_forward_chunked(p, x, cfg, chunk=cfg.mlstm_chunk)
        else:
            y, _ = mlstm_forward(p, x, cfg)
        x = x + y
    if last_only:
        x = x[:, -1:]                    # §Perf B1: slice before lm_head
    return L.rms_norm(x, params["final_norm"], cfg.norm_eps) @ params["lm_head"]


def init_cache(cfg: ModelConfig, batch: int, cache_len: int,
               ctx: ParallelContext = SINGLE):
    caches = []
    for i in range(cfg.n_layers):
        if is_slstm_layer(cfg, i):
            caches.append(init_slstm_state(cfg, batch))
        else:
            caches.append(init_mlstm_state(cfg, batch))
    return caches


def decode_step(params, cache, token, pos, cfg: ModelConfig,
                ctx: ParallelContext = SINGLE):
    x = params["embed"][token][:, None, :].astype(ctx.compute_dtype)
    new_cache = []
    for i, (p, st) in enumerate(zip(params["blocks"], cache)):
        if is_slstm_layer(cfg, i):
            y, st = slstm_forward(p, x, cfg, state=st)
        else:
            y, st = mlstm_forward(p, x, cfg, state=st)
        x = x + y
        new_cache.append(st)
    lg = L.rms_norm(x, params["final_norm"], cfg.norm_eps) @ params["lm_head"]
    return lg[:, 0], new_cache
