"""Moonlight-16B-A3B through the normal path (``build_model`` -> ``moe``)
against its plain reference (``models/moonlight_ref.py``), at a tiny size on
the CPU: latent attention, a leading dense layer, 8 experts top-3 with 2
shared ones, and the sigmoid router whose bias chooses but never weights.
"""

import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import moe, moonlight_ref as ref
from repro.models.registry import build_model
from repro.sharding.context import SINGLE, ParallelContext

ROOT = Path(__file__).resolve().parents[1]
CFG = dataclasses.replace(
    get_config("moonlight-16b-a3b"), name="moonlight-tiny", n_layers=3,
    d_model=64, n_heads=4, n_kv_heads=4, d_ff=32, vocab=128, n_experts=8,
    top_k=3, d_ff_dense=96, kv_lora_rank=16, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16)


def ref_cfg(c):
    """The reference's published keys for a ModelConfig."""
    return {"num_attention_heads": c.n_heads, "kv_lora_rank": c.kv_lora_rank,
            "qk_nope_head_dim": c.qk_nope_head_dim,
            "qk_rope_head_dim": c.qk_rope_head_dim, "rope_theta": c.rope_theta,
            "num_experts_per_tok": c.top_k,
            "routed_scaling_factor": c.routed_scale,
            "rms_norm_eps": c.norm_eps,
            "first_k_dense_replace": c.first_dense_layers,
            "num_hidden_layers": c.n_layers}


def make_params(seed=0):
    """The model's own init, with a random selection bias in every layer."""
    params = build_model(CFG, SINGLE).init(jax.random.PRNGKey(seed))
    bias = jax.random.normal(jax.random.PRNGKey(seed + 1),
                             params["blocks"]["router_bias"].shape) * 0.1
    params["blocks"]["router_bias"] = bias
    return params


def tokens(batch, seq, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, CFG.vocab, (batch, seq), dtype=np.int32))


def ref_logits(params, toks):
    return jax.vmap(lambda t: ref.forward(params, t, ref_cfg(CFG))[0])(toks)


def rel_gap(y, r):
    return float(jnp.max(jnp.abs(y - r)) / jnp.max(jnp.abs(r)))


def test_registered_config_is_the_published_one():
    """The registered config and the benchmark's file agree on every width;
    the file cuts only the depth and the vocabulary."""
    c = get_config("moonlight-16b-a3b")
    pub = json.loads((ROOT / "bench/configs/moonlight-16b-a3b.json")
                     .read_text())
    assert pub["published"] == {"num_hidden_layers": c.n_layers,
                                "vocab_size": c.vocab}
    assert (c.d_model, c.n_heads, c.d_ff, c.d_ff_dense, c.n_experts,
            c.top_k, c.n_shared_experts, c.first_dense_layers,
            c.kv_lora_rank, c.qk_nope_head_dim, c.qk_rope_head_dim,
            c.v_head_dim, c.rope_theta, c.norm_eps, c.routed_scale) == (
        pub["hidden_size"], pub["num_attention_heads"],
        pub["moe_intermediate_size"], pub["intermediate_size"],
        pub["n_routed_experts"], pub["num_experts_per_tok"],
        pub["n_shared_experts"], pub["first_k_dense_replace"],
        pub["kv_lora_rank"], pub["qk_nope_head_dim"],
        pub["qk_rope_head_dim"], pub["v_head_dim"], pub["rope_theta"],
        pub["rms_norm_eps"], pub["routed_scaling_factor"])
    assert c.router_score == pub["scoring_func"] == "sigmoid"


def test_benchmark_reference_is_a_copy():
    assert ((ROOT / "bench/configs/moonlight-16b-a3b.py").read_text()
            == (ROOT / "src/repro/models/moonlight_ref.py").read_text())


@pytest.mark.parametrize("seq", [16, 128, 256])
def test_forward_matches_reference(seq):
    """The chip's kernels in interpret mode: at 16 positions attention takes
    the reference and each expert one row tile; at 128 one partial flash
    block; at 256 the flash grid skips its one dead block pair and the 1536
    routed rows give experts several tiles.  Both sides are float32 at
    "highest" and differ only in summation order, ~1e-6 of the largest
    logit; 1e-4 leaves room (a wrong gate moves it ~1e-1)."""
    params, toks = make_params(), tokens(2, seq)
    with jax.default_matmul_precision("highest"):
        y, aux = jax.jit(build_model(CFG, SINGLE).forward)(
            params, {"tokens": toks})
        r = jax.jit(ref_logits)(params, toks)
    assert float(aux) == 0.0
    assert rel_gap(y, r) < 1e-4, rel_gap(y, r)


def test_forward_scopes():
    """The latent attention and the shared experts carry their trace
    scopes."""
    text = jax.jit(build_model(CFG, SINGLE).forward).lower(
        make_params(), {"tokens": tokens(2, 16)}).as_text(debug_info=True)
    for scope in (moe.SHARED, "nimble.attn", moe.ROUTE, moe.FFN):
        assert scope in text, scope


def test_loss_gradients_match_reference():
    """Gradients of the mean next-token loss, leaf by leaf.  Float32 at
    "highest" on both sides; the program's grouped FFN differentiates
    through its own reference VJP.  1e-4 of each leaf's largest gradient
    (summation order, ~1e-6); the bias chooses only, so its gradient is 0
    on both sides."""
    params, toks = make_params(), tokens(2, 16)
    labels = jnp.roll(toks, -1, axis=1)
    model = build_model(CFG, SINGLE)

    def ref_loss(p):
        lp = jax.nn.log_softmax(ref_logits(p, toks), -1)
        return -jnp.take_along_axis(lp, labels[..., None], -1).mean()

    with jax.default_matmul_precision("highest"):
        g = jax.jit(jax.grad(lambda p: model.loss(
            p, {"tokens": toks, "labels": labels})))(params)
        gr = jax.jit(jax.grad(ref_loss))(params)
    flat = jax.tree_util.tree_leaves_with_path(g)
    for (path, a), b in zip(flat, jax.tree.leaves(gr)):
        scale = float(jnp.max(jnp.abs(b)))
        gap = float(jnp.max(jnp.abs(a - b)))
        assert gap <= 1e-4 * scale + 1e-9, (jax.tree_util.keystr(path), gap,
                                            scale)
    assert float(jnp.max(jnp.abs(g["blocks"]["router_bias"]))) == 0.0


def test_router_bias_chooses_but_does_not_weight():
    """Raising the bias of an expert that was not chosen puts it in the set;
    the weights stay the chosen experts' sigmoid scores, renormalized and
    scaled, with no bias in them."""
    rng = np.random.default_rng(3)
    d, E, k = CFG.d_model, CFG.n_experts, CFG.top_k
    p = {"router": jnp.asarray(rng.normal(size=(d, E)) / np.sqrt(d),
                               jnp.float32),
         "router_bias": jnp.zeros((E,), jnp.float32)}
    x = jnp.asarray(rng.normal(size=(5, d)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        idx0, _, aux = moe._router(p, x, CFG)
        left_out = int(np.setdiff1d(np.arange(E), np.asarray(idx0[0]))[0])
        bias = p["router_bias"].at[left_out].set(10.0)
        idx, w, _ = moe._router(dict(p, router_bias=bias), x, CFG)
        scores = jax.nn.sigmoid(x @ p["router"])
    assert float(aux) == 0.0
    assert left_out in np.asarray(idx[0])
    assert left_out not in np.asarray(idx0[0])
    s = np.take_along_axis(np.asarray(scores), np.asarray(idx), -1)
    np.testing.assert_allclose(
        np.asarray(w), s / s.sum(-1, keepdims=True) * CFG.routed_scale,
        rtol=1e-6)


def test_decode_matches_forward():
    """Decoding through the cache (each head's k and v, not the latent)
    gives the full forward's logits."""
    params, toks = make_params(), tokens(2, 8)
    model = build_model(CFG, SINGLE)
    with jax.default_matmul_precision("highest"):
        full, _ = model.forward(params, {"tokens": toks})
        cache = moe.init_cache(CFG, 2, 8)
        outs = []
        for i in range(8):
            lg, cache = model.decode_step(params, cache, toks[:, i],
                                          jnp.int32(i))
            outs.append(lg)
    # float32 at "highest"; softmax over the cache against the forward's
    # masked scores, summation order only
    assert rel_gap(jnp.stack(outs, 1), full) < 1e-4


def _ep_gap(batch, seq) -> float:
    """EP=4 over (data=1, model=4), NIMBLE mode, capacity factor 4 (no
    drops): the largest gap of its logits from the one-device forward."""
    from repro.launch.mesh import make_test_mesh
    from repro.sharding.specs import build_param_shardings

    cfg = dataclasses.replace(CFG, moe_capacity_factor=4.0)
    params, toks = make_params(), tokens(batch, seq)
    with jax.default_matmul_precision("highest"):
        y1, _ = jax.jit(build_model(cfg, SINGLE).forward)(
            params, {"tokens": toks})
        mesh = make_test_mesh(model=4)
        ctx = ParallelContext(mesh=mesh, data_axes=("data",), ep_size=4,
                              group_size=2, moe_mode="nimble")
        with jax.set_mesh(mesh):
            p = jax.device_put(params, build_param_shardings(params, ctx))
            y4, _ = jax.jit(build_model(cfg, ctx).forward)(
                p, {"tokens": toks})
    return rel_gap(y4, y1)


@pytest.fixture(scope="module")
def ep_gaps():
    """Both EP gaps from one process of 4 virtual CPU devices: 2 x 16
    tokens (sharded over the EP devices) and 1 x 6 (replicated)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [str(ROOT / "src"), os.environ.get("PYTHONPATH", "")]))
    p = subprocess.run([sys.executable, __file__], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    full, replicated = p.stdout.strip().splitlines()[-1].split()
    return float(full), float(replicated)


def test_ep_forward_matches_one_device(ep_gaps):
    """On 4 virtual CPU devices (a process of its own) the expert-parallel
    forward, shared experts outside the exchange, gives the one-device
    logits: float32 at "highest", so 1e-5 of the largest logit."""
    assert ep_gaps[0] < 1e-5, ep_gaps[0]


def test_ep_replicated_tokens_match_one_device(ep_gaps):
    """6 tokens, not a multiple of the 4 EP devices, stay replicated: each
    device routes the tokens it owns (round robin) and a psum merges the
    outputs.  Routing every copy instead would count each token 4 times."""
    assert ep_gaps[1] < 1e-5, ep_gaps[1]


if __name__ == "__main__":
    print(_ep_gap(2, 16), _ep_gap(1, 6))
