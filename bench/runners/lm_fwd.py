"""Runner: a MoE language model's forward over whole sequences, as chunked
prefill computes it, through the model's normal path
(``models.registry.build_model(cfg).forward``): embedding, the leading dense
layers, the MoE layers (latent attention, router, grouped FFN, combine,
shared experts) and the output head, on one chip that holds every expert.

The routing is the traffic's, with no near-tie: every MoE layer's router is
the same orthonormal vectors u_e, one per expert; each token id stands for
one set of experts, and its embedding is noise orthogonal to the u_e plus
``router_margin[j]`` times the vector of the set's j-th expert and
``router_floor`` times every other expert's.  The output projections are
projected off the u_e, so that what the layers add leaves that margin as it
is through every MoE layer, and scaled by ``residual_scale``.  The check
reads the reference's choices in every layer against the drawn sets, and
the logits against the reference's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import generate, harness


def model_config(c: dict):
    """The program's ModelConfig for a configuration file's published keys."""
    from repro.configs.base import ModelConfig

    return ModelConfig(
        name=c["name"], arch_type="moe", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["moe_intermediate_size"],
        vocab=c["vocab_size"], n_experts=c["n_routed_experts"],
        top_k=c["num_experts_per_tok"], n_shared_experts=c["n_shared_experts"],
        router_score=c["scoring_func"],
        routed_scale=c["routed_scaling_factor"],
        first_dense_layers=c["first_k_dense_replace"],
        d_ff_dense=c["intermediate_size"], kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        rope_theta=float(c["rope_theta"]), norm_eps=c["rms_norm_eps"])


def expert_sets(n_experts: int, top_k: int) -> np.ndarray:
    """[E * (E-1), k] expert sets in slot order: set ``e * (E-1) + r`` has
    first expert e and then the k-1 experts ``e + 1 + ((k-1) r + j) mod
    (E-1)`` (mod E), all distinct and never e."""
    E, k = n_experts, top_k
    e = np.repeat(np.arange(E), E - 1)[:, None]
    r = np.tile(np.arange(E - 1), E)[:, None]
    rest = (e + 1 + ((k - 1) * r + np.arange(k - 1)) % (E - 1)) % E
    return np.concatenate([e, rest], 1).astype(np.int32)


def fwd_flops_per_token(c: dict, seq: int) -> float:
    """Forward operations per token: latent-attention projections, causal
    attention averaged over the positions, the dense FFN, the router, the
    routed and the shared experts, and the output head.  Embedding, norms
    and RoPE are not counted."""
    d, H = c["hidden_size"], c["num_attention_heads"]
    rank, nope = c["kv_lora_rank"], c["qk_nope_head_dim"]
    rope, dv = c["qk_rope_head_dim"], c["v_head_dim"]
    proj = 2 * (d * H * (nope + rope) + d * (rank + rope)
                + rank * H * (nope + dv) + H * dv * d)
    attn = 2 * H * (nope + rope + dv) * (seq + 1) / 2
    ffn = lambda width: 3 * 2 * d * width
    moe = (2 * d * c["n_routed_experts"]
           + c["num_experts_per_tok"] * ffn(c["moe_intermediate_size"])
           + ffn(c["n_shared_experts"] * c["moe_intermediate_size"]))
    dense = c["first_k_dense_replace"]
    return (c["num_hidden_layers"] * (proj + attn)
            + dense * ffn(c["intermediate_size"])
            + (c["num_hidden_layers"] - dense) * moe
            + 2 * d * c["vocab_size"])


class Runner:
    def __init__(self, cell, seed: int, devices):
        self.cell, self.seed, self.devices = cell, seed, list(devices)
        self.failed = 0

    # -- set-up --------------------------------------------------------------
    def setup(self):
        from repro.models.registry import build_model
        from repro.sharding.context import ParallelContext

        c, t = self.cell.config, self.cell.traffic
        jax.config.update("jax_default_matmul_precision", c["matmul_precision"])
        self.cfg = model_config(c)
        dt = jnp.dtype(c["dtype"])
        model = build_model(self.cfg, ParallelContext(param_dtype=dt,
                                                      compute_dtype=dt))
        self.B, self.S = int(t["batch"]), int(t["seq"])
        self.T = self.B * self.S
        self.E, self.k = self.cfg.n_experts, self.cfg.top_k
        self.n_moe = self.cfg.n_layers - self.cfg.first_dense_layers
        self.one = jax.sharding.SingleDeviceSharding(self.devices[0])
        self.sets = expert_sets(self.E, self.k)
        if len(self.sets) > self.cfg.vocab:
            raise ValueError(f"{len(self.sets)} expert sets need as many "
                             f"token ids; the vocabulary has {self.cfg.vocab}")
        key = harness.seed_key(self.seed)
        self.params = self._weights(model, key)
        self.drawn, self.xs = [], []
        for j in range(int(t["payload_sets"])):
            set_ids, ids = self._tokens(j)
            self.drawn.append(self.sets[set_ids])        # [B, S, k]
            self.xs.append(jax.device_put(ids, self.one))
        self._log_routing()
        forward = model.forward
        self.fn = jax.jit(lambda p, x: forward(p, {"tokens": x})[0],
                          out_shardings=self.one)
        jax.block_until_ready(self.fn(self.params, self.xs[0]))

    def _weights(self, model, key):
        """The model's own init, then the router, its bias, the embedding
        and the output projections set as the module docstring says."""
        c, cfg = self.cell.config, self.cfg
        E, d, V = self.E, cfg.d_model, cfg.vocab
        amp = np.full((len(self.sets), E), c["router_floor"], np.float32)
        np.put_along_axis(amp, self.sets, np.asarray(c["router_margin"],
                                                     np.float32)[None], 1)
        scale = float(c["residual_scale"])

        def make(key, amp):
            k_init, k_u, k_noise, k_bias = jax.random.split(key, 4)
            p = model.init(k_init)
            dt = p["embed"].dtype
            u, _ = jnp.linalg.qr(jax.random.normal(k_u, (d, E), jnp.float32))

            def off_router(w):       # rows of w leave the u_e untouched
                w = w.astype(jnp.float32)
                return w - (w @ u) @ u.T

            noise = off_router(jax.random.normal(k_noise, (V, d)))
            sets = jnp.arange(V) % amp.shape[0]
            p["embed"] = (noise + amp[sets] @ u.T).astype(dt)
            b = p["blocks"]
            b["router"] = jnp.broadcast_to(u.astype(dt), b["router"].shape)
            b["router_bias"] = jax.random.uniform(
                k_bias, b["router_bias"].shape, jnp.float32,
                -c["router_bias_scale"], c["router_bias_scale"])
            outs = [(b["attn"], "wo"), (b, "wd"), (b["shared"], "wd")]
            if "dense_blocks" in p:
                dense = p["dense_blocks"]
                outs += [(dense["attn"], "wo"), (dense["mlp"], "wd")]
            for tree, name in outs:
                tree[name] = (off_router(tree[name]) * scale).astype(dt)
            return p

        return jax.jit(make, out_shardings=self.one)(key, jnp.asarray(amp))

    def _tokens(self, j: int):
        """Payload set ``j``: each token's expert set (first expert at the
        traffic's hot ratio, exact counts; the rest cycling per first
        expert) and a token id drawn among the ids of that set."""
        t = self.cell.traffic
        rng = np.random.default_rng([self.seed, j])
        first = generate.hot_expert_pairs(self.T, self.E, t["hot_ratio"], rng,
                                          hot_expert=t["hot_expert"])[:, 0]
        r = np.empty_like(first)
        for e in range(self.E):
            idx = np.nonzero(first == e)[0]
            r[idx] = np.arange(len(idx)) % (self.E - 1)
        set_ids = first * (self.E - 1) + r
        copies = self.cfg.vocab // len(self.sets)
        ids = set_ids + len(self.sets) * rng.integers(0, copies, self.T)
        shape = (self.B, self.S)
        return set_ids.reshape(shape), ids.astype(np.int32).reshape(shape)

    def _log_routing(self):
        from repro.kernels.grouped_ffn.ops import tile_plan

        per_expert = np.bincount(self.drawn[0].reshape(-1), minlength=self.E)
        hot = self.cell.traffic["hot_expert"]
        self.rows = int(per_expert.sum())
        plan = tile_plan(per_expert, self.rows, self.E, self.cfg.d_model,
                         self.cfg.d_ff, 2)
        harness.log(
            f"routing: {self.T} tokens, top-{self.k} of {self.E}; rows per "
            f"expert a layer: hot {per_expert[hot]}, others "
            f"{int(np.delete(per_expert, hot).min())} to "
            f"{int(np.delete(per_expert, hot).max())}; grouped FFN a layer "
            f"(bf16): {plan}")

    # -- the window ------------------------------------------------------------
    @property
    def hot_device_id(self) -> int:
        return self.devices[0].id

    def step(self, i: int):
        return self.fn(self.params, self.xs[i % len(self.xs)])

    def end_to_end(self, win) -> dict:
        return {"fwd_tokens_per_s": win.calls * self.T / win.seconds}

    def work(self) -> dict:
        from bench import work

        c = self.cell.config
        d, f = c["hidden_size"], c["moe_intermediate_size"]
        return {
            "tokens_per_call": self.T,
            "chips": 1,
            "fwd_flops_per_token": fwd_flops_per_token(c, self.S),
            "ffn_flops_per_call": self.n_moe * work.ffn_flops(self.rows, d, f),
            "ffn_bytes_per_call": self.n_moe * work.ffn_bytes(
                self.E, self.rows, d, f, 2),
        }

    # -- the reference -----------------------------------------------------------
    def _reference(self, mm_name: str):
        """jitted reference stages at ``mm_name`` precision; a layer's
        weights are cast to float32 inside its stage, one layer at a time."""
        ref, c = self.cell.reference(), self.cell.config
        mm = getattr(ref, mm_name)
        return {
            "embed": jax.jit(lambda p, t: p["embed"][t].astype(jnp.float32)),
            "dense": jax.jit(lambda b, i, x: ref.dense_layer(
                ref.layer(b, i), x, c, mm)),
            "moe": jax.jit(lambda b, i, x: ref.moe_layer(
                ref.layer(b, i), x, c, mm)),
            "head": jax.jit(lambda p, x: ref.head(p, x, c, mm)),
        }

    def _reference_seq(self, stages, tokens):
        """One sequence through the reference: (logits [S, V], the chosen
        experts of each MoE layer)."""
        p = self.params
        x = stages["embed"](p, tokens)
        for i in range(self.cfg.first_dense_layers):
            x = stages["dense"](p["dense_blocks"], i, x)
        chosen = []
        for i in range(self.n_moe):
            x, ch = stages["moe"](p["blocks"], i, x)
            chosen.append(ch)
        return stages["head"](p, x), chosen

    def control(self):
        """The reference, its matmul inputs rounded to fp8's 3-bit mantissa,
        in the program's place."""
        stages = self._reference("mm_fp8")
        self.fn = lambda p, x: jnp.stack(
            [self._reference_seq(stages, x[b])[0] for b in range(self.B)])

    # -- the check --------------------------------------------------------------
    def free(self):
        self.fn = None

    def check(self, samples) -> dict:
        """``out_gap``: widest gap of a sampled call's logits from the
        reference's, over the reference's largest logit.  ``route_miss``:
        the share of tokens, in the worst MoE layer, whose drawn experts are
        not the reference's choice."""
        stages = self._reference("mm_highest")
        parts = jax.jit(lambda y, r: (jnp.max(jnp.abs(y - r)),
                                      jnp.max(jnp.abs(r))))
        agree = np.ones(self.n_moe)
        worst = 0.0
        limit = float(self.cell.limits["out_gap"])
        for i, y in samples:
            n = i % len(self.xs)
            num = den = 0.0
            for b in range(self.B):
                r, chosen = self._reference_seq(stages, self.xs[n][b])
                drawn = np.sort(self.drawn[n][b], -1)
                for layer, ch in enumerate(chosen):
                    same = np.all(np.sort(np.asarray(ch), -1) == drawn, -1)
                    agree[layer] = min(agree[layer], float(same.mean()))
                gap, top = (float(v) for v in parts(y[b], r))
                num, den = max(num, gap), max(den, top)
            gap = num / den
            harness.log(f"answer of call {i}: out_gap {gap}")
            self.failed += int(not gap <= limit)
            worst = max(worst, gap) if np.isfinite(gap) else float("inf")
        harness.log(f"the reference router chooses the drawn top-{self.k} "
                    f"per MoE layer on {agree.tolist()} of tokens")
        return {"out_gap": (worst, limit),
                "route_miss": (1.0 - float(agree.min()),
                               float(self.cell.limits["route_miss"]))}
