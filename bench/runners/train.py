"""Runner: the training step, ``train.step.make_train_step`` over
``models.registry.build_model`` (forward, backward and AdamW), on one chip.

Set-up builds the compiled step and its state once and drives that same
object through the first three steps on three different batches; the window
goes on from there.  What is compared with the plain reference: each of the
three losses, the first gradient as the optimizer got it (from its first
moment after one step), and the parameters' change over the three steps.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import generate, harness, work

FIRST_STEPS = 3


def leaf_norms(tree) -> dict:
    return {jax.tree_util.keystr(path): jnp.sqrt(jnp.sum(jnp.square(a)))
            for path, a in jax.tree_util.tree_flatten_with_path(tree)[0]}


def diff_norms(a, b) -> dict:
    return leaf_norms(jax.tree.map(jnp.subtract, a, b))


def leaf_gaps(prog: dict, ref: dict, counted) -> dict:
    """Gap between the program's and the reference's norm of each leaf,
    relative to the larger of that leaf's reference norm and the median
    leaf's."""
    med = float(np.median([ref[k] for k in counted]))
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in counted}


def worst(gaps: dict, name: str) -> float:
    top = sorted(gaps.items(), key=lambda kv: -kv[1])[:3]
    harness.log(f"{name}: worst leaves {top}")
    return top[0][1]


class Runner:
    def __init__(self, cell, seed: int, devices):
        self.cell, self.seed, self.devices = cell, seed, list(devices)
        self.failed = 0

    def setup(self):
        from repro.configs.base import ModelConfig
        from repro.models.registry import build_model
        from repro.optim import adamw
        from repro.train.step import make_train_step

        c, t = self.cell.config, self.cell.traffic
        jax.config.update("jax_default_matmul_precision", c["matmul_precision"])
        self.c = c
        self.mcfg = ModelConfig(
            name=c["name"], arch_type="moe",
            n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
            n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"],
            d_ff=c["intermediate_size"], vocab=c["vocab_size"],
            n_experts=c["num_local_experts"], top_k=c["num_experts_per_tok"],
            rope_theta=float(c["rope_theta"]), norm_eps=c["rms_norm_eps"])
        self.B, self.S = int(t["batch"]), int(t["seq"])
        self.opt_cfg = adamw.AdamWConfig(**c["optimizer"])
        toks, labs = generate.lm_batches(
            int(t["batches"]), self.B, self.S, c["vocab_size"],
            np.random.default_rng([self.seed, 0x7B]))
        dev = self.devices[0]
        self.batches = [{"tokens": jax.device_put(a, dev),
                         "labels": jax.device_put(b, dev)}
                        for a, b in zip(toks, labs)]
        self.key = harness.seed_key(self.seed)
        model = build_model(self.mcfg)
        params = self.init_params()
        opt = jax.jit(adamw.init)(params)
        self.fn = jax.jit(make_train_step(model, self.opt_cfg),
                          donate_argnums=(0, 1))
        self.losses = []
        for i in range(FIRST_STEPS):
            params, opt, m = self.fn(params, opt, self.batches[i])
            self.losses.append(float(m["loss"]))
            if i == 0:
                b1 = self.opt_cfg.beta1
                self.grad_norms = {
                    k: float(v) / (1 - b1)
                    for k, v in jax.jit(leaf_norms)(opt.m).items()}
        p0 = self.init_params()
        self.change_norms = {k: float(v) for k, v in
                             jax.jit(diff_norms)(params, p0).items()}
        del p0
        self.params, self.opt = params, opt
        harness.log(f"first {FIRST_STEPS} losses {self.losses}")

    def init_params(self):
        """The model's parameters from the seed, in one call on the chip:
        projections N(0, 1/fan_in), embedding N(0, 0.02^2), norms 1."""
        c, L = self.c, self.c["num_hidden_layers"]
        d, F = c["hidden_size"], c["intermediate_size"]
        E, V = c["num_local_experts"], c["vocab_size"]
        H, Hkv = c["num_attention_heads"], c["num_key_value_heads"]
        dh = d // H
        shapes = {
            "embed": ((V, d), 0.02),
            "blocks": {
                "ln1": ((L, d), None),
                "attn": {"wq": ((L, d, H * dh), d), "wk": ((L, d, Hkv * dh), d),
                         "wv": ((L, d, Hkv * dh), d),
                         "wo": ((L, H * dh, d), H * dh)},
                "ln2": ((L, d), None),
                "router": ((L, d, E), d),
                "wg": ((L, E, d, F), d), "wu": ((L, E, d, F), d),
                "wd": ((L, E, F, d), F),
            },
            "final_norm": ((d,), None),
            "lm_head": ((d, V), d),
        }
        leaves, tdef = jax.tree.flatten(
            shapes, is_leaf=lambda x: isinstance(x, tuple))

        def make(key):
            out = []
            for i, (shape, fan) in enumerate(leaves):
                if fan is None:
                    out.append(jnp.ones(shape, jnp.float32))
                    continue
                std = fan if isinstance(fan, float) else 1 / math.sqrt(fan)
                out.append(std * jax.random.normal(jax.random.fold_in(key, i),
                                                   shape, jnp.float32))
            return jax.tree.unflatten(tdef, out)

        return jax.jit(make, out_shardings=jax.sharding.SingleDeviceSharding(
            self.devices[0]))(self.key)

    # -- the window ------------------------------------------------------------
    @property
    def hot_device_id(self) -> int:
        return self.devices[0].id

    def step(self, i: int):
        batch = self.batches[(FIRST_STEPS + i) % len(self.batches)]
        self.params, self.opt, m = self.fn(self.params, self.opt, batch)
        return m["loss"]

    def end_to_end(self, win) -> dict:
        return {"train_tokens_per_s": win.calls * self.B * self.S
                / win.seconds}

    def work(self) -> dict:
        c = self.c
        kw = dict(d=c["hidden_size"], heads=c["num_attention_heads"],
                  kv_heads=c["num_key_value_heads"],
                  head_dim=c["hidden_size"] // c["num_attention_heads"],
                  f=c["intermediate_size"], n_experts=c["num_local_experts"],
                  top_k=c["num_experts_per_tok"], vocab=c["vocab_size"],
                  n_layers=c["num_hidden_layers"], seq=self.S)
        rows = self.B * self.S * c["num_experts_per_tok"]
        L = c["num_hidden_layers"]
        return {
            "tokens_per_call": self.B * self.S,
            "chips": 1,
            "train_flops_per_token": work.lm_train_flops_per_token(**kw),
            "ffn_flops_per_call": L * work.ffn_flops(rows, kw["d"], kw["f"]),
            "ffn_bytes_per_call": L * work.ffn_bytes(
                kw["n_experts"], rows, kw["d"], kw["f"], 4),
            "flash_flops_per_call": L * work.causal_attn_flops(
                self.B, kw["heads"], self.S, kw["head_dim"]),
            "flash_bytes_per_call": L * work.attn_bytes(
                self.B, kw["heads"], kw["kv_heads"], self.S,
                kw["head_dim"], 4),
        }

    # -- the check ---------------------------------------------------------------
    def free(self):
        self.params = self.opt = self.fn = None

    def check(self, samples) -> dict:
        ref = self.cell.reference()
        batches = [(b["tokens"][0], b["labels"][0])
                   for b in self.batches[:FIRST_STEPS]]
        losses, grads, p3 = ref.adamw_steps(self.init_params(), batches,
                                            self.c)
        p0 = self.init_params()
        change = {k: float(v) for k, v in
                  jax.jit(ref.leaf_norms)(jax.tree.map(jnp.subtract, p3,
                                                       p0)).items()}
        del p0, p3
        med = float(np.median(list(grads.values())))
        counted = [k for k, v in grads.items() if v >= 1e-3 * med]
        left_out = sorted(set(grads) - set(counted))
        if left_out:
            harness.log(f"leaves left out (reference gradient under 1e-3 of "
                        f"the median leaf's): {left_out}")
        step_gaps = [abs(a - b) / abs(b) for a, b in zip(self.losses, losses)]
        harness.log(f"losses program {self.losses} reference {losses}; "
                    f"relative gaps {step_gaps}")
        lim = self.cell.limits
        out = {
            "loss_gap": (max(step_gaps), lim["loss_gap"]),
            "grad_gap": (worst(leaf_gaps(self.grad_norms, grads, counted),
                               "grad_gap"), lim["grad_gap"]),
            "change_gap": (worst(leaf_gaps(self.change_norms, change,
                                           counted), "change_gap"),
                           lim["change_gap"]),
        }
        self.failed = int(not all(v <= l for v, l in out.values()))
        return out
