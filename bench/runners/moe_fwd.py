"""Runner: the MoE expert layer's forward step, ``models.moe.make_moe_ffn``.

On one chip the layer holds every expert and runs router -> grouped FFN ->
combine; on four it runs expert-parallel over a (data=1, model=4) mesh, with
NIMBLE dispatch and combine around the grouped FFN.  The router makes the
traffic's choices: its weights are unit vectors, one per expert, and each
token is noise plus ``router_margin`` times the vectors of the two experts
the traffic picked for it, so that those two win by a wide margin.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from bench import generate, harness, work


class Runner:
    def __init__(self, cell, seed: int, devices):
        self.cell, self.seed, self.devices = cell, seed, list(devices)
        self.failed = 0

    # -- set-up --------------------------------------------------------------
    def setup(self):
        from repro.configs.base import ModelConfig
        from repro.models.moe import make_moe_ffn
        from repro.sharding.context import ParallelContext

        c, t = self.cell.config, self.cell.traffic
        jax.config.update("jax_default_matmul_precision", c["matmul_precision"])
        self.E, self.d, self.F, self.k = (c["n_experts"], c["d_model"],
                                          c["d_ff"], c["top_k"])
        chips = len(self.devices)
        self.T = int(t["tokens"])
        mcfg = ModelConfig(
            name=c["name"], arch_type="moe", n_layers=1, d_model=self.d,
            n_heads=1, n_kv_heads=1, d_ff=self.F, vocab=1,
            n_experts=self.E, top_k=self.k,
            moe_capacity_factor=float(c["capacity_factor"]))
        if chips == 1:
            ctx = ParallelContext()
            one = jax.sharding.SingleDeviceSharding(self.devices[0])
            self.tok_sh = self.exp_sh = self.rep_sh = one
        else:
            mesh = jax.make_mesh(
                (1, chips), ("data", "model"), devices=self.devices,
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
            ctx = ParallelContext(
                mesh=mesh, data_axes=("data",), ep_size=chips,
                group_size=c["group_size"], moe_mode=c["mode"],
                moe_chunk_tokens=c["chunk_tokens"], moe_alt_frac=c["alt_frac"])
            self.tok_sh = NamedSharding(mesh, P(("data", "model"), None))
            self.exp_sh = NamedSharding(mesh, P("model", None, None))
            self.rep_sh = NamedSharding(mesh, P())
        self.mesh_chips = chips
        key = harness.seed_key(self.seed)
        self.params = self._weights(key)
        self.pairs, self.xs = [], []
        for j in range(int(t["payload_sets"])):
            pairs = self._pairs(j)
            self.pairs.append(pairs)
            self.xs.append(self._tokens(jax.random.fold_in(key, 1 + j), pairs))
        self._log_routing()
        apply = make_moe_ffn(mcfg, ctx)
        self.fn = jax.jit(lambda p, x: apply(p, x[None])[0][0],
                          out_shardings=self.tok_sh)
        jax.block_until_ready(self.fn(self.params, self.xs[0]))

    def _weights(self, key):
        E, d, F = self.E, self.d, self.F
        shard = {"router": self.rep_sh, "wg": self.exp_sh, "wu": self.exp_sh,
                 "wd": self.exp_sh}

        def make(key):
            kr, kg, ku, kd = jax.random.split(jax.random.fold_in(key, 0), 4)
            u = jax.random.normal(kr, (d, E), jnp.float32)
            return {
                "router": u / jnp.linalg.norm(u, axis=0, keepdims=True),
                "wg": jax.random.normal(kg, (E, d, F)) / np.sqrt(d),
                "wu": jax.random.normal(ku, (E, d, F)) / np.sqrt(d),
                "wd": jax.random.normal(kd, (E, F, d)) / np.sqrt(F),
            }

        return jax.jit(make, out_shardings=shard)(key)

    def _pairs(self, j: int) -> np.ndarray:
        """Each chip's tokens get the same multiset of expert pairs, in an
        order drawn from the seed."""
        t = self.cell.traffic
        per_chip = self.T // self.mesh_chips
        return np.concatenate([
            generate.hot_expert_pairs(
                per_chip, self.E, t["hot_ratio"],
                np.random.default_rng([self.seed, j, chip]),
                hot_expert=t["hot_expert"])
            for chip in range(self.mesh_chips)])

    def _tokens(self, key, pairs):
        margin = float(self.cell.traffic["router_margin"])

        def make(key, router, pairs):
            u = router.T                                   # [E, d]
            noise = jax.random.normal(key, (self.T, self.d), jnp.float32)
            return noise + margin * (u[pairs[:, 0]] + u[pairs[:, 1]])

        return jax.jit(make, out_shardings=self.tok_sh)(
            key, self.params["router"], jnp.asarray(pairs))

    def _log_routing(self):
        pairs = self.pairs[0]
        per_expert = np.bincount(pairs.reshape(-1), minlength=self.E)
        hot = self.cell.traffic["hot_expert"]
        epc = self.E // self.mesh_chips
        per_chip = per_expert.reshape(self.mesh_chips, epc).sum(1)
        self.valid_rows = int(per_chip[hot // epc])
        harness.log(
            f"routing: {self.T} tokens, top-{self.k}; assignments per expert "
            f"{per_expert.tolist()}; hot expert {hot} share "
            f"{per_expert[hot] / pairs.size}; per chip {per_chip.tolist()} "
            f"(hot chip share {per_chip.max() / pairs.size})")
        cap = int(np.ceil(self.T * self.k / self.mesh_chips / self.mesh_chips
                          * self.cell.config["capacity_factor"]))
        per_src = self.T // self.mesh_chips * self.k
        verdict = ("no assignment can be dropped" if cap >= per_src
                   else "drops possible")
        harness.log(f"dispatch capacity {cap} rows per destination per "
                    f"source; a source sends at most {per_src}: {verdict}")

    # -- the window ------------------------------------------------------------
    @property
    def hot_device_id(self) -> int:
        epc = self.E // self.mesh_chips
        return self.devices[self.cell.traffic["hot_expert"] // epc].id

    def step(self, i: int):
        return self.fn(self.params, self.xs[i % len(self.xs)])

    def end_to_end(self, win) -> dict:
        return {"fwd_tokens_per_s": win.calls * self.T / win.seconds}

    def work(self) -> dict:
        held = self.E // self.mesh_chips
        return {
            "tokens_per_call": self.T,
            "chips": self.mesh_chips,
            "fwd_flops_per_token": work.moe_fwd_flops_per_token(
                self.d, self.F, self.E, self.k),
            "ffn_flops_per_call": work.ffn_flops(self.valid_rows, self.d,
                                                 self.F),
            "ffn_bytes_per_call": work.ffn_bytes(held, self.valid_rows,
                                                 self.d, self.F, 4),
        }

    # -- the check --------------------------------------------------------------
    def free(self):
        self.fn = None

    def check(self, samples) -> dict:
        """Widest gap of a sampled answer from the reference, relative to the
        reference's largest value."""
        ref = self.cell.reference()
        params = jax.device_put(self.params, self.rep_sh)
        f_ref = jax.jit(lambda p, x: ref.moe_block(p, x, self.k),
                        out_shardings=self.tok_sh)
        gap_fn = jax.jit(lambda y, r: jnp.max(jnp.abs(y - r))
                         / jnp.max(jnp.abs(r)))
        gates = jax.jit(lambda x, r: ref.route(x, r, self.k))(
            self.xs[0], params["router"])
        chosen = np.asarray(jnp.argsort(-gates, axis=1)[:, :self.k])
        agree = np.mean(np.sort(chosen, 1) == np.sort(self.pairs[0], 1))
        harness.log(f"the reference router's top-{self.k} matches the drawn "
                    f"pairs on {agree} of choices")
        limit = float(self.cell.limits["out_gap"])
        worst = 0.0
        for i, y in samples:
            r = f_ref(params, self.xs[i % len(self.xs)])
            gap = float(gap_fn(y, r))
            harness.log(f"answer of call {i}: out_gap {gap}")
            self.failed += int(not gap <= limit)
            worst = max(worst, gap) if np.isfinite(gap) else float("inf")
        return {"out_gap": (worst, limit)}
