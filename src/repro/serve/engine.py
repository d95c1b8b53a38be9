"""Batched serving engine: prefill + greedy/temperature decode loop.

``make_serve_step`` builds the single-token decode function (one new token
against a seq_len-deep cache).  ``make_prefill_scan`` rolls the per-token
prompt prefill into one ``lax.scan`` — a single jitted dispatch instead of
P host round-trips, bit-identical to stepping the prompt token by token (pinned by
``tests/test_serve_engine.py``).  ``ServeEngine`` drives both for real
batched requests (examples/ and the end-to-end serving smoke test).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import InputShape
from repro.models.registry import Model


def make_serve_step(model: Model):
    def serve_step(params, cache, token, pos):
        """token [B] int32, pos scalar int32 -> (logits [B, V], cache')."""
        return model.decode_step(params, cache, token, pos)
    return serve_step


def make_prefill_scan(model: Model):
    """Whole-prompt prefill as one scan over (token column, position).

    The scan body is exactly one ``decode_step`` — the same computation
    the per-token loop ran — so the final cache and last-position logits
    are bit-identical to P sequential steps, in one dispatch.
    """

    def prefill(params, cache, prompts):
        """prompts [B, P] int32 -> (last logits [B, V], cache')."""
        P = prompts.shape[1]

        def body(cache, tok_pos):
            tok, pos = tok_pos
            logits, cache = model.decode_step(params, cache, tok, pos)
            return cache, logits

        cache, logits_seq = jax.lax.scan(
            body, cache, (prompts.T, jnp.arange(P, dtype=jnp.int32))
        )
        return logits_seq[-1], cache

    return prefill


@dataclasses.dataclass
class ServeEngine:
    model: Model
    params: object
    max_len: int = 256

    def __post_init__(self):
        self._step = jax.jit(make_serve_step(self.model))
        self._prefill = jax.jit(make_prefill_scan(self.model))

    def prefill(self, prompts: np.ndarray):
        """Run [B, P] prompts through a fresh cache in one jitted scan
        (cache-correct for all families; bit-identical to stepping token by
        token).  Returns (last-position logits [B, V], cache)."""
        B, P = prompts.shape
        if P < 1:
            raise ValueError("prompts must carry at least one token")
        shape = InputShape("serve", self.max_len, B, "decode")
        cache = self.model.init_cache(B, shape)
        return self._prefill(
            self.params, cache, jnp.asarray(prompts, dtype=jnp.int32)
        )

    def generate(
        self,
        prompts: np.ndarray,          # [B, P] int32 prompt tokens
        n_new: int = 32,
        temperature: float = 0.0,
        seed: int = 0,
    ) -> np.ndarray:
        P = prompts.shape[1]
        logits, cache = self.prefill(prompts)
        rng = jax.random.PRNGKey(seed)
        out: List[np.ndarray] = []
        # autoregressive decode
        for j in range(n_new):
            if temperature > 0:
                rng, sub = jax.random.split(rng)
                tok = jax.random.categorical(
                    sub, logits.astype(jnp.float32) / temperature, axis=-1
                )
            else:
                tok = jnp.argmax(logits, axis=-1)
            out.append(np.asarray(tok))
            logits, cache = self._step(self.params, cache, tok.astype(jnp.int32),
                                       jnp.int32(P + j))
        return np.stack(out, axis=1)
