"""Model registry: uniform Model facade over the arch families.

``build_model(cfg, ctx)`` returns a :class:`Model` exposing
``init / forward / loss / init_cache / decode_step / input_specs`` with the
same signatures across all the architectures, so the launchers and
benchmarks are arch-agnostic.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import INPUT_SHAPES, InputShape, ModelConfig
from repro.sharding.context import ParallelContext, SINGLE

from . import dense, encdec, hybrid, moe, vlm, xlstm

_FAMILIES = {
    "dense": dense,
    "moe": moe,
    "hybrid": hybrid,
    "ssm": xlstm,
    "audio": encdec,
    "vlm": vlm,
}

# decode cache length policy: sub-quadratic archs keep O(1)/windowed state
_LONG = "long_500k"


@dataclasses.dataclass
class Model:
    cfg: ModelConfig
    ctx: ParallelContext
    mod: Any

    # -- parameters -------------------------------------------------------------
    def init(self, rng):
        return self.mod.init(rng, self.cfg, self.ctx)

    # -- forward / loss ----------------------------------------------------------
    def forward(self, params, batch: Dict[str, jnp.ndarray], *, window=None,
                last_only: bool = False):
        kwargs = {}
        if self.cfg.arch_type == "audio":
            kwargs["frames"] = batch["frames"]
        if self.cfg.arch_type == "vlm":
            kwargs["patches"] = batch["patches"]
        out = self.mod.forward(params, batch["tokens"], self.cfg, self.ctx,
                               window=window, last_only=last_only, **kwargs)
        if isinstance(out, tuple):
            return out              # (logits, aux)
        return out, jnp.float32(0.0)

    def loss(self, params, batch, *, window=None, aux_weight: float = 0.01):
        logits, aux = self.forward(params, batch, window=window)
        labels = batch["labels"]
        # vlm: logits cover patch prefix too; score text positions only
        if logits.shape[1] != labels.shape[1]:
            logits = logits[:, -labels.shape[1]:]
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(lp, labels[..., None], axis=-1)[..., 0]
        return nll.mean() + aux_weight * aux

    # -- serving ----------------------------------------------------------------
    def cache_len(self, shape: InputShape) -> int:
        if self.cfg.arch_type in ("ssm",):
            return 0                               # O(1) recurrent state
        if shape.name == _LONG:
            # dense/hybrid/moe/vlm run long context via sliding window
            return self.cfg.window or 4096
        if self.cfg.arch_type == "audio":
            return min(shape.seq_len, 448)         # whisper max target len
        return shape.seq_len

    def init_cache(self, batch: int, shape: InputShape):
        return self.mod.init_cache(
            self.cfg, batch, max(self.cache_len(shape), 1), self.ctx
        )

    def decode_step(self, params, cache, token, pos):
        return self.mod.decode_step(params, cache, token, pos, self.cfg,
                                    self.ctx)

    # -- input specs --------------------------------------------------------------
    def supports(self, shape: InputShape) -> bool:
        return shape.name not in self.cfg.skip_shapes

    def input_specs(self, shape: InputShape) -> Dict[str, jax.ShapeDtypeStruct]:
        """ShapeDtypeStruct stand-ins for every model input (no allocation)."""
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        if shape.kind in ("train", "prefill"):
            specs = {
                "tokens": jax.ShapeDtypeStruct((B, S), i32),
                "labels": jax.ShapeDtypeStruct((B, S), i32),
            }
            if cfg.arch_type == "audio":
                # decoder scores text; encoder consumes stub frames
                specs["tokens"] = jax.ShapeDtypeStruct((B, min(S, 448)), i32)
                specs["labels"] = jax.ShapeDtypeStruct((B, min(S, 448)), i32)
                specs["frames"] = jax.ShapeDtypeStruct(
                    (B, cfg.n_audio_frames, cfg.d_model), self.ctx.compute_dtype
                )
            if cfg.arch_type == "vlm":
                specs["patches"] = jax.ShapeDtypeStruct(
                    (B, cfg.n_patches, cfg.d_model), self.ctx.compute_dtype
                )
            return specs
        # decode: one token against a seq_len-deep cache
        return {
            "token": jax.ShapeDtypeStruct((B,), i32),
            "pos": jax.ShapeDtypeStruct((), i32),
        }


def build_model(cfg: ModelConfig, ctx: ParallelContext = SINGLE) -> Model:
    if cfg.arch_type not in _FAMILIES:
        raise KeyError(f"unknown arch_type {cfg.arch_type!r}")
    return Model(cfg, ctx, _FAMILIES[cfg.arch_type])
