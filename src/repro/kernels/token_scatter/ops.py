"""Jit'd public wrapper for the token gather/pack kernel.

On the CPU the Pallas body runs in interpret mode (Python
execution, bit-identical semantics); gradients route through the jnp
reference via ``jax.custom_vjp`` since the gather's VJP is a scatter-add.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .ref import token_gather_ref
from .scatter import token_gather as _token_gather_pallas


@jax.custom_vjp
def token_gather(x: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    return _token_gather_pallas(x, idx)


def _fwd(x, idx):
    return token_gather(x, idx), (x.shape, idx)


def _bwd(res, g):
    (n, d), idx = res
    safe = jnp.clip(idx, 0, n - 1)
    gx = jnp.zeros((n, d), g.dtype).at[safe].add(
        jnp.where((idx >= 0)[:, None], g, 0)
    )
    return gx.astype(g.dtype), None


token_gather.defvjp(_fwd, _bwd)

__all__ = ["token_gather", "token_gather_ref"]
