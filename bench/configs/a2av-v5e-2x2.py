"""Plain reference of the All-to-Allv: ``ref_all_to_allv``'s semantics in jnp.

Source s's buffer for destination d is ``x[s * n + d]``, a [C, E] block of
which the first ``counts[s * n + d]`` chunks are live.  After the exchange,
destination d holds, at ``y[d * n + s]``, source s's live chunks in order and
zeros past them, and ``recv[d * n + s] = counts[s * n + d]``.  Run on the
global arrays, XLA moves the blocks; nothing of the program is used.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def all_to_allv(x, counts, n: int, dtype=None):
    """x [n*n, C, E], counts [n*n] -> (y [n*n, C, E], recv [n*n]).

    ``dtype`` rounds the payload to another type's precision on the way (the
    control); ``reduce_precision`` is kept by XLA where a round trip through
    that type may be optimised away."""
    _, C, E = x.shape
    c = counts.reshape(n, n)
    live = jnp.arange(C)[None, None, :] < c[..., None]          # [s, d, C]
    moved = x.reshape(n, n, C, E)
    if dtype is not None:
        fi = jnp.finfo(dtype)
        moved = jax.lax.reduce_precision(moved, exponent_bits=fi.nexp,
                                         mantissa_bits=fi.nmant)
    y = jnp.where(live[..., None], moved, jnp.zeros((), x.dtype))
    y = jnp.swapaxes(y, 0, 1).reshape(n * n, C, E)
    return y, c.T.reshape(n * n)
