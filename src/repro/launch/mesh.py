"""Mesh construction.

The "model" axis hosts tensor/expert parallelism and is the NIMBLE
orchestration axis; "data" (and "pod", where a mesh has one) is pure data
parallel (DESIGN.md §8).

A FUNCTION, not a module constant: importing this module never touches jax
device state (a process may still set XLA_FLAGS, e.g. a forced host device
count, before its first jax call).
"""

from __future__ import annotations

import jax


def make_test_mesh(n_devices: int | None = None, model: int | None = None):
    """Small mesh over whatever devices exist (selftests, examples)."""
    n = n_devices or len(jax.devices())
    model = model or n
    data = n // model
    import jax.sharding as jsh
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(jsh.AxisType.Auto,) * 2)
