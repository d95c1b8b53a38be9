"""Pallas kernels of the MoE path, each with a plain-jnp reference (ref.py).

Every ``pallas_call`` takes ``interpret=None`` by default and resolves it with
:func:`resolve_interpret`: the Pallas interpreter runs where the platform is
the CPU, and never on a TPU.
"""

from __future__ import annotations

import re

import jax


def tpu_kernels(hlo_text: str) -> set:
    """Names of the Pallas kernels compiled into a program (its HLO text)."""
    return set(re.findall(r"%([A-Za-z_]\w*?)(?:\.\d+)? = [^\n]*"
                          r'custom_call_target="tpu_custom_call"', hlo_text))


def resolve_interpret(interpret: bool | None = None) -> bool:
    """Interpret mode for a ``pallas_call``: the CPU's default, refused on TPU."""
    platform = jax.default_backend()
    if interpret is None:
        return platform == "cpu"
    if interpret and platform == "tpu":
        raise ValueError("Pallas interpret mode requested on a TPU")
    return interpret
