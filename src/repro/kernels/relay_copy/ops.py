"""Jit'd wrapper for the staged relay copy."""

from __future__ import annotations

from .ref import relay_copy_ref
from .relay import parity_slot_map
from .relay import relay_copy as _relay_pallas


def relay_copy(x, slot_map=None, *, block_chunk: int = 256):
    return _relay_pallas(x, slot_map, block_chunk=block_chunk)


__all__ = ["relay_copy", "relay_copy_ref", "parity_slot_map"]
