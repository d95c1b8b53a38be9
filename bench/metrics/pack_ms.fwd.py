"""Device milliseconds per forward step packing the dispatch
(``nimble.pack``: stable sort, counts, scatter into the per-destination send
buffer and the expert-id sideband) on the chip that sets the pace."""

from bench import scopes


def read(r):
    return scopes.per_call_ms(r, "nimble.pack")
