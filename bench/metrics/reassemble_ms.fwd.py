"""Device milliseconds per forward step in reassembly (``nimble.reassemble``:
the scatter-add into per-source buffers and the local copy), over every
exchange of the step, on the chip that sets the pace."""

from bench import scopes


def read(r):
    return scopes.per_call_ms(r, "nimble.reassemble")
