"""Device milliseconds per forward step in latent attention
(``nimble.attn``: the projections, the latent's norm, RoPE, the flash kernel
and the output projection, over every layer) on the chip that sets the
pace."""

from bench import scopes


def read(r):
    return scopes.per_call_ms(r, "nimble.attn")
