"""Device milliseconds per forward step in the shared experts
(``nimble.shared``: one SwiGLU over every token, in every MoE layer) on the
chip that sets the pace."""

from bench import scopes


def read(r):
    return scopes.per_call_ms(r, "nimble.shared")
