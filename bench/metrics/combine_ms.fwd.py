"""Device milliseconds per forward step in combine outside its exchange
(innermost ``nimble.combine``: the gather by destination and slot and the
gate-weighted sum) on the chip that sets the pace."""

from bench import scopes


def read(r):
    return scopes.per_call_ms(r, "nimble.combine")
