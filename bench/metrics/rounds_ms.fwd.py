"""Device milliseconds per forward step in the exchange rounds
(``nimble.rounds``: slot gather, per-round concat and slice, the ppermutes),
over every exchange of the step: payload, sideband and return, on the chip
that sets the pace."""

from bench import scopes


def read(r):
    return scopes.per_call_ms(r, "nimble.rounds")
