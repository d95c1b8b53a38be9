"""Device milliseconds per forward step in the planner (``nimble.plan``:
the counts' all-gather, the MWU or static rule, chunk quantisation) on the
chip that sets the pace."""

from bench import scopes


def read(r):
    return scopes.per_call_ms(r, "nimble.plan")
