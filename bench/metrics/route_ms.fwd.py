"""Device milliseconds per forward step in the router (``nimble.route``:
logits, softmax, top-k, load-balance loss) on the chip that sets the pace."""

from bench import scopes


def read(r):
    return scopes.per_call_ms(r, "nimble.route")
