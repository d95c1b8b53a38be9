"""Device milliseconds per forward step in the row arrangement around the
grouped FFN kernel (``nimble.arrange``: the sort of the expert ids, the
index maps, the gather of the kernel's input rows and the gather of its
output rows back) on the chip that sets the pace."""

from bench import scopes


def read(r):
    return scopes.per_call_ms(r, "nimble.arrange")
