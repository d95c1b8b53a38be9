"""Device time of collective ops per forward step on the chip that holds
the hot expert."""

from bench import trace as tr


def read(r):
    spent = tr.collective_s(r.ops, r.t0, r.t1)
    return r.per_call_ms(spent) if spent > 0 else None
