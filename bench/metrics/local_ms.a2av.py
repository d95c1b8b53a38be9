"""Device time per call outside collective ops on the hot destination: the
planner, the slot gather and the reassembly passes."""

from bench import trace as tr


def read(r):
    if not r.ops:
        return None
    return r.per_call_ms(tr.local_s(r.ops, r.t0, r.t1))
