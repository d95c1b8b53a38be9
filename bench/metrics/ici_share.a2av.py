"""Per cent of a call's time that the hot destination's ingress needs: the
bytes it must take in over the chip's ICI peak, over the traced call time."""


def read(r):
    if r.calls == 0:
        return None
    least = r.work["hot_ingress_bytes"] / (r.peaks["ici_bits_per_s"] / 8)
    return 100 * least * r.calls / r.window_s
