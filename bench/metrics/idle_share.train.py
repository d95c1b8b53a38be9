"""Per cent of the traced window in which the pace-setting device ran no
operation."""


def read(r):
    return r.idle_share()
