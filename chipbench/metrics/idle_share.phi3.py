"""Share of the traced window in which the device ran no operation, %."""


def read(r):
    return r.idle_share()
