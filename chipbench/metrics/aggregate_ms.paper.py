"""Device ms per round in operations under the `aggregate` scope."""


def read(r):
    return r.per_round_ms(r.red.scope_s("aggregate"))
