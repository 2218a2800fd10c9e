"""Device ms per round in operations under the `client_pass` scope."""


def read(r):
    return r.per_round_ms(r.red.scope_s("client_pass"))
