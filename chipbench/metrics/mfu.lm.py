"""The whole round step's share of the chips' bf16 peak, %: the forward
and backward operations the traced steps required (counts/) over the
traced window times chips times peak."""


def read(r):
    return r.mfu()
