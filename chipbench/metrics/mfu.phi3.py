"""The whole round step's share of the chip's bf16 peak, %: the forward
and backward operations of the round's tokens (counts/dense_lm) over the
traced window times chips times peak."""


def read(r):
    return r.mfu()
