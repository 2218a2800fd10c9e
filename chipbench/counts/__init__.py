"""Operations and bytes from shapes, one file per model or kernel.

Training counts are what the forward and backward passes require: twice
the multiply-adds forward, three times that with the backward pass;
nothing recomputed is counted.
"""
