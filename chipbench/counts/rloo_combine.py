"""The client-level RLOO combine over a (K, N) f32 gradient stack.

Per column: the K-term sum and the mean, the K leave-one-out baselines
and reshaped units (a subtract, a divide, a multiply, a subtract each),
and the K squares summed into S2: 7 K + 1 operations.  Bytes are those
the caller needs: the stack read once and the (N,) mean written once;
the (K, N) reshaped tile counts only where the caller consumes it (local
training past one epoch) and not where it is dropped.
"""
from __future__ import annotations


def flops(k, n):
    return (7 * k + 1) * n


def bytes_needed(k, n, tile_consumed):
    return 4 * n * (k + 1 + (k if tile_consumed else 0))
