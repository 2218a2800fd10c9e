"""Pallas TPU kernel for the robust server aggregators (DESIGN.md §9).

`rank_band_mean` — per-coordinate order-statistic band mean over the
(cohort, N) stack of uploaded client gradients, the one primitive behind
both coordinate-wise trimmed mean and coordinate-wise median:

    trimmed mean :  lo = k,                hi = m_valid - 1 - k
    median       :  lo = floor((m_v-1)/2), hi = floor(m_v/2)

Like the Eq. 10-12 weighted sum (kernels/rloo), the op is memory-bound:
one HBM read of the stack per round.  Mosaic has no sort primitive, so
instead of sorting each coordinate's column the kernel computes each
entry's *stable rank* among the valid rows by pairwise comparison —

    rank_u = #{ v valid : g_v < g_u  or  (g_v == g_u and v < u) }

— an O(M^2) contraction per tile, unrolled statically over the M cohort
rows (M <= a few dozen; the tile stays (M, block_n) in VMEM and the VPU
eats the M extra passes while the next tile streams in).  The row-index
tie-break makes ranks a permutation of 0..m_valid-1 even with duplicate
values, so the band sum matches a stable sort exactly; invalid rows
(dead cohort slots, sharding pad rows) are excluded from every count and
from the band.  Entries with rank in [lo, hi] are averaged by the exact
band size hi - lo + 1.

The pure-jnp oracle (`ref.rank_band_mean_ref`) sorts instead — see its
docstring for why the two formulations agree — and serves as the CPU
production path via the shared `default_interpret` convention.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import default_interpret
from repro.kernels.rloo.rloo import accumulate_lanes, acc_out, row_out


def _rank_band_kernel(g_ref, alive_ref, alive_s_ref, band_ref, agg_ref,
                      nrm_ref, *, m: int):
    g = g_ref[...].astype(jnp.float32)            # (M, block_n)
    alive = alive_ref[...]                        # (M, 1) in {0, 1}
    lo = band_ref[0]                              # SMEM scalars
    hi = band_ref[1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (m, 1), 0)
    rank = jnp.zeros_like(g)
    for v in range(m):                            # static unroll: M small
        gv = g[v:v + 1, :]                        # (1, block_n)
        tie = (v < rows).astype(jnp.float32)      # row-index tie-break
        contrib = (gv < g).astype(jnp.float32) + \
            (gv == g).astype(jnp.float32) * tie
        rank = rank + alive_s_ref[v] * contrib
    inc = (rank >= lo) & (rank <= hi) & (alive > 0)
    band = jnp.sum(jnp.where(inc, g, 0.0), axis=0, keepdims=True) \
        / band_ref[2]
    agg_ref[...] = band
    accumulate_lanes(nrm_ref, band * band)        # norm lane partials


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def rank_band_mean(g_flat, alive, lo, hi, *, block_n: int = 512,
                   interpret: bool | None = None):
    """Per-coordinate mean of ascending-order ranks [lo, hi], valid rows
    only.

    g_flat: (M, N) f32 cohort stack; alive: (M,) f32 validity mask
    (0 excludes the row entirely); lo, hi: scalar ranks (traced values —
    they depend on the round's survivor count), inclusive.  Returns
    (band_mean (N,), ||band_mean||^2).

    Zero-padding N to a block multiple is safe: a padded column is
    all-zero, its band mean is 0 and contributes nothing to the norm.
    The alive mask rides twice: as an (M, 1) VMEM block for the band
    mask, and as (M,) SMEM scalars for the per-row rank weights (Mosaic
    broadcasts a scalar over a tile, but not a (1, 1) slice of a vector).
    The band (lo, hi, max(hi - lo + 1, 1)) rides as SMEM scalars.
    """
    if interpret is None:
        interpret = default_interpret()
    m, n = g_flat.shape
    alive = jnp.asarray(alive, jnp.float32)
    lo = jnp.asarray(lo, jnp.float32)
    hi = jnp.asarray(hi, jnp.float32)
    band = jnp.stack([lo, hi, jnp.maximum(hi - lo + 1.0, 1.0)])
    pad = (-n) % block_n
    g_padded = g_flat.astype(jnp.float32)
    if pad:
        g_padded = jnp.pad(g_padded, ((0, 0), (0, pad)))
    n_padded = n + pad
    (agg_spec, agg_shape), (acc_spec, acc_shape) = \
        row_out(block_n, n_padded), acc_out(block_n)
    agg, nrm_parts = pl.pallas_call(
        functools.partial(_rank_band_kernel, m=m),
        grid=(n_padded // block_n,),
        in_specs=[
            pl.BlockSpec((m, block_n), lambda i: (0, i)),
            pl.BlockSpec((m, 1), lambda i: (0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[agg_spec, acc_spec],
        out_shape=[agg_shape, acc_shape],
        interpret=interpret,
        name="rank_band_mean",
    )(g_padded, jnp.reshape(alive, (m, 1)), alive, band)
    return agg[0, :n], jnp.sum(nrm_parts)
