"""Pallas TPU kernels for the two FedNCV hot spots.

`rloo_combine` — fused RLOO reshape + reduction over K microbatch gradients
(the client-side pass).  The op is memory-bound (arithmetic intensity < 1
flop/byte): the K gradient copies are streamed HBM -> VMEM once, and in that
single pass we produce

    gbar    = mean_i g_i                      (the client message, pre-scale)
    gprime  = g_i - alpha * (K gbar - g_i)/(K-1)   (reshaped units, optional)
    sumsq   = sum_i ||g_i||^2                 (RLOO statistic S2)

A naive composition (mean, then baseline, then reshape, then norms) reads the
(K, N) stack four times; the fused kernel reads it once and keeps the
working set in VMEM.

`ncv_aggregate` — fused server-side networked aggregation (paper Eq. 10-12)
over the (cohort, N) stack of uploaded client gradients.  The whole estimator

    g = sum_u p_u (g_u - beta * c_{V\\u}),
    c_{V\\u} = (n * gbar_w - n_u g_u) / (n - n_u)

collapses to a single weighted sum  g = sum_u w_u g_u  with per-client
scalar coefficients

    w_u = p_u * (1 - beta * sum_v p_v n/(n - n_v)) + beta * p_u n_u/(n - n_u)

so the kernel is one read of the stack: a (cohort,) x (cohort, block_n)
contraction per tile, plus a running ||g||^2 partial for diagnostics.

`ncv_aggregate_q` — the same reduction fused with chunked-scale int8
dequantization: the (cohort, N_packed) stack is streamed from HBM in its
*compressed* wire format (1 byte/param instead of 4) and expanded to f32
only inside VMEM, so the HBM traffic of the server step drops 4x together
with the uploaded bytes (DESIGN.md §5).  `ncv_aggregate_q4` extends this to
the packed int4 wire (two nibbles per byte, split-halves layout within each
chunk — DESIGN.md §5.1): 8x less HBM traffic, unpacked in VMEM.

Every reduction is exposed in two layers: `ncv_weighted_sum*` takes the
per-client scalar weights directly (this is what the sharded cohort path
uses — each device reduces its local slice of the stack with weights
computed from globally psum'd/all-gathered sample counts, DESIGN.md §6),
and `ncv_aggregate*` derives the weights from `n_samples` via
`ncv_coefficients` for the single-device call sites.

Tiling: grid over the flattened gradient dimension N in `block_n` columns;
each program instance holds a (K, block_n) tile in VMEM.  K is small (<= 32)
and block_n = 512 f32 lanes keeps the tile well inside the ~16 MB VMEM
budget while filling the 8x128 VPU registers (block_n multiple of 128).

Every block is a legal Mosaic tile (last two dims multiples of (8, 128) or
equal to the array's): the (N,) results are written as (1, N) rows; scalar
inputs (alpha) and weight vectors ride as whole (1, 1) / (M, 1) blocks, so
the shapes stay legal when the Simulator vmaps the client pass over its
cohort; per-chunk scales are read 128 chunks at a time as (M, 128) blocks;
and the scalar side outputs (S2, ||agg||^2) accumulate lane partials in one
(1, block_n) block that stays resident across the sequential grid and is
reduced outside.

`interpret` defaults to `jax.default_backend() != "tpu"` so the same call
site compiles to a real Mosaic kernel on TPU and falls back to the
op-by-op interpreter on CPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import default_interpret

LANES = 128


def accumulate_lanes(acc_ref, x):
    """Add the column sums of tile `x` into the (1, block_n) accumulator
    block `acc_ref`, which maps to the same block at every grid step and so
    stays resident in VMEM over the whole (sequential) grid; zeroed at the
    first step, summed to a scalar by the caller."""
    @pl.when(pl.program_id(0) == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.sum(x, axis=0, keepdims=True)


def row_out(block_n: int, n_padded: int):
    """(spec, shape) of a (1, N) f32 row written one (1, block_n) tile per
    grid step."""
    return (pl.BlockSpec((1, block_n), lambda i: (0, i)),
            jax.ShapeDtypeStruct((1, n_padded), jnp.float32))


def acc_out(block_n: int):
    """(spec, shape) of the resident (1, block_n) lane accumulator."""
    return (pl.BlockSpec((1, block_n), lambda i: (0, 0)),
            jax.ShapeDtypeStruct((1, block_n), jnp.float32))


def _rloo_kernel(g_ref, alpha_ref, mean_ref, gp_ref, ssq_ref, *, k: int):
    g = g_ref[...].astype(jnp.float32)            # (K, block_n)
    alpha = alpha_ref[...]                        # (1, 1)
    gsum = jnp.sum(g, axis=0, keepdims=True)      # (1, block_n)
    mean_ref[...] = gsum / k
    # leave-one-out baseline: c_i = (K mean - g_i) / (K - 1)
    c = (gsum - g) / (k - 1)
    gp_ref[...] = g - alpha * c
    accumulate_lanes(ssq_ref, g * g)              # lane partials of S2


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def rloo_combine(g_stack, alpha, *, block_n: int = 512,
                 interpret: bool | None = None):
    """g_stack: (K, N) f32; alpha: scalar f32.

    Returns (mean (N,), gprime (K, N), sumsq scalar).
    interpret=None auto-detects the backend (Mosaic on TPU, interpreter
    elsewhere).  Non-divisible N is zero-padded once up front and the
    outputs sliced once at the end (zero columns contribute nothing to the
    sumsq reduction).
    """
    if interpret is None:
        interpret = default_interpret()
    k, n = g_stack.shape
    assert k >= 2, "RLOO needs K >= 2"
    pad = (-n) % block_n
    g_padded = g_stack.astype(jnp.float32)
    if pad:
        g_padded = jnp.pad(g_padded, ((0, 0), (0, pad)))
    n_padded = n + pad
    grid = (n_padded // block_n,)
    alpha_arr = jnp.reshape(alpha.astype(jnp.float32), (1, 1))
    (mean_spec, mean_shape), (acc_spec, acc_shape) = \
        row_out(block_n, n_padded), acc_out(block_n)
    mean, gp, ssq_parts = pl.pallas_call(
        functools.partial(_rloo_kernel, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((k, block_n), lambda i: (0, i)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=[
            mean_spec,
            pl.BlockSpec((k, block_n), lambda i: (0, i)),
            acc_spec,
        ],
        out_shape=[
            mean_shape,
            jax.ShapeDtypeStruct((k, n_padded), jnp.float32),
            acc_shape,
        ],
        interpret=interpret,
        name="rloo_combine",
    )(g_padded, alpha_arr)
    mean = mean[0]
    if pad:
        mean, gp = mean[:n], gp[:, :n]
    return mean, gp, jnp.sum(ssq_parts)


# ---------------------------------------------------------------------------
# Server-side fused aggregation (Eq. 10-12 in one read)
# ---------------------------------------------------------------------------

def _ncv_agg_kernel(g_ref, w_ref, agg_ref, nrm_ref):
    g = g_ref[...].astype(jnp.float32)            # (M, block_n)
    agg = jnp.sum(w_ref[...] * g, axis=0, keepdims=True)   # (1, block_n)
    agg_ref[...] = agg
    accumulate_lanes(nrm_ref, agg * agg)          # ||agg||^2 lane partials


def ncv_coefficients(n_samples, beta):
    """Per-client scalar weights w_u of the collapsed Eq. 10-12 estimator.

    Padding rule (DESIGN.md §6): a client with n_u = 0 gets w_u = 0 exactly
    (p_u = 0 and every n_u-proportional term vanishes), so zero-weight rows
    appended to make the cohort divisible by the device count contribute
    nothing to the estimator and nothing to the global stats n and
    sum_v n_v/(n - n_v).

    Degenerate lone-reporter rule (DESIGN.md §9): a client carrying *all*
    the mass (n_u = n, every peer at zero — only reachable under fault
    injection) has no leave-one-out network, so its correction terms are
    dropped (the 1/(n - n_u) ratios are where-guarded to 0) and the
    estimator degrades to the plain weighted mean instead of 0 * inf = NaN.
    The guard selects the identical expression whenever every denominator
    is positive, so all honest paths are bit-unchanged.
    """
    n_samples = jnp.asarray(n_samples, jnp.float32)
    n = jnp.sum(n_samples)
    p = n_samples / n
    beta = jnp.asarray(beta, jnp.float32)
    d = n - n_samples
    a0 = 1.0 - beta * jnp.sum(p * jnp.where(d > 0, n / d, 0.0))
    return a0 * p + beta * p * jnp.where(d > 0, n_samples / d, 0.0)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def ncv_weighted_sum(g_flat, w, *, block_n: int = 512,
                     interpret: bool | None = None):
    """Fused weighted sum sum_u w_u g_u over the flat (M, N) stack.

    Returns (agg (N,), ||agg||^2 scalar) in one HBM read of the stack.
    The weight vector is taken as-is: single-device callers derive it from
    `ncv_coefficients(n_samples, beta)` (see `ncv_aggregate`); sharded
    callers pass their local slice of the globally-computed coefficients
    and psum the partial sums afterwards (the returned norm is then the
    norm of the *partial* sum — recompute it from the psum'd vector).
    """
    if interpret is None:
        interpret = default_interpret()
    m, n = g_flat.shape
    w = jnp.asarray(w, jnp.float32)
    pad = (-n) % block_n
    g_padded = g_flat.astype(jnp.float32)
    if pad:
        g_padded = jnp.pad(g_padded, ((0, 0), (0, pad)))
    n_padded = n + pad
    grid = (n_padded // block_n,)
    (agg_spec, agg_shape), (acc_spec, acc_shape) = \
        row_out(block_n, n_padded), acc_out(block_n)
    agg, nrm_parts = pl.pallas_call(
        _ncv_agg_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((m, block_n), lambda i: (0, i)),
            pl.BlockSpec((m, 1), lambda i: (0, 0)),
        ],
        out_specs=[agg_spec, acc_spec],
        out_shape=[agg_shape, acc_shape],
        interpret=interpret,
        name="ncv_weighted_sum",
    )(g_padded, jnp.reshape(w, (m, 1)))
    agg = agg[0, :n]
    return agg, jnp.sum(nrm_parts)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def ncv_aggregate(g_flat, n_samples, beta=1.0, *, block_n: int = 512,
                  interpret: bool | None = None):
    """Fused FedNCV server reduction over the flat cohort stack.

    g_flat: (M, N) f32 — uploaded client gradients, flat substrate.
    n_samples: (M,) per-client sample counts.  Returns (agg (N,),
    agg_norm_sq scalar) — identical math to `networked_aggregate_stacked`
    but one HBM read of the stack instead of four per-leaf passes.
    """
    return ncv_weighted_sum(g_flat, ncv_coefficients(n_samples, beta),
                            block_n=block_n, interpret=interpret)


# ---------------------------------------------------------------------------
# Fused dequantize-aggregate: Eq. 10-12 straight off the int8 wire format
# ---------------------------------------------------------------------------

def scale_column(s_ref):
    """This grid step's (M, 1) per-chunk scale column.  The (M, C) scale
    table is read 128 chunks at a time as an (M, 128) block (an (M, 1)
    block is not a legal tile); chunk i is lane i % 128 of it, picked by a
    one-hot select and a lane sum (exact: one nonzero term per row)."""
    s = s_ref[...]                                    # (M, 128)
    lane = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    hit = lane == pl.program_id(0) % LANES
    return jnp.sum(jnp.where(hit, s, 0.0), axis=1, keepdims=True)


def _dequant_weighted_sum(kernel, name, stack, scales, w, chunk, interpret):
    """Shared pallas_call of the two wire kernels, named `name` in the
    trace: grid over the C chunks, one (M, stack_cols / C) wire tile per
    step, the scale table zero-padded to a multiple of 128 columns (M * C
    floats — negligible next to the stack) so every (M, 128) scale block
    is in bounds."""
    if interpret is None:
        interpret = default_interpret()
    m, c = scales.shape
    width = stack.shape[1] // c                       # wire bytes per chunk
    table = jnp.pad(scales.astype(jnp.float32), ((0, 0), (0, (-c) % LANES)))
    (agg_spec, agg_shape), (acc_spec, acc_shape) = \
        row_out(chunk, c * chunk), acc_out(chunk)
    agg, nrm_parts = pl.pallas_call(
        kernel,
        grid=(c,),
        in_specs=[
            pl.BlockSpec((m, width), lambda i: (0, i)),
            pl.BlockSpec((m, LANES), lambda i: (0, i // LANES)),
            pl.BlockSpec((m, 1), lambda i: (0, 0)),
        ],
        out_specs=[agg_spec, acc_spec],
        out_shape=[agg_shape, acc_shape],
        interpret=interpret,
        name=name,
    )(stack, table, jnp.reshape(jnp.asarray(w, jnp.float32), (m, 1)))
    return agg[0], jnp.sum(nrm_parts)


def _ncv_agg_q_kernel(q_ref, s_ref, w_ref, agg_ref, nrm_ref):
    # int8 tile -> f32 in VMEM; one scale column per (client, chunk) tile.
    g = q_ref[...].astype(jnp.float32) * scale_column(s_ref)  # (M, chunk)
    agg = jnp.sum(w_ref[...] * g, axis=0, keepdims=True)     # (1, chunk)
    agg_ref[...] = agg
    accumulate_lanes(nrm_ref, agg * agg)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ncv_weighted_sum_q(q, scales, w, *, chunk: int = 512,
                       interpret: bool | None = None):
    """Weighted sum sum_u w_u g_u fused with chunked-scale int8 dequant.

    q: (M, N_packed) int8 — the compressed cohort stack exactly as uploaded
    (comm `int8` wire format, N_packed = C * chunk); scales: (M, C) f32
    per-chunk scales; w: (M,) per-client weights.  Returns
    (agg (N_packed,) f32, ||agg||^2).

    The stack is read from HBM *compressed* — 4x less traffic than the f32
    `ncv_weighted_sum` path — and dequantized in VMEM tile by tile; the grid
    iterates chunks so each program sees one (M, chunk) int8 tile plus its
    scale column, and the estimator stays the collapsed weighted sum
    g = sum_u w_u * scale_u,c * q_u,c.  (On TPU the int8 sublane tile is 32;
    Mosaic masks cohort stacks smaller than that — cohort size never pads
    HBM traffic.)
    """
    m, n_packed = q.shape
    c = n_packed // chunk
    assert n_packed == c * chunk, (n_packed, chunk)
    assert scales.shape == (m, c), (scales.shape, (m, c))
    return _dequant_weighted_sum(_ncv_agg_q_kernel, "ncv_weighted_sum_q", q,
                                 scales, w, chunk, interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ncv_aggregate_q(q, scales, n_samples, beta=1.0, *, chunk: int = 512,
                    interpret: bool | None = None):
    """`ncv_aggregate` fused with chunked-scale int8 dequantization."""
    return ncv_weighted_sum_q(q, scales, ncv_coefficients(n_samples, beta),
                              chunk=chunk, interpret=interpret)


# ---------------------------------------------------------------------------
# Fused unpack-dequantize-aggregate: Eq. 10-12 off the packed int4 wire
# ---------------------------------------------------------------------------

def _ncv_agg_q4_kernel(qp_ref, s_ref, w_ref, agg_ref, nrm_ref):
    # packed uint8 tile -> two int4 nibbles -> f32 in VMEM.  Split-halves
    # layout (DESIGN.md §5.1): within each chunk, byte j carries value j in
    # its low nibble and value j + chunk/2 in its high nibble, so unpacking
    # is a lane concatenation instead of an interleave.
    qp = qp_ref[...].astype(jnp.int32)                # (M, chunk//2)
    lo = qp & 0xF
    hi = (qp >> 4) & 0xF
    g = jnp.concatenate([lo, hi], axis=1)             # (M, chunk)
    g = jnp.where(g < 8, g, g - 16).astype(jnp.float32) * scale_column(s_ref)
    agg = jnp.sum(w_ref[...] * g, axis=0, keepdims=True)     # (1, chunk)
    agg_ref[...] = agg
    accumulate_lanes(nrm_ref, agg * agg)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ncv_weighted_sum_q4(qp, scales, w, *, chunk: int = 512,
                        interpret: bool | None = None):
    """Weighted sum fused with packed-int4 unpack + dequantization.

    qp: (M, N_packed // 2) uint8 — two 4-bit two's-complement codes per
    byte in the split-halves layout; scales: (M, C) f32 per-chunk scales
    (C = N_packed / chunk); w: (M,).  Returns (agg (N_packed,) f32,
    ||agg||^2).  The stack is streamed from HBM at 0.5 bytes/param — 8x
    less traffic than f32 — and expanded to f32 only inside VMEM.
    """
    m, half = qp.shape
    n_packed = 2 * half
    c = n_packed // chunk
    assert chunk % 2 == 0, chunk
    assert n_packed == c * chunk, (n_packed, chunk)
    assert scales.shape == (m, c), (scales.shape, (m, c))
    return _dequant_weighted_sum(_ncv_agg_q4_kernel, "ncv_weighted_sum_q4",
                                 qp, scales, w, chunk, interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ncv_aggregate_q4(qp, scales, n_samples, beta=1.0, *, chunk: int = 512,
                     interpret: bool | None = None):
    """`ncv_aggregate` fused with packed-int4 unpack-dequantization."""
    return ncv_weighted_sum_q4(qp, scales, ncv_coefficients(n_samples, beta),
                               chunk=chunk, interpret=interpret)
