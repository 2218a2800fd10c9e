"""LeNet-5's layers against the XLA ops they stand in for: the max-pool's
elementwise 2x2 form with its first-max VJP against `lax.reduce_window`
and the `select_and_scatter_add` gradient JAX derives for it, bit for
bit, ties included; the convolution's patch matmul against
`lax.conv_general_dilated`, forward and per-group gradients."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import lenet

# (cohort, K) of the two nested vmaps, then one microbatch's (B, H, W, C)
LEAD = (2, 3)
SHAPES = {"pool1": (4, 28, 28, 6), "pool2": (4, 10, 10, 16),
          "odd": (4, 7, 9, 5)}


def reduce_window_pool(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                 (1, 2, 2, 1), "VALID")


def inputs(kind, shape, dtype):
    k = jax.random.PRNGKey(0)
    z = jax.random.normal(k, shape, jnp.float32)
    if kind == "normal":
        x = z
    elif kind == "constant":        # every window one four-way tie
        x = jnp.full(shape, 0.5)
    elif kind == "grid":            # few distinct values: many ties
        x = jnp.round(2 * z) / 2
    else:                           # tanh saturated: +-1 ties in bf16
        assert kind == "tanh"
        x = jnp.tanh(4 * z)
    return x.astype(dtype)


def bits(a):
    a = np.asarray(a)
    return a.view(np.uint16 if a.dtype.itemsize == 2 else np.uint32)


def pool_and_vjp(pool, x, g):
    f = jax.vmap(jax.vmap(pool))
    y, vjp = jax.vjp(f, x)
    return y, vjp(g)[0]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("kind", ["normal", "constant", "grid", "tanh"])
def test_pool_matches_reduce_window_bit_for_bit(kind, shape, dtype):
    shape = LEAD + SHAPES[shape]
    x = inputs(kind, shape, dtype)
    b, h, w, c = shape[2:]
    g = jax.random.normal(jax.random.PRNGKey(1),
                          LEAD + (b, h // 2, w // 2, c)).astype(dtype)
    run = jax.jit(pool_and_vjp, static_argnums=0)
    y_ref, dx_ref = run(reduce_window_pool, x, g)
    y, dx = run(lenet._pool, x, g)
    assert y.shape == y_ref.shape and dx.shape == x.shape
    np.testing.assert_array_equal(bits(y), bits(y_ref))
    np.testing.assert_array_equal(bits(dx), bits(dx_ref))
    if kind == "constant":
        # the whole cotangent on each window's first element, as
        # select_and_scatter_add(ge) routes it; jnp.max's VJP would split it
        win = dx[..., :2 * (h // 2), :2 * (w // 2), :].reshape(
            LEAD + (b, h // 2, 2, w // 2, 2, c))
        np.testing.assert_array_equal(win[..., 0, :, 0, :], g)
        assert not np.asarray(win[..., 1, :, :, :]).any()
        assert not np.asarray(win[..., 0, :, 1, :]).any()


def test_lenet_grad_matches_reduce_window_pool(monkeypatch):
    """jax.grad of the whole loss over a paper-sized cohort batch (cohort
    10, K 4, microbatch 16 of 32x32x3) with the new pool and with the
    reduce_window form."""
    cfg = lenet.LeNetConfig()
    params = lenet.init(cfg, jax.random.PRNGKey(0))
    kx, ky = jax.random.split(jax.random.PRNGKey(1))
    batch = {"images": jax.random.normal(kx, (10, 4, 16, 32, 32, 3)),
             "labels": jax.random.randint(ky, (10, 4, 16), 0, 10)}

    def grads():
        g = jax.grad(lambda p, b: lenet.loss_fn(cfg, p, b))
        return jax.jit(jax.vmap(jax.vmap(g, (None, 0)), (None, 0)))(
            params, batch)

    new = grads()
    monkeypatch.setattr(lenet, "_pool", reduce_window_pool)
    ref = grads()
    for name in params:
        scale = float(jnp.max(jnp.abs(ref[name])))
        err = float(jnp.max(jnp.abs(new[name] - ref[name])))
        assert err <= 1e-6 * max(scale, 1.0), (name, err, scale)


# one microbatch's (B, H, W, C) input and the layer's output channels
CONV_SHAPES = {"conv1": ((16, 32, 32, 3), 6), "conv2": ((16, 14, 14, 6), 16),
               "gray": ((16, 28, 28, 1), 6)}


def lax_conv(x, w, b):
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC")) + b


@pytest.mark.parametrize("params", ["shared", "per_client"])
@pytest.mark.parametrize("layer", sorted(CONV_SHAPES))
def test_conv_matches_conv_general_dilated(layer, params):
    """`lenet._conv` under the client pass's two vmaps (cohort, then K
    microbatches): the forward and each group's weight and bias gradient
    match `lax.conv_general_dilated` at `highest` precision.  "shared"
    parameters are the first local epoch's (one set for the cohort),
    "per_client" the second's (one set per client)."""
    (b, h, w, cin), cout = CONV_SHAPES[layer]
    kx, kw, kb, kg = jax.random.split(jax.random.PRNGKey(2), 4)
    lead_p = LEAD[:1] if params == "per_client" else ()
    x = jax.random.normal(kx, LEAD + (b, h, w, cin))
    wt = jax.random.normal(kw, lead_p + (5, 5, cin, cout)) / np.sqrt(
        25 * cin)
    bias = jax.random.normal(kb, lead_p + (cout,))
    # cotangent of the output, scaled so each gradient is of order one
    g = jax.random.normal(kg, LEAD + (b, h - 4, w - 4, cout)) / np.sqrt(
        b * (h - 4) * (w - 4))

    def run(conv):
        def one(wt, bias, x, g):
            y, vjp = jax.vjp(lambda w_, b_: conv(x, w_, b_), wt, bias)
            return (y,) + vjp(g)
        p_ax = 0 if params == "per_client" else None
        f = jax.vmap(jax.vmap(one, (None, None, 0, 0)), (p_ax, p_ax, 0, 0))
        with jax.default_matmul_precision("highest"):
            return jax.jit(f)(wt, bias, x, g)

    got, ref = run(lenet._conv), run(lax_conv)
    for what, a, r in zip(("forward", "d_weight", "d_bias"), got, ref):
        assert a.shape == r.shape == LEAD + r.shape[2:], what
        err = float(jnp.max(jnp.abs(a - r)))
        scale = float(jnp.max(jnp.abs(r)))
        assert err <= 1e-5 * max(scale, 1.0), (what, err, scale)
