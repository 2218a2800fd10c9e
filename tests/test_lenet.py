"""LeNet-5's max-pool: the elementwise 2x2 form with its first-max VJP
against `lax.reduce_window` and the `select_and_scatter_add` gradient JAX
derives for it, bit for bit, ties included."""
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
