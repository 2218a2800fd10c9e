"""LeNet-5 as the paper trains it (two 5x5 conv + 2x2 max-pool stages with
tanh, then dense 120 -> 84 -> classes), in plain `jax.numpy`.

`init` is also how the benchmark makes the weights the program trains:
one jitted call on the device from the seed, float32.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def shapes(cfg):
    s, c, k = cfg["image_size"], cfg["channels"], cfg["n_classes"]
    c1, c2, kk = cfg["conv1_channels"], cfg["conv2_channels"], cfg["kernel"]
    side = ((s - kk + 1) // 2 - kk + 1) // 2
    flat = side * side * c2
    f1, f2 = cfg["fc1"], cfg["fc2"]
    return {"conv1": (kk, kk, c, c1), "conv2": (kk, kk, c1, c2),
            "fc1": (flat, f1), "fc2": (f1, f2), "head": (f2, k),
            "b1": (c1,), "b2": (c2,), "bf1": (f1,), "bf2": (f2,),
            "bh": (k,)}


def init(cfg, key, dtype=jnp.float32):
    """Weights ~ N(0, 1/fan_in), biases 0."""
    shp = shapes(cfg)
    keys = dict(zip(sorted(shp), jax.random.split(key, len(shp))))
    out = {}
    for name, s in shp.items():
        if len(s) == 1:
            out[name] = jnp.zeros(s, dtype)
        else:
            fan_in = math.prod(s[:-1])
            out[name] = (jax.random.normal(keys[name], s, jnp.float32)
                         / math.sqrt(fan_in)).astype(dtype)
    return out


def _conv(x, w, b):
    y = jax.lax.conv_general_dilated(
        x, w.astype(x.dtype), (1, 1), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + b.astype(x.dtype)


def _pool(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


def logits(params, images):
    dt = params["fc1"].dtype
    x = images.astype(dt)
    x = _pool(jnp.tanh(_conv(x, params["conv1"], params["b1"])))
    x = _pool(jnp.tanh(_conv(x, params["conv2"], params["b2"])))
    x = x.reshape(x.shape[0], -1)
    x = jnp.tanh(x @ params["fc1"] + params["bf1"])
    x = jnp.tanh(x @ params["fc2"] + params["bf2"])
    return x @ params["head"] + params["bh"]


def loss(params, batch):
    """Mean softmax cross-entropy, accumulated in the params' dtype."""
    z = logits(params, batch["images"])
    logz = jax.nn.logsumexp(z, axis=-1)
    gold = jnp.take_along_axis(z, batch["labels"][:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold)
