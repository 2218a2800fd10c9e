"""LeNet-5 (LeCun et al. 2015) — the model the paper's experiments use — plus
a small MLP; both classify (B, H, W, C) images.  Used by the FL benchmarks
(Table 1 / Figures 1-3 reproductions) on synthetic Dirichlet-non-IID data.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from repro.models.layers import softmax_xent


@dataclasses.dataclass(frozen=True)
class LeNetConfig:
    n_classes: int = 10
    image_size: int = 32
    channels: int = 3
    # FedRep/FedPer/pFedSim need a body/head split: the final dense layer is
    # the "personal" head; everything before is the shared body.


def _conv_init(key, shape):  # (kh, kw, cin, cout)
    fan_in = shape[0] * shape[1] * shape[2]
    return jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)


def init(cfg: LeNetConfig, key):
    ks = jax.random.split(key, 5)
    s = cfg.image_size
    s_after = ((s - 4) // 2 - 4) // 2          # two conv5+pool2 stages
    flat = s_after * s_after * 16
    return {
        "conv1": _conv_init(ks[0], (5, 5, cfg.channels, 6)),
        "conv2": _conv_init(ks[1], (5, 5, 6, 16)),
        "fc1": jax.random.normal(ks[2], (flat, 120), jnp.float32) / math.sqrt(flat),
        "fc2": jax.random.normal(ks[3], (120, 84), jnp.float32) / math.sqrt(120),
        "head": jax.random.normal(ks[4], (84, cfg.n_classes), jnp.float32) / math.sqrt(84),
        "b1": jnp.zeros((6,)), "b2": jnp.zeros((16,)),
        "bf1": jnp.zeros((120,)), "bf2": jnp.zeros((84,)),
        "bh": jnp.zeros((cfg.n_classes,)),
    }


HEAD_KEYS = ("head", "bh")          # personalization split (FedRep/FedPer)


PATCH_MAX = 128    # widest patch (kh * kw * cin) taken as a patch matmul


def _conv(x, w, b):
    """VALID stride-1 convolution of (B, H, W, Cin) by an HWIO kernel,
    plus bias.

    A layer whose patch (kh * kw * cin) fits one 128-wide lane tile runs
    as a patch matmul: its patches, taken by a convolution with a
    constant one-hot kernel (feature (i, j, c) of a patch is the input at
    offset (i, j), channel c: the order of `w.reshape(-1, cout)`),
    against the (kh * kw * cin, cout) kernel.  Under the client pass's
    vmaps over the cohort and the K microbatches, the one-hot kernel is
    the same for every group, so the patch convolution only widens its
    batch, and every product with `w` is a dot with the (client,
    microbatch) groups as its batch dimension.  `lax.conv_general_dilated`
    would become a convolution grouped over them, with a few channels on
    the TPU's lane axis: in the paper protocol's round on a TPU v5e,
    conv1's grouped weight gradient (3 input channels) was the largest op,
    a quarter of the round.  A wider patch keeps the convolution: conv2's
    (150 wide) would pad to two lane tiles, and its grouped gradient (6
    input channels) is not among the round's ten largest ops.
    """
    kh, kw, cin, cout = w.shape
    k = kh * kw * cin
    if k > PATCH_MAX:
        return jax.lax.conv_general_dilated(
            x, w, window_strides=(1, 1), padding="VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC")) + b
    eye = jnp.eye(k, dtype=x.dtype).reshape(kh, kw, cin, k)
    p = jax.lax.conv_general_dilated(
        x, eye, window_strides=(1, 1), padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return p @ w.reshape(k, cout) + b


def _corners(x):
    """The 2x2 stride-2 windows of (B, H, W, C) as four (B, H//2, W//2, C)
    arrays, in row-major window order (0,0), (0,1), (1,0), (1,1); an odd
    last row or column is dropped, as VALID pooling drops it."""
    b, hh, ww, c = x.shape
    h, w = hh // 2, ww // 2
    win = x[:, :2 * h, :2 * w].reshape(b, h, 2, w, 2, c)
    return [win[:, :, i, :, j] for i in (0, 1) for j in (0, 1)]


@jax.custom_vjp
def _pool(x):
    """2x2 stride-2 VALID max-pool over (B, H, W, C), as elementwise ops
    that XLA fuses and lays out freely (`lax.reduce_window` and its
    `select_and_scatter_add` gradient are layout-bound on the TPU).  The
    gradient is `reduce_window`'s bit for bit: the whole cotangent goes to
    the first maximum of each window in window order, where the gradient
    of `jnp.max` would split it over ties."""
    q = _corners(x)
    return jnp.maximum(jnp.maximum(q[0], q[1]), jnp.maximum(q[2], q[3]))


def _pool_fwd(x):
    y = _pool(x)
    return y, (x, y)


def _pool_bwd(res, g):
    x, y = res
    q = _corners(x)
    # window order of each window's first maximum
    first = jnp.where(q[0] == y, 0, jnp.where(
        q[1] == y, 1, jnp.where(q[2] == y, 2, 3))).astype(jnp.int8)
    # and of each element of the (B, h, 2, w, 2, C) view: 2 * row + column
    order = jnp.arange(4, dtype=jnp.int8).reshape(2, 1, 2, 1)
    dx = jnp.where(order == first[:, :, None, :, None],
                   g[:, :, None, :, None], jnp.zeros((), g.dtype))
    b, h, _, w, _, c = dx.shape
    dx = dx.reshape(b, 2 * h, 2 * w, c)
    return (jnp.pad(dx, ((0, 0), (0, x.shape[1] - 2 * h),
                         (0, x.shape[2] - 2 * w), (0, 0))),)


_pool.defvjp(_pool_fwd, _pool_bwd)


def forward(cfg: LeNetConfig, params, images):
    x = images
    x = jnp.tanh(_conv(x, params["conv1"], params["b1"]))
    x = _pool(x)
    x = jnp.tanh(_conv(x, params["conv2"], params["b2"]))
    x = _pool(x)
    x = x.reshape(x.shape[0], -1)
    x = jnp.tanh(x @ params["fc1"] + params["bf1"])
    x = jnp.tanh(x @ params["fc2"] + params["bf2"])
    return x @ params["head"] + params["bh"]


def loss_fn(cfg: LeNetConfig, params, batch):
    logits = forward(cfg, params, batch["images"])
    return softmax_xent(logits, batch["labels"])


def accuracy(cfg: LeNetConfig, params, batch):
    logits = forward(cfg, params, batch["images"])
    return jnp.mean((jnp.argmax(logits, -1) == batch["labels"]).astype(jnp.float32))


# ----------------------------- tiny MLP ------------------------------------

@dataclasses.dataclass(frozen=True)
class MLPConfig:
    n_classes: int = 10
    in_dim: int = 64
    hidden: int = 128


def init_mlp(cfg: MLPConfig, key):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w1": jax.random.normal(k1, (cfg.in_dim, cfg.hidden)) / math.sqrt(cfg.in_dim),
        "w2": jax.random.normal(k2, (cfg.hidden, cfg.hidden)) / math.sqrt(cfg.hidden),
        "head": jax.random.normal(k3, (cfg.hidden, cfg.n_classes)) / math.sqrt(cfg.hidden),
        "b1": jnp.zeros((cfg.hidden,)), "b2": jnp.zeros((cfg.hidden,)),
        "bh": jnp.zeros((cfg.n_classes,)),
    }


def forward_mlp(cfg: MLPConfig, params, x):
    x = jax.nn.relu(x @ params["w1"] + params["b1"])
    x = jax.nn.relu(x @ params["w2"] + params["b2"])
    return x @ params["head"] + params["bh"]


def loss_mlp(cfg: MLPConfig, params, batch):
    return softmax_xent(forward_mlp(cfg, params, batch["images"]),
                        batch["labels"])