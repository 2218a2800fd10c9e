"""LeNet-5 operations (configs/paper-lenet5.json)."""
from __future__ import annotations


def macs_per_sample(cfg):
    """Forward multiply-adds of one image: two VALID convs, three dense."""
    s, c, kk = cfg["image_size"], cfg["channels"], cfg["kernel"]
    c1, c2 = cfg["conv1_channels"], cfg["conv2_channels"]
    o1 = s - kk + 1
    o2 = o1 // 2 - kk + 1
    side = o2 // 2
    return (o1 * o1 * c1 * kk * kk * c
            + o2 * o2 * c2 * kk * kk * c1
            + side * side * c2 * cfg["fc1"]
            + cfg["fc1"] * cfg["fc2"]
            + cfg["fc2"] * cfg["n_classes"])


def train_flops_per_sample(cfg):
    """Forward and backward: 2 operations per multiply-add, times 3."""
    return 6 * macs_per_sample(cfg)

