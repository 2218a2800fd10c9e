"""A decoder-only LM with the Phi-3-mini layer (arXiv:2404.14219): RMSNorm,
multi-head attention with rotary embeddings (rotate-half, theta from the
configuration) under a causal mask, SwiGLU feed-forward, untied output
head; mean next-token cross-entropy.  Plain `jax.numpy`, one layer after
another.

The parameter tree is the layout the system under test reads (per-layer
weights stacked on a leading layer axis; a norm weight w scales by 1 + w),
so `init` also makes the weights the program trains: one jitted call on
the device from the seed, float32.  Phi-3's fused qkv and gate-up
projections are the same products kept as separate matrices.  The
published sliding window (2047) is wider than every sequence the cells
send, so attention is plain causal.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def dims(cfg):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    return d, h, kv, hd, cfg["intermediate_size"], cfg["vocab_size"], \
        cfg["num_hidden_layers"]


def shapes(cfg):
    d, h, kv, hd, f, v, n = dims(cfg)
    layers = {"attn_norm": (n, d), "ffn_norm": (n, d),
              "wq": (n, d, h * hd), "wk": (n, d, kv * hd),
              "wv": (n, d, kv * hd), "wo": (n, h * hd, d),
              "w_gate": (n, d, f), "w_up": (n, d, f), "w_down": (n, f, d)}
    out = {"embed": (v, d), "layers": layers, "final_norm": (d,)}
    if not cfg.get("tie_word_embeddings", False):
        out["unembed"] = (d, v)
    return out


def init(cfg, key, dtype=jnp.float32):
    """Matrices ~ N(0, 1/fan_in), embeddings ~ N(0, 0.02^2), norms 0."""
    is_shape = lambda s: isinstance(s, tuple)  # noqa: E731
    with_paths, treedef = jax.tree_util.tree_flatten_with_path(
        shapes(cfg), is_leaf=is_shape)
    paths = [jax.tree_util.keystr(p) for p, _ in with_paths]
    flat = [s for _, s in with_paths]
    keys = jax.random.split(key, len(flat))
    leaves = []
    for path, s, k in zip(paths, flat, keys):
        if "norm" in path:
            leaves.append(jnp.zeros(s, dtype))
        elif "embed" in path and "unembed" not in path:
            leaves.append((0.02 * jax.random.normal(k, s, jnp.float32))
                          .astype(dtype))
        else:
            leaves.append((jax.random.normal(k, s, jnp.float32)
                           / math.sqrt(s[-2])).astype(dtype))
    return jax.tree.unflatten(treedef, leaves)


def rmsnorm(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def rope(x, theta):
    """x: (B, S, H, hd); rotate-half rotary embedding at positions 0..S-1."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv        # (S, hd/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           -1).astype(x.dtype)


def layer(cfg, p, x):
    d, h, kv, hd, _, _, _ = dims(cfg)
    b, s, _ = x.shape
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    y = rmsnorm(x, p["attn_norm"], eps)
    q = rope((y @ p["wq"]).reshape(b, s, h, hd), theta)
    k = rope((y @ p["wk"]).reshape(b, s, kv, hd), theta)
    v = (y @ p["wv"]).reshape(b, s, kv, hd)
    rep = h // kv
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores.astype(jnp.float32), -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    att = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, h * hd)
    x = x + att @ p["wo"]
    y = rmsnorm(x, p["ffn_norm"], eps)
    gate = jax.nn.silu((y @ p["w_gate"]).astype(jnp.float32))
    up = (y @ p["w_up"]).astype(jnp.float32)
    return x + (gate * up).astype(x.dtype) @ p["w_down"]


def logits(cfg, params, tokens):
    x = params["embed"][tokens]
    for i in range(cfg["num_hidden_layers"]):
        x = layer(cfg, jax.tree.map(lambda t: t[i], params["layers"]), x)
    x = rmsnorm(x, params["final_norm"], cfg["rms_norm_eps"])
    head = params.get("unembed")
    if head is None:
        head = params["embed"].T
    return (x @ head).astype(jnp.float32)


def loss(cfg, params, batch):
    """Mean next-token cross-entropy over every position."""
    z = logits(cfg, params, batch["tokens"])
    logz = jax.nn.logsumexp(z, axis=-1)
    gold = jnp.take_along_axis(z, batch["labels"][..., None], -1)[..., 0]
    return jnp.mean(logz - gold)
