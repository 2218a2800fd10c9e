"""Dense decoder-only LM family.

Covers: mistral-large-123b, llama3.2-3b, phi3-mini-3.8b (uniform causal
layers) and gemma2-9b (alternating local/global attention + logit softcap).

Layers are scanned in *groups*: a group is the repeating attention pattern
(1 layer for uniform models, 2 for gemma2's local/global pair, `global_period`
for llama4-style chunked models).  Group members are unrolled statically
inside the scan body, so per-member attention flavor is resolved at trace
time while the HLO stays constant-size in depth.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers as L


# --------------------------------------------------------------------------
# layer pattern
# --------------------------------------------------------------------------

def group_size(cfg: ArchConfig) -> int:
    if cfg.local_global_period:
        return 2
    if cfg.global_period:
        return cfg.global_period
    return 1


def member_kind(cfg: ArchConfig, j: int) -> str:
    """Attention flavor of group member j: 'full' | 'local' | 'chunked'.
    A uniform model with a sliding window (phi3) windows every layer."""
    if cfg.local_global_period:
        return "local" if j % 2 == 0 else "full"
    if cfg.global_period:
        return "full" if j == cfg.global_period - 1 else "chunked"
    return "local" if cfg.sliding_window else "full"


def _attn_spec(cfg: ArchConfig) -> L.AttnParamsSpec:
    return L.AttnParamsSpec(cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd)


# --------------------------------------------------------------------------
# params
# --------------------------------------------------------------------------

def _stacked_layer_params(cfg: ArchConfig, key, n_layers, dtype):
    spec = _attn_spec(cfg)
    shapes = L.attn_param_shapes(spec)
    d, f = cfg.d_model, cfg.d_ff
    names = sorted(shapes) + ["w_gate", "w_up", "w_down"]
    all_shapes = dict(shapes, w_gate=(d, f), w_up=(d, f), w_down=(f, d))
    keys = jax.random.split(key, len(names))
    out = {n: L.dense_init(k, (n_layers,) + all_shapes[n], dtype)
           for n, k in zip(names, keys)}
    out["attn_norm"] = jnp.zeros((n_layers, d), dtype)
    out["ffn_norm"] = jnp.zeros((n_layers, d), dtype)
    return out


def init(cfg: ArchConfig, key):
    dtype = jnp.dtype(cfg.dtype)
    k_embed, k_layers, k_out = jax.random.split(key, 3)
    params = {
        "embed": L.embed_init(k_embed, (cfg.vocab, cfg.d_model), dtype),
        "layers": _stacked_layer_params(cfg, k_layers, cfg.n_layers, dtype),
        "final_norm": jnp.zeros((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = L.dense_init(k_out, (cfg.d_model, cfg.vocab), dtype)
    return params


# --------------------------------------------------------------------------
# forward (train / prefill)
# --------------------------------------------------------------------------

def _group_xs(cfg: ArchConfig, layer_params):
    """Reshape stacked (L, ...) leaves into (n_groups, group, ...)."""
    g = group_size(cfg)
    n_groups = cfg.n_layers // g
    return jax.tree.map(
        lambda x: x.reshape((n_groups, g) + x.shape[1:]), layer_params)


def _member_attn(cfg: ArchConfig, p, x, positions, j):
    kind = member_kind(cfg, j)
    spec = _attn_spec(cfg)
    kw = dict(rope_theta=cfg.rope_theta, softcap=cfg.softcap)
    if kind == "local":
        kw["window"] = cfg.sliding_window
    elif kind == "chunked":
        kw["chunk"] = cfg.attn_chunk
    return L.attention_block(p, x, positions, spec, causal=True, **kw)


def _layer_body(cfg: ArchConfig, p_j, x, positions, j):
    h = L.rmsnorm(x, p_j["attn_norm"], cfg.norm_eps)
    x = x + _member_attn(cfg, p_j, h, positions, j)
    h = L.rmsnorm(x, p_j["ffn_norm"], cfg.norm_eps)
    x = x + L.swiglu(p_j, h)
    return L.shard_residual(x)


def forward(cfg: ArchConfig, params, tokens):
    """tokens: (B, S) int32 -> logits (B, S, V) f32."""
    b, s = tokens.shape
    x = L.shard_batch(params["embed"][tokens])
    if cfg.softcap is not None:                     # gemma-style input scaling
        x = x * jnp.asarray(jnp.sqrt(cfg.d_model), x.dtype)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    g = group_size(cfg)

    def body(x, p_group):
        for j in range(g):
            p_j = jax.tree.map(lambda t: t[j], p_group)
            x = _layer_body(cfg, p_j, x, positions, j)
        return x, None

    xs = _group_xs(cfg, params["layers"])
    if cfg.scan_layers:
        x, _ = jax.lax.scan(body, x, xs)
    else:
        # unrolled depth loop (static indexing): see ArchConfig.scan_layers
        for i in range(jax.tree.leaves(xs)[0].shape[0]):
            x, _ = body(x, jax.tree.map(lambda t: t[i], xs))
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    unembed = params.get("unembed")
    if unembed is None:
        unembed = params["embed"].T
    logits = L.shard_logits((x @ unembed).astype(jnp.float32))
    if cfg.softcap is not None:                     # gemma2 final logit softcap
        logits = 30.0 * jnp.tanh(logits / 30.0)
    return logits


def loss_fn(cfg: ArchConfig, params, batch):
    logits = forward(cfg, params, batch["tokens"])
    return L.softmax_xent(logits, batch["labels"])


# --------------------------------------------------------------------------
# decode (one token against a KV cache)
# --------------------------------------------------------------------------

# Documented deviation (DESIGN.md §4): at very long decode contexts, "global"
# full-attention layers of sub-quadratic archs (gemma2, llama4) fall back to a
# windowed ring cache of this size — the full 500k cache is exactly the
# quadratic-memory case long_500k excludes.
LONG_DECODE_GLOBAL_WINDOW = 32_768


def _member_cache_len(cfg: ArchConfig, j: int, cache_len: int) -> int:
    kind = member_kind(cfg, j)
    if kind == "local":
        return min(cfg.sliding_window, cache_len)
    if kind == "chunked":
        return min(cfg.attn_chunk, cache_len)
    if cfg.supports_long_decode and cache_len > LONG_DECODE_GLOBAL_WINDOW:
        return LONG_DECODE_GLOBAL_WINDOW
    return cache_len


def _member_mode(cfg: ArchConfig, j: int, cache_len: int) -> str:
    kind = member_kind(cfg, j)
    if kind == "local" and cfg.sliding_window < cache_len:
        return "ring"
    if kind == "chunked" and cfg.attn_chunk < cache_len:
        return "chunk_ring"
    if (kind == "full" and cfg.supports_long_decode
            and cache_len > LONG_DECODE_GLOBAL_WINDOW):
        return "ring"
    return "full"


def init_cache(cfg: ArchConfig, batch, cache_len, dtype=None):
    """Per-group-member cache stacks keyed 'm<j>': (n_groups, B, C_j, KV, hd)."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    g = group_size(cfg)
    n_groups = cfg.n_layers // g
    caches = {}
    for j in range(g):
        cj = _member_cache_len(cfg, j, cache_len)
        caches[f"m{j}"] = L.init_kv_cache(n_groups, batch, cj,
                                          cfg.n_kv_heads, cfg.hd, dtype)
    return caches


def decode_step(cfg: ArchConfig, params, cache, tokens, pos):
    """tokens: (B, 1) int32, pos: scalar int32 -> (logits (B,1,V) f32, cache)."""
    x = L.shard_batch(params["embed"][tokens])
    if cfg.softcap is not None:
        x = x * jnp.asarray(jnp.sqrt(cfg.d_model), x.dtype)
    g = group_size(cfg)
    spec = _attn_spec(cfg)
    cache_len = max(c["k"].shape[2] for c in cache.values())

    def body(x, xs):
        p_group, cache_group = xs
        new_cache = {}
        for j in range(g):
            p_j = jax.tree.map(lambda t: t[j], p_group)
            ck, cv = cache_group[f"m{j}"]["k"], cache_group[f"m{j}"]["v"]
            h = L.rmsnorm(x, p_j["attn_norm"], cfg.norm_eps)
            out, ck, cv = L.decode_attention_block(
                p_j, h, ck, cv, pos, spec,
                mode=_member_mode(cfg, j, cache_len),
                softcap=cfg.softcap, rope_theta=cfg.rope_theta)
            x = x + out
            h = L.rmsnorm(x, p_j["ffn_norm"], cfg.norm_eps)
            x = x + L.swiglu(p_j, h)
            new_cache[f"m{j}"] = dict(k=ck, v=cv)
        return x, new_cache

    x, new_cache = jax.lax.scan(body, x, (_group_xs(cfg, params["layers"]),
                                          cache))
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    unembed = params.get("unembed")
    if unembed is None:
        unembed = params["embed"].T
    logits = (x @ unembed).astype(jnp.float32)
    if cfg.softcap is not None:
        logits = 30.0 * jnp.tanh(logits / 30.0)
    return logits, new_cache