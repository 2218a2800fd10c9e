"""Decoder-only LM operations (configs/phi3-mini-4l.json): per token,
6 x the matmul parameters (the output head included, the embedding
lookup not) plus causal attention, whose scores and weighted values
cover (seq + 1) / 2 positions on average."""
from __future__ import annotations


def _dims(cfg):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    return d, h, kv, hd, cfg["intermediate_size"], cfg["vocab_size"], \
        cfg["num_hidden_layers"]


def matmul_params(cfg):
    d, h, kv, hd, f, v, n = _dims(cfg)
    per_layer = d * h * hd * 2 + d * kv * hd * 2 + 3 * d * f
    return n * per_layer + d * v


def n_params(cfg):
    """Every parameter: matmuls, the embedding table, the norms."""
    d, _, _, _, _, v, n = _dims(cfg)
    tied = cfg.get("tie_word_embeddings", False)
    return matmul_params(cfg) + (0 if tied else d * v) + 2 * n * d + d


def train_flops_per_token(cfg, seq):
    d, h, kv, hd, f, v, n = _dims(cfg)
    attn = 2 * 2 * n * h * hd * (seq + 1) / 2      # QK^T and PV, forward
    return 6 * matmul_params(cfg) + 3 * attn
