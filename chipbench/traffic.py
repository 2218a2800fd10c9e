"""The one traffic generator: every cell's inputs come from its traffic
file (`traffic/<name>.json`) and the run's seed.

Two kinds of traffic, picked by the file's "kind":

* "image_clients": a cifar10-shaped synthetic image set (Gaussian class
  clusters with spatial jitter and label noise) split over `n_clients`
  by a Dirichlet(`alpha`) draw over each class.  The set and the split
  are fixed by the file's `data_seed`, as a real federation's data is
  fixed; the seed of a run draws the model and every cohort and
  microbatch.
* "token_silos": one Zipf(`zipf_a`) token stream with bigram repeats,
  cut into `silos` equal shards; each round draws, per silo, `k_micro`
  microbatches of `batch` windows of `seq` tokens, at offsets drawn from
  the seed and the round number.  Silo u weighs 1 + `silo_skew` u.
"""
from __future__ import annotations

import numpy as np


def image_set(t):
    """-> (images (N, S, S, C) f32, labels (N,) i32) for N = n_train +
    n_heldout, fixed by t["data_seed"]."""
    rng = np.random.default_rng(t["data_seed"])
    n = t["n_train"] + t["n_heldout"]
    s, c, k = t["image_size"], t["channels"], t["n_classes"]
    shared = rng.standard_normal((1, 8, 8, c)).astype(np.float32)
    delta = rng.standard_normal((k, 8, 8, c)).astype(np.float32)
    base = shared + t["class_sep"] * delta
    templates = np.kron(base, np.ones((1, s // 8 + 1, s // 8 + 1, 1)))
    templates = templates[:, :s, :s, :] * 0.5
    labels = rng.integers(0, k, size=n).astype(np.int32)
    images = templates[labels]
    shifts = rng.integers(-3, 4, size=(n, 2))
    for i in range(n):
        images[i] = np.roll(images[i], tuple(shifts[i]), axis=(0, 1))
    images = images + t["noise"] * rng.standard_normal(
        (n, s, s, c)).astype(np.float32)
    flip = rng.random(n) < t["label_noise"]
    labels[flip] = rng.integers(0, k, size=int(flip.sum())).astype(np.int32)
    return images.astype(np.float32), labels


def dirichlet_split(labels, n_clients, alpha, rng, min_per_client=2):
    """-> (client_idx (M, n_max) i32 padded with -1, sizes (M,) i32)."""
    buckets = [[] for _ in range(n_clients)]
    for c in np.unique(labels):
        idx_c = np.flatnonzero(labels == c)
        rng.shuffle(idx_c)
        props = rng.dirichlet([alpha] * n_clients)
        cuts = (np.cumsum(props) * len(idx_c)).astype(int)[:-1]
        for u, part in enumerate(np.split(idx_c, cuts)):
            buckets[u].extend(part.tolist())
    order = np.argsort([len(b) for b in buckets])
    donors = list(order[::-1])
    for u in order:
        while len(buckets[u]) < min_per_client:
            d = donors[0]
            if len(buckets[d]) <= min_per_client:
                break
            buckets[u].append(buckets[d].pop())
    n_max = max(len(b) for b in buckets)
    out = np.full((n_clients, n_max), -1, np.int32)
    sizes = np.zeros((n_clients,), np.int32)
    for u, b in enumerate(buckets):
        out[u, :len(b)] = b
        sizes[u] = len(b)
    return out, sizes


def image_clients(t):
    """-> (train dict as the Simulator takes it, heldout batch dict)."""
    images, labels = image_set(t)
    n = t["n_train"]
    rng = np.random.default_rng(t["data_seed"] + 1)
    idx, sizes = dirichlet_split(labels[:n], t["n_clients"], t["alpha"], rng)
    train = dict(images=images[:n], labels=labels[:n], client_idx=idx,
                 client_sizes=sizes)
    heldout = dict(images=images[n:], labels=labels[n:])
    return train, heldout


def token_stream(t, vocab, seed):
    rng = np.random.default_rng([seed, 0])
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    probs = ranks ** (-t["zipf_a"])
    toks = rng.choice(vocab, size=t["stream_tokens"],
                      p=probs / probs.sum()).astype(np.int32)
    toks[3::4] = toks[2::4][: len(toks[3::4])]
    return toks


def silo_weights(t):
    return np.asarray([1.0 + t["silo_skew"] * u for u in range(t["silos"])],
                      np.float32)


def silo_round(t, toks, seed, r):
    """Round r's batch: dict of (silos, k_micro, batch, seq) int32."""
    n_s, k, b, s = t["silos"], t["k_micro"], t["batch"], t["seq"]
    cut = len(toks) // n_s
    rng = np.random.default_rng([seed, 1, r])
    starts = rng.integers(0, cut - s - 1, size=(n_s, k, b)) \
        + (np.arange(n_s) * cut)[:, None, None]
    pos = starts[..., None] + np.arange(s + 1)
    window = toks[pos]
    return dict(tokens=window[..., :-1], labels=window[..., 1:])


def heldout_tokens(t, toks, seed):
    """Windows for the loss comparison, drawn apart from every round."""
    rng = np.random.default_rng([seed, 2])
    s = t["seq"]
    starts = rng.integers(0, len(toks) - s - 1, size=t["heldout_windows"])
    window = toks[starts[:, None] + np.arange(s + 1)]
    return dict(tokens=window[:, :-1], labels=window[:, 1:])
