"""FedNCV (arXiv:2310.17200, Algorithm 1) written out plainly.

Client level (Eq. 8-9): over K microbatch gradients g_i of one client,
the RLOO-reshaped units are g'_i = g_i - alpha c_i with the leave-one-out
baseline c_i = mean_{j != i} g_j, and the upload is mean_i g'_i.  Server
level (Eq. 10-12): over the cohort's uploads g_u with sample counts n_u,
c_{V\\u} = sum_{v != u} n_v g_v / (n - n_u) and the aggregate is
sum_u p_u (g_u - beta c_{V\\u}), p_u = n_u / n.  The server takes one SGD
step with the aggregate and moves each client's alpha by Algorithm 1
line 12, alpha <- clip(alpha + lr_alpha 2 (1 - alpha) ||mean_i g_i||^2).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def tmap(f, *trees):
    return jax.tree.map(f, *trees)


def sq_norm(tree):
    return sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
               for x in jax.tree.leaves(tree))


def rloo_units(grads, alpha):
    """grads: list of K gradient pytrees -> (list of g'_i, mean_i g_i)."""
    k = len(grads)
    mean = tmap(lambda *g: sum(g) / k, *grads)
    units = []
    for i, g in enumerate(grads):
        others = [h for j, h in enumerate(grads) if j != i]
        c = tmap(lambda *h: sum(h) / (k - 1), *others)
        units.append(tmap(lambda a, b: a - alpha * b, g, c))
    return units, mean


def client_upload(grad_fn, params, batches, alpha, local_epochs, local_lr):
    """One client's upload and its statistic S1 = ||mean_i g_i||^2.

    batches: list of K microbatches.  local_epochs == 1 uploads
    mean_i g'_i = (1 - alpha) mean_i g_i.  With more epochs the client
    first takes (local_epochs - 1) passes of K sequential SGD steps along
    the reshaped units, then uploads the average of the final pass's
    reshaped mean and its drift (params - p_local) / (lr (E - 1) K).
    """
    grads = [grad_fn(params, b) for b in batches]
    units, mean = rloo_units(grads, alpha)
    if local_epochs == 1:
        upload = tmap(lambda *u: sum(u) / len(u), *units)
        return upload, sq_norm(mean)
    p_local = params
    for _ in range(local_epochs - 1):
        for u in units:
            p_local = tmap(lambda p, g: p - local_lr * g, p_local, u)
        grads = [grad_fn(p_local, b) for b in batches]
        units, mean = rloo_units(grads, alpha)
    msg = tmap(lambda *u: sum(u) / len(u), *units)
    k = len(batches)
    drift = tmap(lambda a, b: (a - b) / (local_lr * (local_epochs - 1) * k),
                 params, p_local)
    return tmap(lambda a, b: 0.5 * (a + b), msg, drift), sq_norm(mean)


def aggregate(uploads, n_samples, beta):
    """Eq. 10-12 over the cohort's uploads (a list of pytrees)."""
    n = sum(n_samples)
    agg = None
    for u, g_u in enumerate(uploads):
        rest = [(v, g) for v, g in enumerate(uploads) if v != u]
        if rest:
            c_u = tmap(lambda *gs: sum(n_samples[v] * g for (v, _), g
                                       in zip(rest, gs)) / (n - n_samples[u]),
                       *[g for _, g in rest])
        else:
            c_u = tmap(jnp.zeros_like, g_u)
        term = tmap(lambda g, c: (n_samples[u] / n) * (g - beta * c), g_u, c_u)
        agg = term if agg is None else tmap(jnp.add, agg, term)
    return agg


def alpha_update(alpha, s1, lr_alpha):
    """Algorithm 1 line 12, clamped to [0, 1]."""
    return jnp.clip(alpha + lr_alpha * 2.0 * (1.0 - alpha) * s1, 0.0, 1.0)
