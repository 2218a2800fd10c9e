"""Client/server building blocks of the federated methods: FedNCV (the
paper) + the six comparison baselines from Table 1 (FedAvg, FedProx,
SCAFFOLD, FedRep, FedPer, pFedSim) + the beyond-paper FedNCV+ (stale server
control variates, FedVARP-style).

These are pure, vmap/pjit-friendly functions over a fixed structure:
`batches` is a pytree whose leaves are stacked (K, micro_batch, ...) — the
K RLOO units.  The typed strategy objects that bind them into runnable
methods (state specs, server updates, the registry) live in `fed/api.py`;
runtimes never dispatch on method names, only on `FedMethod` instances.
"""
from __future__ import annotations

import dataclasses
import typing as tp

import jax
import jax.numpy as jnp

from repro.core import control_variates as cv
from repro.utils.tree_math import (
    tree_axpy, tree_mean, tree_norm_sq, tree_scale, tree_sub,
    tree_zeros_like, unravel,
)


@dataclasses.dataclass(frozen=True)
class Task:
    """Binds a model to the FL runtime."""
    loss: tp.Callable            # (params, batch) -> scalar
    head_keys: tuple = ()        # top-level param keys that stay personal
    accuracy: tp.Callable | None = None


@dataclasses.dataclass(frozen=True)
class MethodConfig:
    name: str
    local_lr: float = 0.05
    local_epochs: int = 1
    prox_mu: float = 0.1         # FedProx
    ncv_alpha0: float = 0.5      # FedNCV initial alpha_u
    ncv_alpha_lr: float = 1e-3   # Algorithm 1 line 12 step size
    ncv_beta: float = 1.0        # server-side CV coefficient (paper: 1)
    ncv_alpha_mode: str = "descent"   # "descent" (Alg.1) | "optimal" (Prop.2)
    head_local_steps: int = 3    # FedRep: head-only steps before body pass
    glomo_beta_global: float = 0.9   # FedGLOMO: server momentum coefficient
    glomo_beta_local: float = 0.5    # FedGLOMO: client heavy-ball coefficient


class ClientOut(tp.NamedTuple):
    grad: tp.Any                 # uploaded gradient-like pytree
    cstate: tp.Any               # new per-client state
    aux: tp.Any                  # scalar diagnostics dict


def _aggregate(grads_stacked, n_samples, beta, codec, spec):
    """Cohort aggregation: dense flat path, or straight off the wire."""
    if codec is None:
        return cv.networked_aggregate_flat(grads_stacked, n_samples,
                                           beta=beta)
    from repro import comm
    agg_vec, agg_norm = comm.aggregate_wire(codec, grads_stacked, n_samples,
                                            beta=beta)
    return unravel(agg_vec, spec), agg_norm


def _body_mask(task: Task, params):
    """1.0 for body (aggregated) leaves, 0.0 for personal-head leaves."""
    return {k: jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32) if k in
                            task.head_keys else jnp.ones_like(x, jnp.float32),
                            v) for k, v in params.items()} \
        if isinstance(params, dict) else jax.tree.map(
            lambda x: jnp.ones_like(x, jnp.float32), params)


def _microbatch_grads(task: Task, params, batches):
    """Per-microbatch gradients at fixed params: leaves (K, ...)."""
    return jax.vmap(lambda mb: jax.grad(task.loss)(params, mb))(batches)


def _streamed_mean_grad(task: Task, params, batches):
    """The K microbatch gradients at fixed params, one after another:
    -> (gbar = mean_i g_i in f32, S2 = sum_i ||g_i||^2).

    Holds a running f32 sum in place of the (K, N) gradient stack, so
    one microbatch's activations and gradient are live beside it.
    Unrolled like `_sgd_epoch`; the optimization barrier makes
    microbatch i+1's pass wait for microbatch i's sum, so XLA cannot
    interleave the K passes and hold their activations at once."""
    g_sum, s2 = None, jnp.zeros((), jnp.float32)
    for k in range(_k_of(batches)):
        mb = jax.tree.map(lambda x: x[k], batches)
        g = jax.tree.map(lambda x: x.astype(jnp.float32),
                         jax.grad(task.loss)(params, mb))
        s2 = s2 + tree_norm_sq(g)
        g_sum = g if g_sum is None else jax.tree.map(jnp.add, g_sum, g)
        params, g_sum, s2 = jax.lax.optimization_barrier((params, g_sum, s2))
    return tree_scale(g_sum, 1.0 / _k_of(batches)), s2


def _sgd_epoch(task: Task, params, batches, lr, grad_tx=None):
    """One pass of sequential SGD over the K microbatches.

    Unrolled on purpose (K is a small static constant): a `lax.scan`
    whose carry is model-sharded aborts the SPMD partitioner inside the
    2-d fed mesh's partially-manual shard_map region (DESIGN.md §13.1),
    and the unrolled form is the identical computation."""
    for k in range(_k_of(batches)):
        mb = jax.tree.map(lambda x: x[k], batches)
        g = jax.grad(task.loss)(params, mb)
        if grad_tx is not None:
            g = grad_tx(params, g)
        params = jax.tree.map(lambda pi, gi: pi - lr * gi, params, g)
    return params


def _k_of(batches) -> int:
    return jax.tree.leaves(batches)[0].shape[0]


# ---------------------------------------------------------------------------
# FedAvg
# ---------------------------------------------------------------------------

def fedavg_client(mc: MethodConfig, task: Task, params, cstate, batches, key):
    """local_epochs == 1 reproduces the paper's Eq. (2) exactly: one mean
    gradient evaluated at theta_t.  local_epochs > 1 is McMahan-style
    multi-step local SGD (cumulative gradient upload)."""
    del key
    if mc.local_epochs == 1:
        grad = tree_mean(_microbatch_grads(task, params, batches), axis=0)
        return ClientOut(grad, cstate, dict())
    p_local = params
    for _ in range(mc.local_epochs):
        p_local = _sgd_epoch(task, p_local, batches, mc.local_lr)
    k = _k_of(batches)
    denom = mc.local_lr * mc.local_epochs * k
    grad = jax.tree.map(lambda a, b: (a - b) / denom, params, p_local)
    return ClientOut(grad, cstate, dict())


# ---------------------------------------------------------------------------
# FedProx: proximal term mu/2 ||p - p_t||^2 in the local objective
# ---------------------------------------------------------------------------

def fedprox_client(mc: MethodConfig, task: Task, params, cstate, batches, key):
    del key
    anchor = params

    def prox_grad(p, g):
        return jax.tree.map(lambda gi, pi, ai: gi + mc.prox_mu * (pi - ai),
                            g, p, anchor)

    p_local = params
    for _ in range(mc.local_epochs):
        p_local = _sgd_epoch(task, p_local, batches, mc.local_lr,
                             grad_tx=prox_grad)
    k = _k_of(batches)
    denom = mc.local_lr * mc.local_epochs * k
    grad = jax.tree.map(lambda a, b: (a - b) / denom, params, p_local)
    return ClientOut(grad, cstate, dict())


# ---------------------------------------------------------------------------
# SCAFFOLD: local gradients corrected by (c - c_u); client keeps c_u
# ---------------------------------------------------------------------------

def scaffold_client(mc: MethodConfig, task: Task, params, cstate, batches,
                    key):
    del key
    c_global, c_u = cstate["c_global"], cstate["c_u"]

    def corr(p, g):
        return jax.tree.map(lambda gi, cg, cu: gi - cu + cg, g, c_global, c_u)

    p_local = params
    for _ in range(mc.local_epochs):
        p_local = _sgd_epoch(task, p_local, batches, mc.local_lr, grad_tx=corr)
    k = _k_of(batches)
    steps = mc.local_epochs * k
    denom = mc.local_lr * steps
    grad = jax.tree.map(lambda a, b: (a - b) / denom, params, p_local)
    # c_u+ = c_u - c + (1/(steps*lr)) (x - y_local)  (SCAFFOLD option II)
    c_u_new = jax.tree.map(lambda cu, cg, g: cu - cg + g, c_u, c_global, grad)
    delta_c = tree_sub(c_u_new, c_u)
    return ClientOut(grad, dict(cstate, c_u=c_u_new), dict(delta_c=delta_c))


# ---------------------------------------------------------------------------
# FedNCV (the paper, Algorithm 1)
# ---------------------------------------------------------------------------

def fedncv_client(mc: MethodConfig, task: Task, params, cstate, batches, key):
    """Client side of Algorithm 1 (lines 3-8).

    Computes per-microbatch gradients (the RLOO units), reshapes them with the
    leave-one-out baseline scaled by alpha_u, optionally takes local SGD steps
    with the reshaped gradients, and uploads the expectation gradient plus the
    two sufficient statistics the server needs to adapt alpha_u
    (DESIGN.md §1.2 — the whole RLOO pass costs 2 extra scalars).

    One local epoch needs no reshaped unit: mean_i (g_i - a c_i) =
    (1 - a) gbar, so the gradients stream through a running sum and sum
    of squares (`_streamed_mean_grad`) and no (K, N) stack is formed.
    """
    del key
    alpha = cstate["alpha"]

    if mc.local_epochs > 1:
        g_stack = _microbatch_grads(task, params, batches)
        # Multi-step variant: apply RLOO-reshaped gradients sequentially.
        _, stats, reshaped = cv.client_pass_flat(g_stack, alpha,
                                                 want_reshaped=True)
        p_local = params

        def epoch(p, gs):
            # unrolled like _sgd_epoch: a model-sharded lax.scan carry
            # aborts the partitioner in the 2-d mesh's shard_map region
            for i in range(_k_of(batches)):
                g = jax.tree.map(lambda x: x[i], gs)
                p = jax.tree.map(lambda pi, gi: pi - mc.local_lr * gi, p, g)
            return p
        for _ in range(mc.local_epochs - 1):
            p_local = epoch(p_local, reshaped)
            g_stack = _microbatch_grads(task, p_local, batches)
            msg, stats, reshaped = cv.client_pass_flat(g_stack, alpha,
                                                       want_reshaped=True)
        k = _k_of(batches)
        base = jax.tree.map(
            lambda a, b: (a - b) / (mc.local_lr * (mc.local_epochs - 1) * k),
            params, p_local)
        grad = tree_axpy(1.0, msg, base)
        grad = tree_scale(grad, 0.5)   # average drift + final reshaped grad
    else:
        mean, s2 = _streamed_mean_grad(task, params, batches)
        stats = cv.ClientCVStats(mean,
                                 jnp.asarray(_k_of(batches), jnp.float32),
                                 tree_norm_sq(mean), s2)
        grad = cv.client_message(stats, jnp.asarray(alpha, jnp.float32))

    aux = dict(mean_norm_sq=stats.mean_norm_sq, sum_norm_sq=stats.sum_norm_sq,
               k=stats.k, alpha=alpha)
    return ClientOut(grad, cstate, aux)


# ---------------------------------------------------------------------------
# FedNCV+ (beyond paper): stale per-client control variates at the server.
# Under partial participation the within-round LOO baseline only sees the
# cohort; keeping h_u = last uploaded gradient per client gives the SAGA-style
# estimator  g = mean_all(h) + mean_cohort(g_u - h_u), unbiased and lower
# variance when client gradients are temporally correlated.
# ---------------------------------------------------------------------------

def fedncv_plus_server(mc, task, params, grads_stacked, n_samples, idx,
                       sstate, lr, m_total, invp=None, alive=None):
    """mean_all(h) comes from the running sum `h_sum` kept in `sstate` and
    updated incrementally at the cohort indices, so the per-round cost is
    O(cohort * N) instead of re-reducing all M_total stale gradients.

    `invp` ((cohort,) or None): inverse-probability factors 1/(M q_u) of a
    non-uniform cohort sampler (repro.fed.sampling, DESIGN.md §8.2).  The
    correction term is the sampled estimate of mean_all(g - h), so under
    non-uniform selection each term is Horvitz-Thompson-weighted:
    corr = (1/C) sum_u invp_u (g_u - h_u).  None (or all-ones, i.e.
    uniform/exchangeable selection) is the plain cohort mean.  The h-table
    bookkeeping (h_all scatter, h_sum increment) always uses the raw
    deltas — it tracks the table exactly, not an expectation.

    `alive` ((cohort,) 0/1 or None): under a dropping fault model
    (repro.fed.faults, DESIGN.md §9) a dropped client uploaded nothing,
    so its h-table row must keep the previous value and contribute no
    delta — the correction term's dropout compensation rides `invp`
    (whose dead rows are exactly 0), while the table bookkeeping is
    masked directly."""
    h_all, h_sum = sstate["h"], sstate["h_sum"]   # (M_total, ...), (...)
    h_mean = tree_scale(h_sum, 1.0 / m_total)
    h_cohort = jax.tree.map(lambda h: h[idx], h_all)
    delta = tree_sub(grads_stacked, h_cohort)     # leaves (cohort, ...)
    if invp is None:
        corr = tree_mean(delta, axis=0)
    else:
        corr = jax.tree.map(
            lambda d: jnp.mean(
                d * invp.reshape((-1,) + (1,) * (d.ndim - 1)), axis=0),
            delta)
    agg = jax.tree.map(jnp.add, h_mean, corr)
    params = jax.tree.map(lambda p, g: p - lr * g.astype(p.dtype), params, agg)
    if alive is not None:
        am = lambda x: alive.reshape((-1,) + (1,) * (x.ndim - 1))
        grads_stacked = jax.tree.map(
            lambda g, h: jnp.where(am(g) > 0, g, h), grads_stacked, h_cohort)
        delta = jax.tree.map(lambda d: d * am(d), delta)
    h_all = jax.tree.map(lambda h, g: h.at[idx].set(g), h_all, grads_stacked)
    h_sum = jax.tree.map(lambda s, d: s + jnp.sum(d, axis=0), h_sum, delta)
    return params, dict(sstate, h=h_all, h_sum=h_sum), \
        dict(agg_norm=tree_norm_sq(agg))


# ---------------------------------------------------------------------------
# Personalization baselines: FedRep / FedPer / pFedSim
# ---------------------------------------------------------------------------

def _split_update(task, params, personal):
    """Overlay personal head leaves onto global params."""
    return {k: (personal[k] if k in task.head_keys else v)
            for k, v in params.items()}


def fedper_client(mc: MethodConfig, task: Task, params, cstate, batches, key):
    """FedPer: train body+head locally; upload body delta; keep head."""
    del key
    p_local = _split_update(task, params, cstate["personal"])
    start = p_local
    for _ in range(mc.local_epochs):
        p_local = _sgd_epoch(task, p_local, batches, mc.local_lr)
    k = _k_of(batches)
    denom = mc.local_lr * mc.local_epochs * k
    grad = jax.tree.map(lambda a, b: (a - b) / denom, start, p_local)
    grad = {kk: (tree_zeros_like(v) if kk in task.head_keys else v)
            for kk, v in grad.items()}
    personal = {kk: p_local[kk] for kk in task.head_keys}
    return ClientOut(grad, dict(cstate, personal=personal), dict())


def fedrep_client(mc: MethodConfig, task: Task, params, cstate, batches, key):
    """FedRep: first fit the personal head (body frozen), then the body."""
    del key
    p_local = _split_update(task, params, cstate["personal"])

    def head_only(p, g):
        return {kk: (gv if kk in task.head_keys else tree_zeros_like(gv))
                for kk, gv in g.items()}

    def body_only(p, g):
        return {kk: (tree_zeros_like(gv) if kk in task.head_keys else gv)
                for kk, gv in g.items()}

    for _ in range(mc.head_local_steps):
        p_local = _sgd_epoch(task, p_local, batches, mc.local_lr,
                             grad_tx=head_only)
    start = p_local
    for _ in range(mc.local_epochs):
        p_local = _sgd_epoch(task, p_local, batches, mc.local_lr,
                             grad_tx=body_only)
    k = _k_of(batches)
    denom = mc.local_lr * mc.local_epochs * k
    grad = jax.tree.map(lambda a, b: (a - b) / denom, start, p_local)
    grad = {kk: (tree_zeros_like(v) if kk in task.head_keys else v)
            for kk, v in grad.items()}
    personal = {kk: p_local[kk] for kk in task.head_keys}
    return ClientOut(grad, dict(cstate, personal=personal), dict())


def pfedsim_client(mc: MethodConfig, task: Task, params, cstate, batches, key):
    """pFedSim (simplified): FedAvg-style body training with a personal
    classifier; the similarity-weighted classifier aggregation happens
    server-side from the uploaded head vectors."""
    out = fedper_client(mc, task, params, cstate, batches, key)
    head_flat = jnp.concatenate([jnp.ravel(cstate["personal"][k])
                                 for k in task.head_keys])
    return out._replace(aux=dict(head=head_flat))


def pfedsim_server_mix(heads, personals, temp=5.0):
    """Similarity-aware mixing of personal heads (pFedSim's model-similarity
    aggregation, on the classifier only). heads: (M, d) flattened."""
    norm = heads / (jnp.linalg.norm(heads, axis=1, keepdims=True) + 1e-8)
    sim = norm @ norm.T                                   # (M, M)
    w = jax.nn.softmax(temp * sim, axis=1)                # row-stochastic
    return jax.tree.map(
        lambda ph: jnp.einsum("mn,n...->m...", w, ph), personals)
