"""FedNCV's one-epoch client pass streams its K microbatch gradients
through a running sum and sum of squares: it matches the stacked
`client_pass_flat` form, and a whole `make_round` round matches the
plain reference (`chipbench/reference/fedncv.py`).  The multi-epoch
branch is bit for bit what it was."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, REPO)

from chipbench.drivers.lm_round import arch_config  # noqa: E402
from chipbench.reference import dense_lm as ref_lm, fedncv as ref  # noqa
from repro.core import control_variates as cv  # noqa: E402
from repro.fed.api import get_method  # noqa: E402
from repro.fed.distributed import init_distributed_state, make_round  # noqa
from repro.fed.methods import (  # noqa: E402
    MethodConfig, Task, _k_of, _microbatch_grads, fedncv_client,
)
from repro.models import api, lenet  # noqa: E402
from repro.sharding import make_mesh  # noqa: E402
from repro.utils.tree_math import tree_axpy, tree_scale  # noqa: E402
from test_phi3_dense import TINY as PHI3  # noqa: E402

LENET = lenet.LeNetConfig(n_classes=4, image_size=16, channels=1)


def phi3_case(k=3, b=2, s=16, seed=0):
    arch = arch_config(PHI3)
    task = Task(loss=lambda p, bt: api.loss(arch, p, bt))
    params = ref_lm.init(PHI3, jax.random.PRNGKey(seed))
    t = jax.random.randint(jax.random.PRNGKey(seed + 1), (k, b, s + 1), 0,
                           PHI3["vocab_size"])
    return task, params, dict(tokens=t[..., :-1], labels=t[..., 1:])


def lenet_case(m=4, k=3, b=8, seed=0):
    task = Task(loss=lambda p, bt: lenet.loss_fn(LENET, p, bt))
    params = lenet.init(LENET, jax.random.PRNGKey(seed))
    key = jax.random.PRNGKey(seed + 1)
    return task, params, dict(
        images=jax.random.normal(key, (m, k, b, 16, 16, 1)),
        labels=jax.random.randint(key, (m, k, b), 0, 4))


def stacked(task, params, batches, alpha):
    """The one-epoch pass as it was: the (K, N) stack and the fused
    combine."""
    msg, stats, _ = cv.client_pass_flat(
        _microbatch_grads(task, params, batches), alpha)
    return msg, dict(k=stats.k, mean_norm_sq=stats.mean_norm_sq,
                     sum_norm_sq=stats.sum_norm_sq)


def close(a, b, rtol=1e-6):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
        scale = max(np.max(np.abs(y)), 1e-30)
        assert np.max(np.abs(x - y)) <= rtol * scale, \
            (np.max(np.abs(x - y)), scale)


def test_streamed_matches_stacked_dense():
    task, params, batches = phi3_case()
    mc = MethodConfig(name="fedncv")
    cs = dict(alpha=jnp.float32(0.3))
    out = jax.jit(lambda p, b: fedncv_client(mc, task, p, cs, b, None))(
        params, batches)
    msg, aux = jax.jit(lambda p, b: stacked(task, p, b, 0.3))(params,
                                                             batches)
    close(out.grad, msg)
    for k in aux:
        close(out.aux[k], aux[k])
    assert float(out.aux["k"]) == 3


def test_streamed_matches_stacked_lenet_under_cohort_vmap():
    """Under the Simulator's vmap over the cohort's clients, each with its
    own alpha and microbatches."""
    task, params, batches = lenet_case()
    mc = MethodConfig(name="fedncv")
    alphas = jnp.asarray([0.0, 0.2, 0.5, 0.9], jnp.float32)
    out = jax.jit(jax.vmap(lambda a, b: fedncv_client(
        mc, task, params, dict(alpha=a), b, None)))(alphas, batches)
    msg, aux = jax.jit(jax.vmap(lambda a, b: stacked(task, params, b, a)))(
        alphas, batches)
    close(out.grad, msg)
    for k in aux:
        close(out.aux[k], aux[k])
    close(out.aux["alpha"], alphas)


def two_epoch_as_it_was(mc, task, params, cstate, batches):
    """fedncv_client's multi-epoch branch before the one-epoch pass was
    streamed, copied verbatim."""
    alpha = cstate["alpha"]
    g_stack = _microbatch_grads(task, params, batches)
    _, stats, reshaped = cv.client_pass_flat(g_stack, alpha,
                                             want_reshaped=True)
    p_local = params

    def epoch(p, gs):
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
    grad = tree_scale(tree_axpy(1.0, msg, base), 0.5)
    aux = dict(mean_norm_sq=stats.mean_norm_sq,
               sum_norm_sq=stats.sum_norm_sq, k=stats.k, alpha=alpha)
    return grad, aux


@pytest.mark.parametrize("model", ["phi3", "lenet"])
def test_two_epochs_bit_for_bit(model):
    mc = MethodConfig(name="fedncv", local_epochs=2, local_lr=0.05)
    if model == "phi3":
        task, params, batches = phi3_case()
    else:
        task, params, batches = lenet_case()
        batches = jax.tree.map(lambda x: x[0], batches)
    cs = dict(alpha=jnp.float32(0.3))
    out = jax.jit(lambda p, b: fedncv_client(mc, task, p, cs, b, None))(
        params, batches)
    grad, aux = jax.jit(lambda p, b: two_epoch_as_it_was(
        mc, task, p, cs, b))(params, batches)
    for a, b in zip(jax.tree.leaves((out.grad, out.aux)),
                    jax.tree.leaves((grad, aux))):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_one_make_round_round_matches_reference():
    """One silo through `make_round` on a (1, 1) mesh, against the plain
    reference's upload, Eq. 10-12 and server step, and Algorithm 1's
    alpha update; the round's metrics carry the streamed statistics."""
    arch = arch_config(PHI3)
    task = Task(loss=lambda p, bt: api.loss(arch, p, bt))
    mc = MethodConfig(name="fedncv", ncv_alpha0=0.5, ncv_alpha_lr=0.2,
                      ncv_beta=0.0)
    lr = 0.03
    mesh = make_mesh((1, 1), ("cohort", "model"),
                     devices=jax.devices()[:1])
    round_fn = make_round("fedncv", task, mesh, mc, server_lr=lr)
    _, params, batches = phi3_case(k=2, b=2, s=16, seed=4)
    state = init_distributed_state(get_method("fedncv"), params, task, mc,
                                   n_clients=1)
    batch = jax.tree.map(lambda x: x[None], batches)
    with jax.default_matmul_precision("highest"):
        new_p, new_state, metrics = round_fn(
            params, state, batch, jnp.ones((1,), jnp.float32), jnp.int32(0))
        grad_fn = jax.grad(lambda p, b: ref_lm.loss(PHI3, p, b))
        mbs = [jax.tree.map(lambda x: x[k], batches) for k in range(2)]
        up, s1 = ref.client_upload(grad_fn, params, mbs, jnp.float32(0.5),
                                   1, 0.0)
        grads = [grad_fn(params, mb) for mb in mbs]
    agg = ref.aggregate([up], [1.0], 0.0)
    want_p = jax.tree.map(lambda p, g: p - lr * g, params, agg)
    want_alpha = ref.alpha_update(jnp.float32(0.5), s1, 0.2)
    s2 = sum(ref.sq_norm(g) for g in grads)
    close(new_p, want_p, rtol=1e-5)
    close(jax.tree.map(lambda p, q: p - q, new_p, params),
          jax.tree.map(lambda p, q: p - q, want_p, params), rtol=1e-4)
    close(new_state["alphas"][0], want_alpha, rtol=1e-6)
    assert float(new_state["alphas"][0]) > 0.5
    assert float(metrics["mean_k"]) == 2
    close(metrics["mean_mean_norm_sq"], s1, rtol=1e-5)
    close(metrics["mean_sum_norm_sq"], s2, rtol=1e-5)
