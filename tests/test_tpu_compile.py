"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e,
and the names they carry into a trace.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached (`jax.experimental.topologies`), so these tests
catch what interpret mode cannot: block shapes the Mosaic lowering
refuses, layouts it cannot broadcast, kernels GSPMD cannot partition.
Shapes only, at the widths the rounds run: LeNet-5 on cifar10 (the paper
protocol: cohort 10, K=4) and llama-100m (flat N ~ 1e8: K=2, cohort 4).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and under pytest-xdist
every worker imports this file.
"""
import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.kernels import over_gspmd_axes
from repro.kernels.rloo.rloo import (ncv_weighted_sum, ncv_weighted_sum_q,
                                     ncv_weighted_sum_q4, rloo_combine)
from repro.kernels.robust.robust import rank_band_mean

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "examples"))

CHUNK = 512
# width -> (K microbatches, cohort)
WIDTHS = {"lenet5": (4, 10), "llama100m": (2, 4)}


def flat_width(width):
    if width == "lenet5":
        from repro.models import lenet
        cfg = lenet.LeNetConfig(n_classes=10, image_size=32, channels=3)
        shapes = jax.eval_shape(lambda: lenet.init(cfg, jax.random.key(0)))
    else:
        from train_lm import model_100m
        from repro.models import api
        shapes = jax.eval_shape(
            lambda: api.init_params(model_100m(), jax.random.key(0)))
    return sum(math.prod(x.shape) for x in jax.tree.leaves(shapes))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def kernel_case(kernel, width, sds):
    """(fn, args) of one kernel call at `width`; sds(shape, dtype)."""
    k, m = WIDTHS[width]
    n = flat_width(width)
    c = -(-n // CHUNK)
    if kernel == "rloo_combine":
        return (lambda g, a: rloo_combine(g, a, interpret=False),
                (sds((k, n)), sds(())))
    if kernel == "rloo_combine_vmap":
        # the Simulator vmaps the client pass over its cohort
        return (jax.vmap(lambda g, a: rloo_combine(g, a, interpret=False)),
                (sds((m, k, n)), sds((m,))))
    if kernel == "ncv_weighted_sum":
        return (lambda g, w: ncv_weighted_sum(g, w, interpret=False),
                (sds((m, n)), sds((m,))))
    if kernel == "ncv_weighted_sum_q":
        return (lambda q, s, w: ncv_weighted_sum_q(q, s, w, interpret=False),
                (sds((m, c * CHUNK), jnp.int8), sds((m, c)), sds((m,))))
    if kernel == "ncv_weighted_sum_q4":
        return (lambda q, s, w: ncv_weighted_sum_q4(q, s, w,
                                                    interpret=False),
                (sds((m, c * CHUNK // 2), jnp.uint8), sds((m, c)),
                 sds((m,))))
    assert kernel == "rank_band_mean", kernel
    return (lambda g, a, lo, hi: rank_band_mean(g, a, lo, hi,
                                                interpret=False),
            (sds((m, n)), sds((m,)), sds(()), sds(())))


KERNELS = ["rloo_combine", "ncv_weighted_sum", "ncv_weighted_sum_q",
           "ncv_weighted_sum_q4", "rank_band_mean"]
CASES = [(k, w) for w in WIDTHS for k in KERNELS] + \
    [("rloo_combine_vmap", "lenet5")]


@pytest.mark.parametrize("kernel", KERNELS)
def test_kernel_lowers_for_tpu_under_its_public_name(kernel):
    """A trace names a Mosaic kernel by its `kernel_name`: each main-path
    kernel carries its public op's name, not its private body's.  A
    lowering for the TPU needs no TPU library, so no fixture."""
    fn, args = kernel_case(
        kernel, "lenet5",
        lambda s, dt=jnp.float32: jax.ShapeDtypeStruct(s, dt))
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text
    assert re.findall(r'kernel_name = "([^"]*)"', text) == [kernel]


@pytest.mark.parametrize("kernel,width", CASES)
def test_kernel_compiles_for_v5e(one_chip, kernel, width):
    fn, args = kernel_case(
        kernel, width,
        lambda s, dt=jnp.float32: jax.ShapeDtypeStruct(s, dt,
                                                       sharding=one_chip))
    assert "tpu_custom_call" in compiled_text(fn, *args)


def test_client_pass_kernel_compiles_under_model_axis(topo):
    """On a 2x2 (cohort, model) mesh the client pass runs inside a
    shard_map that is manual over "cohort" only; `over_gspmd_axes` splits
    the Mosaic call over the live "model" axis (GSPMD cannot partition
    it) at llama-100m width."""
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("cohort", "model"),
                axis_types=(AxisType.Auto,) * 2)
    n, (k, _) = flat_width("llama100m"), WIDTHS["llama100m"]

    def body(g, a):
        mean, gp, s2 = over_gspmd_axes(
            lambda x, y: rloo_combine(x, y, interpret=False), g[0], a[0])
        return mean[None], s2[None]

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P("cohort"), P("cohort")),
                       out_specs=(P("cohort"), P("cohort")),
                       axis_names={"cohort"}, check_vma=False)
    args = (jax.ShapeDtypeStruct((2, k, n), jnp.float32,
                                 sharding=NamedSharding(mesh, P("cohort"))),
            jax.ShapeDtypeStruct((2,), jnp.float32,
                                 sharding=NamedSharding(mesh, P("cohort"))))
    assert "tpu_custom_call" in compiled_text(fn, *args)


def test_lenet_microbatch_grads_compile_without_window_ops(one_chip):
    """The paper protocol's client pass, the per-microbatch LeNet-5
    gradients vmapped over the cohort (10 clients, K=4 microbatches of 16
    cifar10 images), compiles for the v5e with its max-pools as fused
    elementwise ops: no `reduce-window` forward, no `select-and-scatter`
    backward (both are layout-bound on the TPU)."""
    from repro.fed.methods import Task, _microbatch_grads
    from repro.models import lenet
    cfg = lenet.LeNetConfig(n_classes=10, image_size=32, channels=3)
    task = Task(loss=lambda p, b: lenet.loss_fn(cfg, p, b))
    k, m = WIDTHS["lenet5"]

    def sds(s, dt=jnp.float32):
        return jax.ShapeDtypeStruct(s, dt, sharding=one_chip)

    params = jax.tree.map(
        lambda x: sds(x.shape),
        jax.eval_shape(lambda: lenet.init(cfg, jax.random.key(0))))
    batches = {"images": sds((m, k, 16, 32, 32, 3)),
               "labels": sds((m, k, 16), jnp.int32)}
    with jax.default_matmul_precision("default"):
        text = compiled_text(
            jax.vmap(lambda p, b: _microbatch_grads(task, p, b), (None, 0)),
            params, batches)
    assert "convolution" in text
    assert "reduce-window" not in text
    assert "select-and-scatter" not in text
