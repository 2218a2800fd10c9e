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
    elementwise ops (no `reduce-window` forward, no `select-and-scatter`
    backward) and conv1 as a patch matmul: its weight gradient is no
    convolution grouped (`batch_group_count` or `feature_group_count`
    above 1) over the 40 (client, microbatch) groups with 3 channels a
    group on the lane axis.  All of these are layout-bound on the TPU.
    The TPU compiler rewrites a grouped convolution into an ungrouped one
    over a dilated window, so the groups are looked for in the module
    handed to it as well as in the compiled one.  conv2 (150-wide
    patches) keeps its convolution, whose weight gradient is the one
    grouped convolution handed over."""
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
        lowered = jax.jit(
            jax.vmap(lambda p, b: _microbatch_grads(task, p, b), (None, 0))
        ).lower(params, batches)
        handed = lowered.as_text(dialect="hlo")
        text = lowered.compile().as_text()
    grouped = [ln for ln in handed.splitlines()
               if re.search(r"_group_count=(?!1\b)\d+", ln)]
    assert grouped and all("f32[5,5,6," in ln for ln in grouped), grouped
    assert not re.search(r"_group_count=(?!1\b)\d+", text)
    assert "reduce-window" not in text
    assert "select-and-scatter" not in text


def test_phi3_silo_round_fits_one_v5e(one_chip, monkeypatch):
    """One round of the `phi3-mini-silo-1chip` cell at Phi-3-mini's
    published widths (4 of 32 layers, untied head, 6.5e8 f32 parameters;
    K=2 microbatches of 2 x 1024 tokens) through `make_round` on a (1, 1)
    mesh of one described v5e: it fits the chip's 16 GiB.  The one-epoch
    client pass streams its microbatch gradients, so no (K, N) stack and
    no `rloo_combine` is in the program (stacked, the compile asked for
    22.6 GB)."""
    import repro.kernels
    # the program's TPU branch, as on the chip: Mosaic kernels, not the
    # jnp fallbacks the CPU backend picks
    monkeypatch.setattr(repro.kernels, "default_interpret", lambda: False)
    root = os.path.join(os.path.dirname(__file__), "..")
    sys.path.insert(0, root)
    from chipbench import harness
    from chipbench.drivers.lm_round import arch_config
    from chipbench.reference import dense_lm as ref_model
    from repro.fed.api import get_method
    from repro.fed.distributed import init_distributed_state, make_round
    from repro.fed.methods import MethodConfig, Task
    from repro.models import api
    from repro.sharding import make_mesh

    base = os.path.join(root, "chipbench")
    cfg = harness.load_json(base, "configs", "phi3-mini-4k-4l.json")
    t = harness.load_json(base, "traffic", "silo-2x2x1024.json")
    m = harness.load_json(base, "workloads",
                          "phi3-mini-silo-1chip.json")["method"]
    arch = arch_config(cfg)
    mesh = make_mesh((1, 1), ("cohort", "model"),
                     devices=list(one_chip.device_set))
    rep, csh = NamedSharding(mesh, P()), NamedSharding(mesh, P("cohort"))
    mc = MethodConfig(name="fedncv", ncv_alpha0=m["alpha0"],
                      ncv_alpha_lr=m["alpha_lr"], ncv_beta=m["beta"])
    task = Task(loss=lambda p, b: api.loss(arch, p, b))
    round_fn = make_round("fedncv", task, mesh, mc, server_lr=m["server_lr"])
    shapes = jax.eval_shape(lambda: ref_model.init(cfg, jax.random.key(0)))
    state = jax.eval_shape(lambda: init_distributed_state(
        get_method("fedncv"), shapes, task, mc, n_clients=t["silos"]))

    def sds(tree, sharding):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=sharding), tree)

    toks = jax.ShapeDtypeStruct((t["silos"], t["k_micro"], t["batch"],
                                 t["seq"]), jnp.int32, sharding=csh)
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        compiled = round_fn.lower(
            sds(shapes, rep), sds(state, csh),
            dict(tokens=toks, labels=toks),
            jax.ShapeDtypeStruct((t["silos"],), jnp.float32, sharding=csh),
            jax.ShapeDtypeStruct((), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    total = mem.argument_size_in_bytes + mem.output_size_in_bytes \
        + mem.temp_size_in_bytes - mem.alias_size_in_bytes
    assert mem.argument_size_in_bytes > 2.5e9      # the 6.5e8 f32 params
    assert total < 16 * 2 ** 30, total
    assert "rloo_combine" not in compiled.as_text()
