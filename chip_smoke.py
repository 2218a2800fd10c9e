"""Smoke run of the FedNCV round on a TPU, through the normal entry points.

    python chip_smoke.py                # one chip: phases A and B
    python chip_smoke.py --four-chips   # four chips: the mesh phases only

Phase A, the paper protocol: `Simulator` with `fedncv` on LeNet-5 over the
cifar10-shaped synthetic Dirichlet(0.1) split, at `benchmarks/bench_fl.py`'s
full protocol (40 clients, cohort 10, K=4 microbatches of 16, 2 local
epochs), `run_rounds(5)` twice and `evaluate` once, with the identity wire
and with int8.  Phase B, federated LM at full width: the llama-100m preset
of `examples/train_lm.py` (12 x 768, 32k vocab, f32) through
`fed.distributed.make_round` on `fed_mesh(1, 1)` for 3 rounds, K=2, batch
8, seq 256, with the identity wire and with lowrank r=16.  Phase A checks
that its compiled program holds the Mosaic kernels (`tpu_custom_call`);
the LM round's one-epoch client pass streams its gradients and calls
none.  Every phase checks that loss, state and params are finite, and
that the params moved; phase B also checks that no round's `agg_norm`
grows to ten times the first round's (a diverging codec stays finite for
a while).

`--four-chips` runs, and compares, only what exists across chips: phase A
on `cohort_mesh(4)` against one device, llama-100m on `fed_mesh(4, 1)`
against a sequential one-device reference of the same 4 clients, and
`fed_mesh(2, 2)` against `fed_mesh(2, 1)`.  These parities run with f32
matmuls at full precision (`jax_default_matmul_precision="highest"`), as
the CPU parity tests do, so that what they pin is placement, not the
chip's one-pass bf16 matmul rounding.  Each mesh must also put work on all
of its devices: the per-device FLOPs of the compiled program fall with the
cohort (or model) split, and the params span every mesh device.

Everything runs in this one process.  It stops at the first failed check
(non-zero exit); without a TPU it stops before any work.  Earlier lines
report wall times (the first call of each entry point includes its
compilation) and `peak_bytes_in_use`: for information, not a benchmark.
The last line is the JSON result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
KERNEL_MARK = "tpu_custom_call"
# benchmarks/bench_fl.py's full protocol (BENCH_FAST=0)
PAPER = dict(n_clients=40, cohort=10, k_micro=4, micro_batch=16,
             server_lr=0.5, local_lr=0.05, local_epochs=2)
PAPER_SCALE = 0.5
# examples/train_lm.py's federated defaults
LM = dict(k_micro=2, batch=8, seq=256, lr=3e-2, ncv_beta=0.5, rank=16)
PARITY_TOL = 1e-5          # tests/test_mesh2d.py's identity pin
DIVERGED = 10.0            # agg_norm growth over round 0 that fails phase B


def require_tpu():
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found "
                 f"{devs[0].platform} ({len(devs)} device(s))")
    return devs


def log(msg):
    print(msg, flush=True)


def check(ok, what):
    if not ok:
        raise AssertionError(f"chip_smoke: check failed: {what}")


def leaves_finite(tree):
    import jax
    import numpy as np
    return all(np.isfinite(np.asarray(x)).all()
               for x in jax.tree.leaves(tree))


def max_diff(a, b):
    import jax
    import numpy as np
    return max(float(np.max(np.abs(np.asarray(x, np.float32)
                                   - np.asarray(y, np.float32))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def copy_tree(tree):
    import jax
    import jax.numpy as jnp
    return jax.tree.map(jnp.copy, tree)


def timed(fn, *args):
    import jax
    t = time.perf_counter()
    out = fn(*args)
    jax.block_until_ready(out)
    return out, time.perf_counter() - t


def peak_bytes(dev):
    return (dev.memory_stats() or {}).get("peak_bytes_in_use")


def flops(compiled):
    """Per-device FLOPs of one compiled SPMD program."""
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    return float(cost["flops"])


def check_kernels(text, what):
    check(KERNEL_MARK in text, f"{what}: no {KERNEL_MARK} in the compiled "
                               f"program (Mosaic kernels did not run)")


# ---------------------------------------------------------------------------
# Phase A: the paper protocol through the Simulator
# ---------------------------------------------------------------------------

def paper_sim(codec, mesh=None, seed=0, paper=PAPER, scale=PAPER_SCALE):
    import jax
    from benchmarks.bench_fl import METHOD_MC, make_task
    from repro.data import federated_splits
    from repro.fed import FLConfig, Simulator
    from repro.models import lenet
    spec, train, test = federated_splits(
        "cifar10", n_clients=paper["n_clients"], alpha=0.1, seed=seed,
        scale=scale)
    cfg, task = make_task(spec)
    params = lenet.init(cfg, jax.random.PRNGKey(seed))
    fl = FLConfig.make(method="fedncv", codec=codec, **paper,
                       **METHOD_MC["fedncv"])
    return Simulator(task, params, train, fl, seed=seed, mesh=mesh), test


def scan_compiled(sim, n):
    """The Simulator's compiled n-round scan, lowered with the arguments
    `run_rounds(n)` passes it next."""
    import jax
    import jax.numpy as jnp
    start = sim.round_idx
    keys = jax.vmap(lambda i: jax.random.fold_in(sim.base_key, i))(
        start + jnp.arange(n))
    rs = start + jnp.arange(1, n + 1, dtype=jnp.int32)
    return sim._scan_jit.lower(sim.params, sim._get_state(), keys,
                               rs).compile()


def phase_a(codec, dev, **kw):
    import numpy as np
    sim, test = paper_sim(codec, **kw)
    p0 = copy_tree(sim.params)
    compiled, t_compile = timed(scan_compiled, sim, 5)
    check_kernels(compiled.as_text(), f"phase A {codec}")
    d1, t_first = timed(sim.run_rounds, 5)
    d2, t_next = timed(sim.run_rounds, 5)
    acc, t_eval = timed(sim.evaluate, test)
    for name, d in (("first", d1), ("next", d2)):
        check(all(np.isfinite(v).all() for v in d.values()),
              f"phase A {codec}: non-finite round diagnostics ({name})")
    check(leaves_finite(sim.params), f"phase A {codec}: non-finite params")
    check(leaves_finite(sim._get_state()),
          f"phase A {codec}: non-finite state")
    moved = max_diff(sim.params, p0)
    check(moved > 0.0, f"phase A {codec}: params did not move")
    check(0.0 <= acc <= 1.0, f"phase A {codec}: accuracy {acc}")
    log(f"phase A lenet5 fedncv codec={codec}: compile {t_compile:.3f}s; "
        f"run_rounds(5) {t_first:.3f}s, again {t_next:.3f}s; evaluate "
        f"(first call, with its compile) "
        f"{t_eval:.3f}s; agg_norm {float(d2['agg_norm'][-1]):.4e}, "
        f"accuracy {acc:.4f}, max|dparams| {moved:.4e}, "
        f"peak_bytes_in_use {peak_bytes(dev)}")


# ---------------------------------------------------------------------------
# Phase B: federated llama-100m through make_round
# ---------------------------------------------------------------------------

def lm_setup(mesh, n_clients, codec_name, cfg=None, lm=LM, seed=0,
             shard_params=False):
    """The `examples/train_lm.py --federated` round on `mesh`: returns
    (round_fn, params, state, draw) with draw(r) -> the round's
    arguments after (params, state)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from examples.train_lm import _draw, model_100m
    from repro import comm
    from repro.data import make_token_dataset
    from repro.fed.api import get_method
    from repro.fed.distributed import init_distributed_state, make_round
    from repro.fed.methods import MethodConfig, Task
    from repro.models import api
    from repro.sharding import params_shardings
    from repro.utils.tree_math import ravel
    from jax.sharding import NamedSharding, PartitionSpec as P

    cfg = cfg or model_100m()
    if mesh.shape.get("model", 1) > 1:
        cfg = cfg.replace(scan_layers=False)   # as train_lm does (§13.1)
    params = api.init_params(cfg, jax.random.PRNGKey(seed))
    # placed as the round returns them, so round 2 reuses round 1's program
    replicated = NamedSharding(mesh, P())
    params = jax.device_put(params, params_shardings(
        jax.eval_shape(lambda: params), mesh) if shard_params
        else replicated)
    task = Task(loss=lambda p, b: api.loss(cfg, p, b))
    # one client has no leave-one-out network: beta = 0, as train_lm
    beta = lm["ncv_beta"] if n_clients > 1 else 0.0
    mc = MethodConfig(name="fedncv", ncv_beta=beta)
    codec = None
    if codec_name != "identity":
        vec, vspec = ravel(params)
        codec = comm.get_codec(codec_name, n=vec.shape[0], spec=vspec,
                               **({"rank": lm["rank"]}
                                  if codec_name == "lowrank" else {}))
    round_fn = make_round("fedncv", task, mesh, mc, server_lr=lm["lr"],
                          codec=codec)
    state = jax.device_put(init_distributed_state(
        get_method("fedncv"), params, task, mc, n_clients=n_clients,
        codec=codec), replicated)
    toks = make_token_dataset(cfg.vocab, 200_000, seed=seed)
    cut = len(toks) // n_clients
    n_samples = jnp.asarray([float(cut * (1.0 + 0.25 * u))
                             for u in range(n_clients)])
    k, b = lm["k_micro"], lm["batch"]

    def draw(r):
        per_client = []
        for u in range(n_clients):
            rng = np.random.default_rng(1000 * r + u)
            mb = _draw(rng, toks[u * cut:(u + 1) * cut], k * b, lm["seq"])
            per_client.append(jax.tree.map(
                lambda x: x.reshape((k, b) + x.shape[1:]), mb))
        batch = jax.tree.map(lambda *xs: jnp.stack(xs), *per_client)
        extra = ((jnp.arange(n_clients, dtype=jnp.uint32) + 1000 * r,)
                 if codec is not None else ())
        return (batch, n_samples, jnp.int32(r)) + extra

    return dict(round_fn=round_fn, params=params, state=state, draw=draw,
                task=task, mc=mc, lr=lm["lr"])


def phase_b(codec, dev, rounds=3, **kw):
    import math
    from repro.sharding import fed_mesh
    s = lm_setup(fed_mesh(1, 1), 1, codec, **kw)
    round_fn, params, state = s["round_fn"], s["params"], s["state"]
    p0 = copy_tree(params)
    t = time.perf_counter()
    compiled = round_fn.lower(params, state, *s["draw"](0)).compile()
    t_compile = time.perf_counter() - t
    times, norms = [], []
    for r in range(rounds):
        (params, state, m), t = timed(round_fn, params, state, *s["draw"](r))
        times.append(t)
        norms.append(float(m["agg_norm"]))
    check(all(map(math.isfinite, norms)), f"phase B {codec}: agg_norm "
                                          f"{norms}")
    # a codec whose error feedback diverges grows the aggregate
    # geometrically while it is still finite
    check(max(norms) < DIVERGED * norms[0],
          f"phase B {codec}: agg_norm diverges {norms}")
    check(leaves_finite(state), f"phase B {codec}: non-finite state")
    check(leaves_finite(params), f"phase B {codec}: non-finite params")
    moved = max_diff(params, p0)
    check(moved > 0.0, f"phase B {codec}: params did not move")
    log(f"phase B llama-100m fed_mesh(1,1) codec={codec}: compile "
        f"{t_compile:.3f}s; rounds "
        + ", ".join(f"{t:.3f}s" for t in times)
        + f"; agg_norm {norms}, max|dparams| {moved:.4e}, "
        f"peak_bytes_in_use {peak_bytes(dev)}")


# ---------------------------------------------------------------------------
# Four chips: mesh placements against one device
# ---------------------------------------------------------------------------

def spans(tree, n):
    """Every leaf of `tree` is placed on n devices."""
    import jax
    return all(len(x.sharding.device_set) == n
               for x in jax.tree.leaves(tree))


def parity_cohort_mesh(devs, paper=PAPER, scale=PAPER_SCALE):
    from repro.sharding import cohort_mesh
    one, _ = paper_sim("identity", paper=paper, scale=scale)
    four, _ = paper_sim("identity", mesh=cohort_mesh(4), paper=paper,
                        scale=scale)
    f_one = flops(scan_compiled(one, 5))
    c_four = scan_compiled(four, 5)
    check_kernels(c_four.as_text(), "cohort_mesh(4)")
    f_four = flops(c_four)
    _, t_one = timed(one.run_rounds, 5)
    _, t_four = timed(four.run_rounds, 5)
    err = max_diff(one.params, four.params)
    log(f"cohort_mesh(4) vs one device, lenet5 fedncv identity, 5 rounds: "
        f"max|dparams| {err:.3e} (pin {PARITY_TOL}); per-device flops "
        f"{f_four:.4e} vs {f_one:.4e}; run_rounds(5) {t_four:.3f}s vs "
        f"{t_one:.3f}s; peak_bytes_in_use "
        f"{[peak_bytes(d) for d in devs[:4]]}")
    check(err < PARITY_TOL, f"cohort_mesh(4) parity {err}")
    check(leaves_finite(four.params), "cohort_mesh(4): non-finite params")
    check(spans(four.params, 4), "cohort_mesh(4): params not on 4 devices")
    # 10 clients padded to 12 over 4 devices: 3 client passes per device
    # against 10 on one, so the per-device work must fall well below half
    check(f_four < 0.5 * f_one,
          f"cohort_mesh(4) does not split the cohort: per-device flops "
          f"{f_four} vs {f_one}")


def lm_reference(s, args):
    """Sequential one-device reference of one fedncv round over the same
    clients (tests/test_distributed.py style): per-client message from
    the microbatch gradients, Eq. 10-12 over the messages, one SGD step.
    Also returns the per-device FLOPs of one client's message."""
    import jax
    from repro.core import control_variates as cv
    from repro.fed.methods import _microbatch_grads
    batch, n_u = args[0], args[1]
    task, mc = s["task"], s["mc"]

    @jax.jit
    def message(params, client_batch, alpha):
        stats = cv.client_stats_from_stack(
            _microbatch_grads(task, params, client_batch))
        return cv.client_message(stats, alpha)

    params, alphas = s["params"], s["state"]["alphas"]
    client = [jax.tree.map(lambda x: x[u], batch)
              for u in range(n_u.shape[0])]
    msgs = [message(params, client[u], alphas[u])
            for u in range(n_u.shape[0])]
    f_client = flops(message.lower(params, client[0], alphas[0]).compile())
    agg = cv.networked_aggregate(msgs, n_u, beta=mc.ncv_beta)
    return jax.tree.map(lambda p, g: p - s["lr"] * g, params, agg), f_client


def parity_fed_mesh_4x1(devs, cfg=None, lm=LM):
    from repro.sharding import fed_mesh
    s = lm_setup(fed_mesh(4, 1), 4, "identity", cfg=cfg, lm=lm)
    args = s["draw"](0)
    ref, f_client = lm_reference(s, args)
    compiled = s["round_fn"].lower(s["params"], s["state"], *args).compile()
    f_dev = flops(compiled)
    (params, state, _), t = timed(s["round_fn"], s["params"], s["state"],
                                  *args)
    err = max_diff(params, ref)
    log(f"fed_mesh(4,1) vs sequential one-device reference, llama-100m "
        f"fedncv, 4 clients, 1 round: max|dparams| {err:.3e} (pin "
        f"{PARITY_TOL}); per-device flops {f_dev:.4e} vs one client's "
        f"message {f_client:.4e}; round {t:.3f}s; peak_bytes_in_use "
        f"{[peak_bytes(d) for d in devs[:4]]}")
    check(err < PARITY_TOL, f"fed_mesh(4,1) parity {err}")
    check(leaves_finite(params) and leaves_finite(state),
          "fed_mesh(4,1): non-finite outputs")
    check(spans(params, 4), "fed_mesh(4,1): params not on 4 devices")
    # one client pass per device: a placement that ran all four clients
    # on one device would show about four clients' work per device
    check(f_dev < 2.0 * f_client,
          f"fed_mesh(4,1) does not split the clients: per-device flops "
          f"{f_dev} vs {f_client} for one client")


def parity_fed_mesh_2x2(devs, cfg=None, lm=LM):
    import jax
    from examples.train_lm import model_100m
    from repro.sharding import fed_mesh
    # both meshes run the same unrolled program (the model axis needs it)
    cfg = (cfg or model_100m()).replace(scan_layers=False)
    out = {}
    for shape in ((2, 1), (2, 2)):
        s = lm_setup(fed_mesh(*shape), 2, "identity", cfg=cfg, lm=lm,
                     shard_params=True)
        args = s["draw"](0)
        compiled = s["round_fn"].lower(s["params"], s["state"],
                                       *args).compile()
        (params, state, _), t = timed(s["round_fn"], s["params"],
                                      s["state"], *args)
        check(leaves_finite(params) and leaves_finite(state),
              f"fed_mesh{shape}: non-finite outputs")
        check(spans(params, shape[0] * shape[1]),
              f"fed_mesh{shape}: params not on every mesh device")
        out[shape] = (params, flops(compiled), t)
    (p21, f21, t21), (p22, f22, t22) = out[(2, 1)], out[(2, 2)]
    err = max_diff(p21, p22)
    split = [x for x in jax.tree.leaves(p22)
             if x.sharding.shard_shape(x.shape) != x.shape]
    log(f"fed_mesh(2,2) vs fed_mesh(2,1), llama-100m fedncv, 2 clients, "
        f"1 round: max|dparams| {err:.3e} (pin {PARITY_TOL}); per-device "
        f"flops {f22:.4e} vs {f21:.4e}; round {t22:.3f}s vs {t21:.3f}s; "
        f"{len(split)} param leaves split over the model axis; "
        f"peak_bytes_in_use {[peak_bytes(d) for d in devs[:4]]}")
    check(err < PARITY_TOL, f"fed_mesh(2,2) parity {err}")
    check(split, "fed_mesh(2,2): no param leaf is split over the model axis")
    check(f22 < 0.75 * f21,
          f"fed_mesh(2,2) does not split the model: per-device flops "
          f"{f22} vs {f21}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip mesh parity phases")
    args = ap.parse_args(argv)
    devs = require_tpu()
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from repro.utils.compile_cache import use_compile_cache
    import jax
    log(f"device {devs[0].device_kind} x{len(devs)}, jax {jax.__version__}, "
        f"compile cache {use_compile_cache()}")
    t0 = time.perf_counter()
    if args.four_chips:
        check(len(devs) >= 4, f"--four-chips needs 4 devices, "
                              f"found {len(devs)}")
        jax.config.update("jax_default_matmul_precision", "highest")
        for phase in (parity_cohort_mesh, parity_fed_mesh_4x1,
                      parity_fed_mesh_2x2):
            t = time.perf_counter()
            phase(devs)
            log(f"{phase.__name__}: ok in {time.perf_counter() - t:.1f}s")
    else:
        for codec in ("identity", "int8"):
            phase_a(codec, devs[0])
        for codec in ("identity", "lowrank"):
            phase_b(codec, devs[0])
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
