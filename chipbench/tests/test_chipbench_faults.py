"""Runs with the timed path broken underneath, and the control: each has
to come out not correct, on the CPU at the tiny sizes."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from chipbench import calibrate, harness
from chipbench.tests.conftest import REPO

CELLS = ("paper-lenet5-c10", "phi3-silo-1chip")


def _unchanged_state(monkeypatch, cell):
    """The timed path returns its state unchanged."""
    if cell == "paper-lenet5-c10":
        from repro.fed.simulator import Simulator
        orig = Simulator._scan_rounds

        def scan(self, params, state, keys, rs):
            _, _, diags = orig(self, params, state, keys, rs)
            return params, state, diags
        monkeypatch.setattr(Simulator, "_scan_rounds", scan)
    else:
        from repro.fed import distributed
        orig = distributed.make_round

        def make_round(*a, **kw):
            fn = orig(*a, **kw)
            return lambda p, st, *rest: (p, st, fn(p, st, *rest)[2])
        monkeypatch.setattr(distributed, "make_round", make_round)


def _half_batch(monkeypatch, cell):
    """Half of each client's microbatches left out, the mean taken over
    the rest (the first half stands in for the second)."""
    def halve(x):
        k = x.shape[1]
        return jnp.concatenate([x[:, :k // 2]] * 2, axis=1)
    if cell == "paper-lenet5-c10":
        from repro.fed.simulator import Simulator
        orig = Simulator._gather_batch
        monkeypatch.setattr(Simulator, "_gather_batch",
                            lambda self, data, sel: jax.tree.map(
                                halve, orig(self, data, sel)))
    else:
        from repro.fed import distributed
        orig = distributed.make_round

        def make_round(*a, **kw):
            fn = orig(*a, **kw)
            return lambda p, st, batch, *rest: fn(
                p, st, jax.tree.map(halve, batch), *rest)
        monkeypatch.setattr(distributed, "make_round", make_round)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch])
def test_broken_timed_path_is_not_correct(tiny_root, cpu_harness,
                                          monkeypatch, cell, fault):
    fault(monkeypatch, cell)
    out = harness.run_cell(cell, 77, 0.3, False, root=tiny_root)
    assert not out["correct"], out["check"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_the_limits(tiny_root, cpu_harness, cell):
    """The reference in bfloat16 in the program's place (the control)
    fails the cell's limits; the program passes them."""
    limits = harness.load_json(tiny_root, "chipbench", "workloads",
                               cell + ".json")["limits"]
    r = calibrate.readings(cell, 5, control=True, root=tiny_root)
    assert all(r["program"][k] <= limits[k] for k in limits), r
    assert any(r["control"][k] > limits[k] for k in limits), r


XSILO = r"""
import json, os, sys
import jax
from chipbench import harness
from chipbench.tests.conftest import make_tiny_root
harness.require_chips = lambda n: jax.devices()
harness.use_cache = lambda: None
harness.peak_of = lambda base, kind: {"flops_bf16": 1.0}
root = make_tiny_root(sys.argv[1])
base = os.path.join(root, "chipbench")
# four unequal silos, one per device, beta 0.5: a cell added as files
silo = harness.load_json(base, "traffic", "silo-2x2x1024.json")
json.dump(dict(silo, silos=4), open(os.path.join(base, "traffic",
                                                 "xsilo4.json"), "w"))
cell = harness.load_json(base, "workloads", "phi3-silo-1chip.json")
cell["method"]["beta"] = 0.5
json.dump(cell, open(os.path.join(base, "workloads", "xsilo4.json"), "w"))
bench = harness.load_json(root, "BENCHMARK.json")
bench["workloads"].append(dict(name="xsilo4", config="phi3-mini-4l",
                               traffic="xsilo4", chips=4, why="test"))
json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))
out = {"sound": harness.run_cell("xsilo4", 31, 0.3, False,
                                 root=root)["correct"]}
# the exchange between chips left out: every psum returns its own shard
jax.lax.psum = lambda x, axis_name, **kw: x
out["no_exchange"] = harness.run_cell("xsilo4", 31, 0.3, False,
                                      root=root)["correct"]
print(json.dumps(out))
"""


def test_four_silos_without_the_exchange_are_not_correct(tmp_path):
    """On four CPU devices: the four-silo cell is correct, and not correct
    once the round's all-reduce is left out."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([REPO, os.path.join(REPO, "src")]))
    p = subprocess.run([sys.executable, "-c", XSILO, str(tmp_path)],
                       cwd=REPO, env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    assert json.loads(p.stdout.strip().splitlines()[-1]) == {
        "sound": True, "no_exchange": False}
