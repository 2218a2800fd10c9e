"""The `phi3-mini-silo-1chip` cell through the harness on the CPU, with
`phi3-mini-4k-4l` shrunk to a tiny Phi-3 shape here (untied embeddings,
RMSNorm eps 1e-5, head_dim set, a window wider than the sequence)."""
import os

import jax
import pytest

from chipbench import calibrate, harness
from chipbench.tests.conftest import edit_json, make_tiny_root
from chipbench.tests.test_chipbench_faults import _half_batch

CELL = "phi3-mini-silo-1chip"
TINY_PHI3 = dict(hidden_size=64, intermediate_size=128,
                 num_attention_heads=4, num_key_value_heads=4, head_dim=16,
                 vocab_size=256, num_hidden_layers=2, sliding_window=47)


@pytest.fixture
def phi3_root(tmp_path):
    root = make_tiny_root(tmp_path)
    edit_json(os.path.join(root, "chipbench", "configs",
                           "phi3-mini-4k-4l.json"), **TINY_PHI3)
    return root


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_cell_is_correct(phi3_root, cpu_harness, trace):
    out = harness.run_cell(CELL, 2 ** 33 + 5, 0.5, bool(trace),
                           root=phi3_root)
    assert out["correct"] and out["failed"] == 0, out["check"]
    if trace:
        assert set(out["metrics"]) == {"idle_share.phi3", "mfu.phi3"}
        assert out["device"]["window_s"] > 0
    else:
        assert set(out["metrics"]) == {"rounds_per_s", "setup_s"}
        assert out["metrics"]["rounds_per_s"]["unit"] == "rounds/s"


def test_cell_reports_rounds_not_tokens(phi3_root, cpu_harness):
    """A timed call is one round: the rate is calls over seconds, and
    the round's streamed statistics come back with it."""
    _, run = harness.find_cell(CELL, phi3_root)
    run.seed, run.devices = 3, jax.devices()[:1]
    run.key = harness.seed_key(3)
    drv = harness.load_module(phi3_root, "chipbench", "drivers",
                              "lm_round.py").Driver(run)
    assert drv.units_per_step == 1 and drv.rounds_per_step == 1
    t = run.traffic
    assert drv.flops_per_step > 0
    assert drv.step()
    m = drv.metrics
    assert float(m["mean_k"]) == t["k_micro"]
    assert float(m["mean_sum_norm_sq"]) > 0
    # S2 >= K S1 for any K vectors (Cauchy-Schwarz on the mean)
    assert float(m["mean_sum_norm_sq"]) >= \
        t["k_micro"] * float(m["mean_mean_norm_sq"]) * (1 - 1e-6)


def test_program_carries_eps_and_window(phi3_root):
    from chipbench.drivers.lm_round import arch_config
    cfg = harness.load_json(phi3_root, "chipbench", "configs",
                            "phi3-mini-4k-4l.json")
    arch = arch_config(cfg)
    assert arch.norm_eps == 1e-5 and arch.sliding_window == 47
    assert not arch.tie_embeddings and arch.hd == 16


def test_readers_are_the_cells_own(phi3_root):
    bench = harness.load_json(phi3_root, "BENCHMARK.json")
    assert set(harness.readers(bench, CELL, phi3_root)) == {
        "idle_share.phi3", "mfu.phi3"}
    assert [m["name"] for m in harness.cell_metrics(
        bench, CELL, "end_to_end")] == ["rounds_per_s", "setup_s"]


def test_half_batch_is_not_correct(phi3_root, cpu_harness, monkeypatch):
    _half_batch(monkeypatch, CELL)
    out = harness.run_cell(CELL, 77, 0.3, False, root=phi3_root)
    assert not out["correct"], out["check"]


def test_control_and_fault_fail_the_limits(phi3_root, cpu_harness):
    """The program passes the cell's limits; the reference in bfloat16
    (the control) and the reference with half of each silo's batch left
    out each fail one."""
    limits = harness.load_json(phi3_root, "chipbench", "workloads",
                               CELL + ".json")["limits"]
    r = calibrate.readings(CELL, 5, control=True, faults=("half_batch",),
                           root=phi3_root)
    assert all(r["program"][k] <= limits[k] for k in limits), r
    assert any(r["control"][k] > limits[k] for k in limits), r
    assert any(r["half_batch"][k] > limits[k] for k in limits), r
