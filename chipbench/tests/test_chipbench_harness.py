"""The harness on the CPU: cells found by file name, the chip check, and
whole runs of the tiny cells."""
import json
import os
import subprocess
import sys

import pytest

from chipbench import harness
from chipbench.tests.conftest import REPO

CELLS = ("paper-lenet5-c10", "phi3-silo-1chip")


def test_new_cell_config_and_metric_are_files_only(tiny_root):
    base = os.path.join(tiny_root, "chipbench")
    with open(os.path.join(base, "configs", "tiny-lm.json"), "w") as f:
        json.dump(harness.load_json(base, "configs", "phi3-mini-4l.json"), f)
    with open(os.path.join(base, "traffic", "short.json"), "w") as f:
        json.dump(dict(harness.load_json(base, "traffic",
                                         "silo-2x2x1024.json"), seq=16), f)
    with open(os.path.join(base, "workloads", "tiny-short.json"), "w") as f:
        json.dump(harness.load_json(base, "workloads",
                                    "phi3-silo-1chip.json"), f)
    with open(os.path.join(base, "metrics", "steps.lm.py"), "w") as f:
        f.write("def read(r):\n    return float(r.steps)\n")
    bench = harness.load_json(tiny_root, "BENCHMARK.json")
    bench["configs"].append(dict(bench["configs"][-1], name="tiny-lm",
                                 file="chipbench/configs/tiny-lm.json"))
    bench["workloads"].append(dict(name="tiny-short", config="tiny-lm",
                                   traffic="short", chips=1, why="test"))
    bench["per_layer"].append(dict(
        name="steps.lm", unit="steps", better="higher", source="host_clock",
        layer="round step", moves="client_tokens_per_s",
        workloads=["tiny-short"]))
    for m in bench["end_to_end"]:
        if m["name"] == "client_tokens_per_s":
            m["workloads"].append("tiny-short")
    with open(os.path.join(tiny_root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    bench, run = harness.find_cell("tiny-short", tiny_root)
    assert run.traffic["seq"] == 16 and run.config["name"] == "phi3-mini-4l"
    assert sorted(m["name"] for m in harness.cell_metrics(
        bench, "tiny-short", "end_to_end")) == ["client_tokens_per_s",
                                                "setup_s"]
    assert "steps.lm" in harness.readers(bench, "tiny-short", tiny_root)
    assert "steps.lm" not in harness.readers(bench, "phi3-silo-1chip",
                                             tiny_root)


def test_refuses_a_cpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        "paper-lenet5-c10", "--seed", str(2 ** 31 + 9),
                        "--seconds", "1", "--trace", "0"], cwd=REPO,
                       env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode == 2, p.stderr[-2000:]
    assert p.stdout == ""
    assert "needs a TPU" in p.stderr


def test_unknown_device_kind_has_no_peaks():
    with pytest.raises(KeyError, match="no published peaks"):
        harness.peak_of(harness.HERE, "cpu")


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_run_is_correct(tiny_root, cpu_harness, cell, trace):
    out = harness.run_cell(cell, 2 ** 33 + 5, 0.5, bool(trace),
                           root=tiny_root)
    assert out["correct"] and out["failed"] == 0, out["check"]
    assert list(out)[-1] == "check"
    bench = harness.load_json(tiny_root, "BENCHMARK.json")
    kind = "per_layer" if trace else "end_to_end"
    names = {m["name"] for m in harness.cell_metrics(bench, cell, kind)}
    assert set(out["metrics"]) <= names
    if not trace:
        assert set(out["metrics"]) == names
        assert all(v["value"] > 0 for v in out["metrics"].values())
    else:
        assert out["device"]["window_s"] > 0
