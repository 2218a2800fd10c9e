"""A tiny copy of the benchmark for CPU runs of the harness.

`tiny_root` copies BENCHMARK.json and chipbench/ into a temporary
checkout, adds the cells of `pending.json` (written, not yet proven on
the chip) to its BENCHMARK.json, and shrinks the configurations and
traffic to sizes a CPU test can run: every code path of a chip run, at
small shapes.
`cpu_harness` lets the harness run on the CPU: it skips the look for a
chip, the persistent compile cache and the peak table.
"""
import json
import os
import shutil

import jax
import pytest

from chipbench import harness

REPO = os.path.dirname(harness.HERE)

TINY = {
    "traffic/paper-c10.json": dict(n_train=1200, n_heldout=64, n_clients=8,
                                   cohort=4, rounds_per_call=2),
    "traffic/silo-2x2x1024.json": dict(seq=32, stream_tokens=20000),
    "configs/phi3-mini-4l.json": dict(
        hidden_size=64, intermediate_size=128, num_attention_heads=4,
        num_key_value_heads=4, head_dim=16, vocab_size=256,
        num_hidden_layers=2),
}


def edit_json(path, **kw):
    with open(path) as f:
        d = json.load(f)
    d.update(kw)
    with open(path, "w") as f:
        json.dump(d, f, indent=1)


def make_tiny_root(dst):
    shutil.copytree(harness.HERE, os.path.join(dst, "chipbench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = harness.load_json(REPO, "BENCHMARK.json")
    for key, entries in harness.load_json(harness.HERE,
                                          "pending.json").items():
        if key != "why":
            bench[key] += entries
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    for rel, kw in TINY.items():
        edit_json(os.path.join(dst, "chipbench", rel), **kw)
    return str(dst)


@pytest.fixture
def tiny_root(tmp_path):
    return make_tiny_root(tmp_path)


@pytest.fixture
def cpu_harness(monkeypatch):
    monkeypatch.setattr(harness, "require_chips", lambda n: jax.devices())
    monkeypatch.setattr(harness, "use_cache", lambda: None)
    monkeypatch.setattr(harness, "peak_of", lambda base, kind: {
        "flops_bf16": 197e12, "hbm_bytes_per_s": 819e9})
    return harness
