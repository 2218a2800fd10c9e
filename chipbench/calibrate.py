"""Readings that the limits of `correct` are set from (PERF.md §2).

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--control] [--faults half_batch,no_exchange]

For each seed, in one process: the program driven through the cell's
first steps and compared with the float32 reference, as a run compares
it (harness.drive_first_steps and harness.check: the lower readings);
with `--control`, the same reference put in the program's place at the
configuration's "control", the precision below the one it states (its
readings have to fail); with `--faults`, the float32 reference with each
fault planted.  One JSON line per seed and kind.  Not part of a
benchmark run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(os.path.dirname(HERE), "src"),
                os.path.dirname(HERE)]

from chipbench import harness  # noqa: E402


def readings(name, seed, control=False, faults=(), root=harness.ROOT):
    """-> {kind: {number: value, "by_step": [...]}} for one seed."""
    import jax
    t_start = time.perf_counter()
    base = os.path.join(root, "chipbench")
    _, run = harness.find_cell(name, root)
    chips = run.entry["chips"]
    run.seed, run.devices = seed, harness.require_chips(chips)[:chips]
    run.key = harness.seed_key(seed)
    jax.config.update("jax_default_matmul_precision",
                      run.config["matmul_precision"])
    drv = harness.load_module(base, "drivers",
                              run.cell["driver"] + ".py").Driver(run)
    prog, _, _ = harness.drive_first_steps(drv, t_start)
    drv.release()
    others = [("control", run.config["control"])] if control else []
    others += [(fault, {"fault": fault}) for fault in faults]
    return harness.check(drv, prog, run.cell["method"]["server_lr"],
                         others, by_step=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--faults", default="")
    args = ap.parse_args(argv)
    harness.use_cache()
    faults = [f for f in args.faults.split(",") if f]
    for seed in map(int, args.seeds.split(",")):
        for kind, nums in readings(args.workload, seed, args.control,
                                   faults).items():
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "kind": kind, **nums}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
