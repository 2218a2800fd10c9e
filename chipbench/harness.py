"""The benchmark harness: one run of one cell.

Everything a cell needs is found by name: the cell's entry in
BENCHMARK.json, its file `workloads/<cell>.json` (driver, method,
limits), its traffic `traffic/<traffic>.json`, its configuration
`configs/<config>.json`, its driver `drivers/<driver>.py`, and one
reader `metrics/<metric>.py` for each per-layer metric that lists the
cell.  Adding a cell, a configuration or a metric adds files; nothing
here changes.

A run: check the chips, build the driver, drive the timed path through
its first `CHECK_STEPS` calls (they compile, and their parameters are
kept on the host for the check), then time calls of the same path in a
closed loop for `--seconds` (traced with `--trace 1`), read the peak
memory, free the program's state, run the plain reference from the same
seed through the same inputs, and compare.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import shutil
import statistics
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
CHECK_STEPS = 3
CACHE_BYTES = 8 << 30
TRACE_SECONDS = 3.0
STEADY_LEAF = 1e-3     # leaves whose reference gradient is under this
                       # share of the median leaf's are left out of the
                       # change comparison (moved by round-off alone)


class NoChip(RuntimeError):
    pass


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(*parts):
    path = os.path.join(*parts)
    name = "chipbench_" + os.path.basename(path).replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Run:
    name: str
    entry: dict          # the cell's entry in BENCHMARK.json
    cell: dict           # workloads/<cell>.json
    traffic: dict        # traffic/<traffic>.json
    config: dict         # configs/<config>.json
    seed: int
    devices: list = None
    key: object = None


def find_cell(name, root=ROOT):
    """The cell `name` and everything it names, read from files only."""
    bench = load_json(root, "BENCHMARK.json")
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(entries)}")
    entry = entries[name]
    base = os.path.join(root, "chipbench")
    return bench, Run(name=name, entry=entry,
                      cell=load_json(base, "workloads", name + ".json"),
                      traffic=load_json(base, "traffic",
                                        entry["traffic"] + ".json"),
                      config=load_json(base, "configs",
                                       entry["config"] + ".json"),
                      seed=0)


def cell_metrics(bench, name, kind):
    """The metrics of `kind` ("end_to_end" or "per_layer") a cell reports."""
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    if kind == "end_to_end":
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if name in m.get("workloads", [name]) and m["moves"] in moved]


def readers(bench, name, root=ROOT):
    return {m["name"]: load_module(root, "chipbench", "metrics",
                                   m["name"] + ".py")
            for m in cell_metrics(bench, name, "per_layer")}


def require_chips(n):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devs[0].platform} "
                     f"({len(devs)} device(s))")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips; JAX found {len(devs)}")
    return devs


def use_cache():
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, every program in it whatever its size or compile time.
    The Simulator's scan holds its data set as constants (a 215 MB
    executable at the paper protocol), so the cache's total is lifted to
    CACHE_BYTES: a machine may set a total below one such entry, and
    JAX then refuses to write it."""
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_compilation_cache_max_size", CACHE_BYTES)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


class Compiles:
    """Counts of JAX compilations and persistent-cache hits and writes,
    read from `jax.monitoring` events, for the progress lines: the
    timed window should compile nothing, and a warm run should find
    every program in the cache."""

    def __init__(self):
        from jax import monitoring
        self.n = {"compiled": 0, "cache_hits": 0, "cache_writes": 0}
        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.n["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.n["cache_writes"] += 1

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n["compiled"] += 1

    def snapshot(self):
        return dict(self.n)


def log(msg, t_start):
    """A progress line on standard error: seconds since start, host RSS."""
    import resource
    rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2 ** 20
    print(f"chipbench {time.perf_counter() - t_start:8.2f}s "
          f"rss<={rss:.2f}GiB {msg}", file=sys.stderr, flush=True)


def seed_key(seed):
    """A PRNG key for any seed up to 2**63, in one device call."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def window(drv, seconds):
    """Closed loop of timed calls; returns (calls, seconds, calls whose
    outputs were not finite)."""
    import jax
    calls, failed = 0, 0
    with jax.profiler.TraceAnnotation("window"):
        t0 = time.perf_counter()
        while True:
            failed += not drv.step()
            calls += 1
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds:
                return calls, elapsed, failed


def diff_norms(a, b, scale=1.0):
    """Per-leaf norms of (a - b) / scale, one leaf at a time in float64
    on the host (the trees are host copies of the parameters)."""
    import jax
    import numpy as np
    return [float(np.linalg.norm((np.asarray(x, np.float64)
                                  - np.asarray(y, np.float64)).ravel()))
            / scale for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]


def leaf_gap(prog, ref, keep=None):
    """Worst leaf of |norm(prog) - norm(ref)| / max(norm(ref), median)."""
    med = statistics.median(ref)
    gaps = [abs(p - r) / max(r, med, 1e-30)
            for i, (p, r) in enumerate(zip(prog, ref))
            if keep is None or keep[i]]
    return max(gaps) if all(map(math.isfinite, prog)) else math.inf


def compare(drv, p0, prog, ref, lr, alphas=None):
    """The numbers a cell's limits can hold, from host parameters: p0 the
    seed's initial parameters, prog/ref after each of the first steps,
    alphas the clients' alphas after the first step (program,
    reference).  A cell compares those its `limits` name."""
    import jax
    loss_gaps = []
    for p, r in zip(prog, ref):
        with jax.default_matmul_precision("highest"):
            lp, lr_ = drv.ref_loss(p), drv.ref_loss(r)
        loss_gaps.append(abs(lp - lr_) / abs(lr_) if math.isfinite(lp)
                         else math.inf)
    g_prog = diff_norms(p0, prog[0], lr)
    g_ref = diff_norms(p0, ref[0], lr)
    med = statistics.median(g_ref)
    keep = [g >= STEADY_LEAF * med for g in g_ref]
    c_prog = diff_norms(prog[-1], p0)
    c_ref = diff_norms(ref[-1], p0)
    out = {"loss_gap": max(loss_gaps),
           "loss_gap_first": loss_gaps[0],
           "grad_gap": leaf_gap(g_prog, g_ref),
           "change_gap": leaf_gap(c_prog, c_ref, keep)}
    if alphas is not None:
        out["alpha_gap"] = alpha_gap(*alphas, drv.m["alpha0"])
    return out


def alpha_gap(prog, ref, a0):
    """The median client's |(a - a0) of the program| - |(a - a0) of the
    reference|, over max(|(a - a0) of the reference|, its median), among
    the clients the reference moved.  The median, because the local SGD
    of the first call's later rounds amplifies the program's matmul
    rounding in a few clients (PERF.md section 2)."""
    import numpy as np
    d_prog, d_ref = (np.asarray(a, np.float64) - a0 for a in (prog, ref))
    if not np.isfinite(d_prog).all():
        return math.inf
    moved = d_ref != 0
    d_prog, d_ref = np.abs(d_prog[moved]), np.abs(d_ref[moved])
    scale = np.maximum(d_ref, np.median(d_ref))
    return float(np.median(np.abs(d_prog - d_ref) / scale))


def drive_first_steps(drv, t_start):
    """The timed path's first CHECK_STEPS calls, through the window's own
    call: they compile, and their parameters are kept on the host for
    the check.  -> (host params after each call, calls whose outputs
    were not finite, seconds spent copying)."""
    prog, held, failed = [], 0.0, 0
    for _ in range(CHECK_STEPS):
        failed += not drv.step()
        t = time.perf_counter()
        prog.append(drv.params())
        if len(prog) == 1:
            drv.prog_alphas = drv.alphas()
        held += time.perf_counter() - t
        log(f"step {len(prog)} done", t_start)
    return prog, failed, held


def check(drv, prog, lr, others=(), by_step=False):
    """With the program's state freed: the float32 reference from the
    seed through the same inputs, compared with `prog`, the program's
    host parameters after its first calls.  `others` names further
    references put in the program's place, each with the keyword
    arguments of `reference_params` (the control, a planted fault).
    -> {"program": numbers, <other>: numbers}; with by_step, each also
    holds the numbers after each earlier step alone."""
    import jax
    p0 = jax.device_get(drv.init_params())
    ref = reference_params(drv, CHECK_STEPS)
    ref_alphas = drv.ref_alphas
    runs = {"program": (prog, drv.prog_alphas)}
    for name, kw in others:
        runs[name] = (reference_params(drv, CHECK_STEPS, **kw),
                      drv.ref_alphas)
    out = {}
    for name, (got, alphas) in runs.items():
        out[name] = compare(drv, p0, got, ref, lr, (alphas, ref_alphas))
        if by_step:
            out[name]["by_step"] = [compare(drv, p0, got[:s], ref[:s], lr)
                                    for s in range(1, CHECK_STEPS)]
    return out


def reference_params(drv, n_steps, dtype="float32", precision="highest",
                     fault=None):
    """Host parameters after each of the reference's first n_steps, in
    `dtype` at matmul `precision`: float32 at "highest" is the reference;
    the configuration's "control" names the precision below it."""
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision(precision):
        return drv.reference(n_steps, jnp.dtype(dtype), fault=fault)


def peak_of(base, kind):
    peaks = load_json(base, "peaks.json")
    if kind not in peaks:
        raise KeyError(f"no published peaks for device kind {kind!r} in "
                       f"peaks.json; have {sorted(peaks)}")
    return peaks[kind]


def memory_peak(devices):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return max(peaks)


def run_cell(name, seed, seconds, trace, keep_trace=None, root=ROOT,
             t_start=None):
    """One run; returns the result dict (the last line of stdout).
    `root` is the checkout whose BENCHMARK.json and chipbench/ files
    define the cell; `t_start` the process's start on the host clock."""
    t_start = time.perf_counter() if t_start is None else t_start
    base = os.path.join(root, "chipbench")
    bench, run = find_cell(name, root)
    chips = run.entry["chips"]
    devs = require_chips(chips)
    peaks = peak_of(base, devs[0].device_kind)
    use_cache()
    compiles = Compiles()
    import jax
    # the precision the configuration states for the program's matmuls
    jax.config.update("jax_default_matmul_precision",
                      run.config["matmul_precision"])
    run.seed, run.devices, run.key = seed, devs[:chips], seed_key(seed)
    drv = load_module(base, "drivers", run.cell["driver"] + ".py").Driver(run)
    log(f"built {run.cell['driver']} driver", t_start)

    # the first calls compile; their outputs are kept for the check
    prog, failed, held = drive_first_steps(drv, t_start)
    setup_s = time.perf_counter() - t_start - held
    at_setup = compiles.snapshot()
    log(f"setup {setup_s:.3f}s (less {held:.3f}s of copies for the check); "
        f"programs {at_setup}", t_start)

    trace_dir = None
    if trace:
        trace_dir = keep_trace or tempfile.mkdtemp(prefix="chipbench-")
        jax.profiler.start_trace(trace_dir)
    calls, elapsed, w_failed = window(drv, min(seconds, TRACE_SECONDS)
                                  if trace else seconds)
    if trace:
        jax.profiler.stop_trace()
    mem_peak = memory_peak(run.devices)
    in_window = {k: v - at_setup[k] for k, v in compiles.snapshot().items()}
    log(f"window: {calls} calls in {elapsed:.3f}s; peak {mem_peak} bytes; "
        f"programs in the window {in_window}", t_start)
    failed += w_failed
    drv.release()

    # the check, with the program's state freed
    numbers = check(drv, prog, run.cell["method"]["server_lr"])["program"]
    log("compared", t_start)
    limits = run.cell["limits"]
    correct = failed == 0 and all(numbers[k] <= limits[k] for k in limits)

    metrics = {}
    if trace:
        from chipbench import trace as tr
        red = tr.reduce_file(tr.find_xplane(trace_dir))
        rd = Reading(run=run, red=red, steps=calls,
                     flops_per_step=drv.flops_per_step, chips=chips,
                     rounds_per_step=drv.rounds_per_step,
                     peak=peaks)
        units = {m["name"]: m["unit"] for m in bench["per_layer"]}
        for mname, mod in readers(bench, name, root).items():
            v = mod.read(rd)
            if v is not None:
                metrics[mname] = {"value": v, "unit": units[mname]}
        if keep_trace is None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        rate = calls * drv.units_per_step / elapsed
        for m in cell_metrics(bench, name, "end_to_end"):
            if m["name"] == "setup_s":
                metrics["setup_s"] = {"value": setup_s, "unit": m["unit"]}
            else:
                metrics[m["name"]] = {"value": rate, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": mem_peak}
    out = {"correct": bool(correct), "attempted": calls + CHECK_STEPS,
           "failed": failed, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = red.busy_s()
        device["window_s"] = red.window_s
        out["breakdown"] = {"device_ops": red.top_ops(),
                            "idle_gaps": red.idle_gaps()}
    out["check"] = {k: {"value": numbers[k], "limit": limits[k]}
                    for k in limits}
    return out


@dataclasses.dataclass
class Reading:
    """What a per-layer metric reader sees."""
    run: Run
    red: object          # trace.Reduction of the traced window
    steps: int           # timed calls inside the traced window
    flops_per_step: float
    chips: int
    peak: dict           # peaks.json entry of this device kind
    rounds_per_step: int = 1

    @property
    def rounds(self):
        return self.steps * self.rounds_per_step

    def idle_share(self):
        return 100.0 * (1.0 - self.red.busy_s() / self.red.window_s)

    def mfu(self):
        """The operations the traced steps required over what the chips
        could do at their bf16 peak in the traced window, in %."""
        return 100.0 * self.steps * self.flops_per_step / (
            self.red.window_s * self.chips * self.peak["flops_bf16"])

    def per_round_ms(self, seconds):
        """Device seconds in the window -> ms per round, or None if the
        trace showed none (the reader then reports nothing)."""
        return 1e3 * seconds / self.rounds if seconds > 0 else None
