"""repro.track: streaming per-round telemetry (DESIGN.md §10).

Covers the tracker registry contract (names, typed options, FLConfig
routing), the `none` bit-identity guarantee across sync/async/mesh round
builds, in-scan streaming through the ordered io_callback (the jsonl file
gains one row per round WHILE `run_rounds`'s lax.scan executes), the
async-bubble zeroed-row invariant, checkpoint-restart resume semantics,
and the host-side sinks/emitter in isolation.
"""
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import track
from repro.data import federated_splits
from repro.fed import FLConfig, Simulator, Task
from repro.models import lenet
from repro.sharding import cohort_mesh

ROOT = os.path.join(os.path.dirname(__file__), "..")
SRC = os.path.join(ROOT, "src")

# host-side fields the emitter adds in the callback — excluded from
# parity checks against the device-side stacked diagnostics
HOST_KEYS = ("round", "sec_per_round", "bytes_up_cum")


@pytest.fixture(scope="module")
def tiny_setup():
    spec, train, test = federated_splits("mnist", n_clients=6, alpha=0.5,
                                         seed=0, scale=0.1)
    cfg = lenet.LeNetConfig(n_classes=spec.n_classes,
                            image_size=spec.image_size,
                            channels=spec.channels)
    task = Task(loss=lambda p, b: lenet.loss_fn(cfg, p, b),
                accuracy=lambda p, b: lenet.accuracy(cfg, p, b),
                head_keys=lenet.HEAD_KEYS)
    params = lenet.init(cfg, jax.random.PRNGKey(0))
    return task, params, train, test


def _sim(tiny_setup, tracker="none", tracker_opts=None, staleness=0,
         mesh=None, track_variance=False, fault="none", fault_opts=None,
         tracker_obj=None, **opts):
    task, params, train, _ = tiny_setup
    params = jax.tree.map(jnp.copy, params)   # run_rounds donates buffers
    fl = FLConfig.make(method="fedncv", n_clients=6, cohort=3, k_micro=3,
                       micro_batch=4, server_lr=0.5, ncv_beta=0.0,
                       local_epochs=1, staleness=staleness, tracker=tracker,
                       tracker_opts=dict(tracker_opts or {}),
                       track_variance=track_variance, fault=fault,
                       fault_opts=dict(fault_opts or {}), **opts)
    return Simulator(task, params, train, fl, seed=0, mesh=mesh,
                     tracker=tracker_obj)


def _same(d0, d1):
    assert sorted(d0) == sorted(d1), (sorted(d0), sorted(d1))
    for k in d0:
        np.testing.assert_array_equal(np.asarray(d0[k]), np.asarray(d1[k]),
                                      err_msg=k)


# ----------------------------- registry --------------------------------------

def test_registry_roster_and_validation():
    for name in ("none", "memory", "jsonl", "csv", "stdout", "composite"):
        assert name in track.registered_trackers()
    with pytest.raises(KeyError, match="unknown tracker"):
        track.get_tracker("nope")
    with pytest.raises(TypeError, match="not used by tracker"):
        track.make_tracker("stdout", path="x")
    with pytest.raises(ValueError, match="every"):
        track.make_tracker("stdout", every=0)
    with pytest.raises(ValueError, match="interval"):
        track.make_tracker("stdout", interval=-1.0)
    with pytest.raises(TypeError, match="composite children"):
        track.make_tracker("composite", children=[42])
    with pytest.raises(ValueError, match="already registered"):
        track.register_tracker(track.get_tracker("memory"))


def test_flconfig_routes_tracker_options(tiny_setup, tmp_path):
    # FLConfig.make validates the tracker name + typed options
    with pytest.raises(KeyError, match="unknown tracker"):
        FLConfig.make(method="fedncv", tracker="nope")
    with pytest.raises(TypeError, match="not used by"):
        FLConfig.make(method="fedncv", tracker="jsonl", every=3)
    # bare-option routing: `every` belongs to stdout alone
    fl = FLConfig.make(method="fedncv", tracker="stdout", every=5)
    assert fl.tracker_opts == {"every": 5}
    # bad values are rejected at construction, not at round time
    with pytest.raises(ValueError, match="every"):
        FLConfig.make(method="fedncv", tracker="stdout", every=0)


def test_memory_and_jsonl_sinks_unit(tmp_path):
    m = track.MemoryTracker()
    m.log(1, {"a": 1.0})
    m.log(2, {"a": 2.0})
    m.finish({"done": True})
    assert [r["round"] for r in m.rows] == [1, 2]
    assert m.summary == {"done": True}
    assert m.resume(1) == {"round": 1, "a": 1.0}
    assert len(m.rows) == 1

    path = os.path.join(str(tmp_path), "t.jsonl")
    j = track.JsonlTracker(path)
    for r in range(1, 5):
        j.log(r, {"a": float(r)})
    last = j.resume(2)          # truncate rows 3, 4
    assert last == {"round": 2, "a": 2.0}
    j.log(3, {"a": 30.0})
    j.finish({"ok": 1})
    rows = [json.loads(l) for l in open(path)]
    assert [r.get("round") for r in rows] == [1, 2, 3, None]
    assert rows[-1] == {"summary": {"ok": 1}}


def test_composite_fans_out_and_resumes(tmp_path):
    a, b = track.MemoryTracker(), track.MemoryTracker()
    c = track.composite(a, b)
    c.log(1, {"x": 1.0})
    c.log(2, {"x": 2.0})
    assert len(a.rows) == len(b.rows) == 2
    assert c.resume(1)["round"] == 1
    assert len(a.rows) == len(b.rows) == 1
    c.finish({"s": 1})
    assert a.summary == b.summary == {"s": 1}


def test_emitter_host_enrichment():
    m = track.MemoryTracker()
    emit = track.emitter(m)
    jax.jit(lambda r, v: emit(r, {"bytes_up": v}))(
        jnp.int32(1), jnp.float32(100.0))
    jax.jit(lambda r, v: emit(r, {"bytes_up": v}))(
        jnp.int32(2), jnp.float32(50.0))
    jax.effects_barrier()
    assert [r["bytes_up_cum"] for r in m.rows] == [100.0, 150.0]
    assert all(r["sec_per_round"] >= 0.0 for r in m.rows)
    # resume restores the accumulator from the surviving row
    emit.resume({"bytes_up_cum": 70.0})
    jax.jit(lambda r, v: emit(r, {"bytes_up": v}))(
        jnp.int32(3), jnp.float32(1.0))
    jax.effects_barrier()
    assert m.rows[-1]["bytes_up_cum"] == 71.0


# ------------------------ none bit-identity ----------------------------------

@pytest.mark.parametrize("staleness", [0, 1])
def test_none_bit_identical_to_memory_tracked(tiny_setup, staleness):
    """Identical trajectories and stacked diags with and without a sink —
    the callback is pure observation."""
    sa = _sim(tiny_setup, staleness=staleness)
    sb = _sim(tiny_setup, tracker="memory", staleness=staleness)
    da = sa.run_rounds(3)
    db = sb.run_rounds(3)
    _same(da, db)
    for x, y in zip(jax.tree.leaves(sa.params), jax.tree.leaves(sb.params)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_none_stages_no_callback_op(tiny_setup):
    """tracker="none" must not stage an io_callback: the lowered HLO of the
    round is callback-free (the bit-identity guarantee, statically)."""
    sim = _sim(tiny_setup)
    assert sim._emit is None and not sim._track_on
    txt = jax.jit(sim._round_core).lower(
        sim.params, sim._get_state(), jax.random.PRNGKey(0),
        jnp.int32(1)).as_text()
    assert "callback" not in txt.lower()
    tracked = _sim(tiny_setup, tracker="memory")
    txt2 = jax.jit(tracked._round_core).lower(
        tracked.params, tracked._get_state(), jax.random.PRNGKey(0),
        jnp.int32(1)).as_text()
    assert "callback" in txt2.lower()


def test_none_bit_identical_mesh(tiny_setup):
    sa = _sim(tiny_setup, mesh=cohort_mesh())
    sb = _sim(tiny_setup, tracker="memory", mesh=cohort_mesh())
    _same(sa.run_rounds(2), sb.run_rounds(2))
    rows = sorted(sb.tracker.rows, key=lambda r: r["round"])
    assert [r["round"] for r in rows] == [1, 2]


# ------------------------ host spans -----------------------------------------

def _host_spans(trace_dir):
    """[(start_ns, end_ns, name, ids)] of the HOST_SPANS in the profile
    written under trace_dir, in time order."""
    import glob
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    pd = ProfileData.from_file(path)
    return sorted((int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                   ev.name, dict(ev.stats))
                  for plane in pd.planes if plane.name.startswith("/host:")
                  for line in plane.lines for ev in line.events
                  if ev.name in track.HOST_SPANS)


def test_span_is_a_trace_annotation():
    with track.span(track.DISPATCH, round=3, rounds=2) as s:
        assert isinstance(s, jax.profiler.TraceAnnotation)
    assert track.HOST_SPANS == ("draw_batch", "dispatch", "block")


@pytest.mark.parametrize("staleness,tracker", [
    (0, "none"), (1, "none"), (2, "none"), (0, "memory")])
def test_run_rounds_writes_host_spans(tiny_setup, tmp_path, staleness,
                                      tracker):
    """Each call writes draw_batch, dispatch, block once, in that order and
    without overlap, each with the call's first round as `round`; under a
    profiler the trajectory and diagnostics equal an unprofiled run's."""
    plain = _sim(tiny_setup, staleness=staleness, tracker=tracker)
    sim = _sim(tiny_setup, staleness=staleness, tracker=tracker)
    calls = [(0, 2), (2, 2)]
    want = [plain.run_rounds(n) for _, n in calls]
    with jax.profiler.trace(str(tmp_path)):
        got = [sim.run_rounds(n) for _, n in calls]
    for a, b in zip(want, got):
        _same(a, b)
    for x, y in zip(jax.tree.leaves(plain.params),
                    jax.tree.leaves(sim.params)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    spans = _host_spans(str(tmp_path))
    assert [s[2] for s in spans] == list(track.HOST_SPANS) * len(calls)
    assert all(a[1] <= b[0] for a, b in zip(spans, spans[1:]))
    for i, (start, n) in enumerate(calls):
        draw, disp, block = (s[3] for s in spans[3 * i:3 * i + 3])
        assert draw == {"round": start}
        assert disp == {"round": start, "rounds": n}
        assert block == {"round": start, "arrays": len(got[i])}


# ------------------------ in-scan streaming ----------------------------------

class _FileCountProbe(track.Tracker):
    """Records, at each log() callback, how many complete rows the jsonl
    sibling sink has already flushed (and a wall-clock stamp) — run as a
    composite AFTER the jsonl sink, it proves rows hit the file while the
    scan is still executing."""

    def __init__(self, path):
        self.path = path
        self.seen = []
        self.stamps = []

    def log(self, round_idx, metrics):
        self.stamps.append(time.perf_counter())
        with open(self.path, encoding="utf-8") as f:
            self.seen.append((int(round_idx), sum(1 for _ in f)))


def test_jsonl_streams_during_scan(tiny_setup, tmp_path):
    """One flushed row per round, visible before the scan returns: at the
    round-r callback the file already holds >= r rows (ordered=True keeps
    round order), and the final file has exactly n_rounds rows."""
    path = os.path.join(str(tmp_path), "stream.jsonl")
    probe = _FileCountProbe(path)
    sink = track.composite(track.JsonlTracker(path), probe)
    sim = _sim(tiny_setup, tracker_obj=sink)
    diags = sim.run_rounds(5)
    rows = [json.loads(l) for l in open(path)]
    assert [r["round"] for r in rows] == [1, 2, 3, 4, 5]
    assert probe.seen == [(r, r) for r in range(1, 6)]
    # the streamed rows equal the stacked diagnostics, row by row
    for i, row in enumerate(rows):
        for k, v in diags.items():
            assert row[k] == pytest.approx(float(v[i]), rel=1e-6), k
    # rows must land DURING the dispatch, not burst out at its end: on a
    # compile-warm scan the callback stamps should spread across the
    # execution (the track.tether data dependency — without it the CPU
    # runtime bunches every callback into the dispatch's last millisecond)
    probe.stamps.clear()
    t0 = time.perf_counter()
    sim.run_rounds(5)
    total = time.perf_counter() - t0
    span = probe.stamps[-1] - probe.stamps[0]
    assert span > 0.3 * total, (
        f"telemetry bunched at dispatch end: callback span {span:.4f}s "
        f"of a {total:.4f}s dispatch")


def test_run_round_and_chunked_run_rounds_number_contiguously(tiny_setup):
    sim = _sim(tiny_setup, tracker="memory")
    sim.run_round()
    sim.run_rounds(2)
    sim.run_round()
    assert [r["round"] for r in sim.tracker.rows] == [1, 2, 3, 4]


# ------------------------ async bubble invariant -----------------------------

def test_async_bubble_streams_zeroed_row(tiny_setup):
    """staleness=1's warmup bubble (round 1) must stream a row of ZEROS —
    `_round_async_core` jnp.where-zeroes every diag key so the tracker
    sees defined values and round numbering stays aligned with sync."""
    sim = _sim(tiny_setup, tracker="memory", staleness=1)
    sim.run_rounds(4)
    rows = sim.tracker.rows
    assert [r["round"] for r in rows] == [1, 2, 3, 4]
    bubble = {k: v for k, v in rows[0].items() if k not in HOST_KEYS}
    assert bubble and all(v == 0.0 for v in bubble.values()), bubble
    # later rounds are real: at least one live metric is nonzero
    assert any(v != 0.0 for k, v in rows[1].items() if k not in HOST_KEYS)
    # bytes_up_cum counted nothing for the bubble round
    assert rows[0]["bytes_up_cum"] == 0.0


def test_async_bubble_zeroed_with_faults(tiny_setup):
    """The invariant holds for every diag key the fault layer adds."""
    sim = _sim(tiny_setup, tracker="memory", staleness=1, fault="dropout",
               fault_opts={"drop_rate": 0.3})
    sim.run_rounds(3)
    rows = sim.tracker.rows
    assert "live" in rows[1] and "corrupt_frac" not in rows[1]
    bubble = {k: v for k, v in rows[0].items() if k not in HOST_KEYS}
    assert all(v == 0.0 for v in bubble.values()), bubble


# ------------------------ metric surface -------------------------------------

def test_track_variance_adds_gvar_proxy(tiny_setup):
    base = _sim(tiny_setup)
    sim = _sim(tiny_setup, tracker="memory", track_variance=True)
    d0 = base.run_rounds(3)
    d1 = sim.run_rounds(3)
    assert "gvar_proxy" in d1 and "gvar_proxy" not in d0
    assert np.all(np.asarray(d1["gvar_proxy"]) >= 0.0)
    # the per-client ||g||^2 scalar is an honest upload: bytes_up grows
    assert float(d1["bytes_up"][0]) > float(d0["bytes_up"][0])
    assert all("gvar_proxy" in r for r in sim.tracker.rows)


def test_fault_counters_stream(tiny_setup):
    # byz_frac=0.7 -> 5 of 6 client ids are adversarial, so every cohort
    # of 3 holds at least 2: corrupt_frac is deterministically positive
    sim = _sim(tiny_setup, tracker="memory", fault="byzantine",
               fault_opts={"byz_frac": 0.7, "byz_scale": 10.0})
    sim.run_rounds(2)
    for r in sim.tracker.rows:
        assert 2.0 / 3.0 <= r["corrupt_frac"] <= 1.0, r
    # corrupt_frac is tracker-only: an untracked build stays bit-identical
    base = _sim(tiny_setup, fault="byzantine",
                fault_opts={"byz_frac": 0.7, "byz_scale": 10.0})
    d0 = base.run_rounds(2)
    assert "corrupt_frac" not in d0


# ------------------------ checkpoint-restart ---------------------------------

def test_checkpoint_restore_resumes_round_numbering(tiny_setup, tmp_path):
    """Crash-after-checkpoint: the pre-crash run streamed rounds the
    checkpoint never saw; restore truncates them and the resumed run
    continues the SAME file with a monotone round index and a continuous
    bytes_up_cum."""
    from repro.checkpoint import restore_sim, save_sim
    path = os.path.join(str(tmp_path), "run.jsonl")
    ckdir = os.path.join(str(tmp_path), "ck")
    sa = _sim(tiny_setup, tracker="jsonl", tracker_opts={"path": path})
    sa.run_rounds(2)
    save_sim(ckdir, sa)
    sa.run_rounds(2)            # rounds 3-4: streamed, never checkpointed
    assert len(open(path).readlines()) == 4

    sb = _sim(tiny_setup, tracker="jsonl", tracker_opts={"path": path})
    restore_sim(ckdir, sb)
    rows = [json.loads(l) for l in open(path)]
    assert [r["round"] for r in rows] == [1, 2]      # stale rows truncated
    sb.run_rounds(2)
    rows = [json.loads(l) for l in open(path)]
    assert [r["round"] for r in rows] == [1, 2, 3, 4]
    cums = [r["bytes_up_cum"] for r in rows]
    assert all(b > a for a, b in zip(cums, cums[1:])), cums


# ------------------------ multi-device (subprocess) --------------------------
# jax fixes the device count at first backend use, so genuine multi-device
# coverage runs in a subprocess with XLA_FLAGS, like tests/test_distributed.py.
# These also pin the jax 0.9.0 workaround: mesh paths use ordered=False
# callbacks (the SPMD partitioner rejects an ordered callback's effect
# token in a jit holding shard_map collectives), pinned to device 0 so each
# round still fires exactly once.

MESH_CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
import numpy as np
from repro import track
from repro.data import federated_splits
from repro.fed import FLConfig, Simulator, Task
from repro.models import lenet
from repro.sharding import cohort_mesh

spec, train, test = federated_splits("mnist", n_clients=6, alpha=0.5,
                                     seed=0, scale=0.1)
cfg = lenet.LeNetConfig(n_classes=spec.n_classes, image_size=spec.image_size,
                        channels=spec.channels)
task = Task(loss=lambda p, b: lenet.loss_fn(cfg, p, b),
            accuracy=lambda p, b: lenet.accuracy(cfg, p, b),
            head_keys=lenet.HEAD_KEYS)
params0 = lenet.init(cfg, jax.random.PRNGKey(0))

def mk(tracker="none", staleness=0):
    fl = FLConfig.make(method="fedncv", n_clients=6, cohort=3, k_micro=3,
                       micro_batch=4, server_lr=0.5, ncv_beta=0.0,
                       staleness=staleness, tracker=tracker)
    return Simulator(task, jax.tree.map(jnp.copy, params0), train, fl,
                     seed=0, mesh=cohort_mesh())

assert len(jax.devices()) == 4
d0 = mk().run_rounds(3)
sm = mk(tracker="memory")
d1 = sm.run_rounds(3)
for k in d0:
    assert np.array_equal(np.asarray(d0[k]), np.asarray(d1[k])), k
# exactly one firing per round (device-0 pinned), not one per device
rows = sorted(sm.tracker.rows, key=lambda r: r["round"])
assert [r["round"] for r in rows] == [1, 2, 3], rows

sma = mk(tracker="memory", staleness=1)
sma.run_rounds(3)
arows = sorted(sma.tracker.rows, key=lambda r: r["round"])
assert [r["round"] for r in arows] == [1, 2, 3]
z = {k: v for k, v in arows[0].items()
     if k not in ("round", "sec_per_round", "bytes_up_cum")}
assert z and all(v == 0.0 for v in z.values()), z
print("MESH_TRACK_OK")
"""


@pytest.mark.slow
def test_mesh_tracking_subprocess():
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", MESH_CODE],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert "MESH_TRACK_OK" in out.stdout, (out.stdout[-1000:],
                                           out.stderr[-2000:])


DIST_TRACK_CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
import numpy as np
from repro import track
from repro.fed import api
from repro.fed.distributed import init_distributed_state, make_round
from repro.fed.methods import MethodConfig, Task
from repro.models import lenet

from repro.sharding import make_mesh
mesh = make_mesh((4,), ("data",))
cfg = lenet.LeNetConfig(n_classes=4, image_size=16, channels=1)
task = Task(loss=lambda p, b: lenet.loss_fn(cfg, p, b))
params = lenet.init(cfg, jax.random.PRNGKey(0))
M, K, B = 4, 3, 8
key = jax.random.PRNGKey(1)
batch = dict(images=jax.random.normal(key, (M, K, B, 16, 16, 1)),
             labels=jax.random.randint(key, (M, K, B), 0, 4))
n_u = jnp.asarray([10.0, 20.0, 30.0, 40.0])
mc = MethodConfig(name="fedncv", ncv_beta=0.0)
state = init_distributed_state(api.get_method("fedncv"), params, task, mc, M)

p0, _, m0 = make_round("fedncv", task, mesh, mc, server_lr=0.5)(
    params, dict(state), batch, n_u, jnp.int32(1))

trk = track.MemoryTracker()
rf = make_round("fedncv", task, mesh, mc, server_lr=0.5, tracker=trk)
p1, s1, m1 = rf(params, dict(state), batch, n_u, jnp.int32(1))
p1, s1, m1 = rf(p1, s1, batch, n_u, jnp.int32(2))
jax.effects_barrier()
# one row per round_fn call (not per device), round index from the arg
assert [r["round"] for r in trk.rows] == [1, 2], trk.rows
# tracked round 1 == untracked round 1, metric for metric
for k in m0:
    assert np.allclose(float(m0[k]), trk.rows[0][k]), k
err = max(float(jnp.max(jnp.abs(a - b)))
          for a, b in zip(jax.tree.leaves(p0), jax.tree.leaves(
              rf(params, dict(state), batch, n_u, jnp.int32(1))[0])))
assert err == 0.0, err
print("DIST_TRACK_OK")
"""


@pytest.mark.slow
def test_distributed_tracking_subprocess():
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", DIST_TRACK_CODE],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert "DIST_TRACK_OK" in out.stdout, (out.stdout[-1000:],
                                           out.stderr[-2000:])


# ------------------------ flwatch CLI ----------------------------------------

def test_flwatch_check_gate(tmp_path):
    good = os.path.join(str(tmp_path), "good.jsonl")
    with open(good, "w") as f:
        for r in range(1, 4):
            f.write(json.dumps({"round": r, "agg_norm": 1.0 / r}) + "\n")
        f.write(json.dumps({"summary": {"rounds": 3}}) + "\n")
    flwatch = os.path.join(ROOT, "tools", "flwatch.py")

    def run(*argv):
        return subprocess.run([sys.executable, flwatch, *argv],
                              capture_output=True, text=True, timeout=60)

    ok = run(good, "--check", "--expect-rounds", "3")
    assert ok.returncode == 0, ok.stderr
    assert "monotone index" in ok.stdout and "summary present" in ok.stdout

    n = run(good, "--check", "--expect-rounds", "5")
    assert n.returncode == 1 and "expected 5" in n.stderr

    bad = os.path.join(str(tmp_path), "bad.jsonl")
    with open(bad, "w") as f:
        f.write(json.dumps({"round": 2, "x": 1.0}) + "\n")
        f.write(json.dumps({"round": 2, "x": 2.0}) + "\n")
    b = run(bad, "--check")
    assert b.returncode == 1 and "not strictly increasing" in b.stderr

    table = run(good)
    assert table.returncode == 0
    assert "agg_norm" in table.stdout and "ema" in table.stdout
