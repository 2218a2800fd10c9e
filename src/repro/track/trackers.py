"""repro.track — streaming per-round telemetry (DESIGN.md §10).

The simulator's `run_rounds` drives thousands of rounds as ONE `lax.scan`
dispatch: until this module, every per-round diagnostic surfaced only as a
stacked array *after* the scan returned, so a long run was a black box
while it ran.  A `Tracker` is a host-side sink with a two-call protocol —

    tracker.log(round_idx, metrics)     # one scalar dict per round
    tracker.finish(summary)             # once, at end of run

— and the round bodies emit into it **from inside jit** via an ordered
`jax.experimental.io_callback`: the device computation streams each round's
scalar diagnostics to the host the moment that round's server update has
produced them, whether the round is Python-stepped (`run_round`,
`fed/distributed.make_round`) or scanned (`run_rounds`, sync and async
alike).  `tracker="none"` (the default) wires nothing: no callback op
enters the graph, so trajectories and compiled HLO are bit-identical to an
untracked run.

Trackers mirror the method/sampler/aggregator/fault registries
(`fed/api.py` §7, `fed/sampling.py` §8, `fed/faults.py` §9): a
`TrackerSpec` declares a factory plus typed options with defaults, sinks
register under a name, and `FLConfig.make(tracker=..., **opts)` validates
names and options at construction.  Registered sinks:

* ``none``      — the bit-identical default; `log` is never wired.
* ``jsonl``     — one JSON object per line, appended and flushed per round
  (crash-safe: a killed run keeps every completed round).  On checkpoint
  restart `resume(round_idx)` truncates rows past the restore point so the
  re-streamed rounds keep the file's round index monotone.
* ``csv``       — header from the first row's keys, one line per round.
* ``stdout``    — human-readable line per round, rate-limited by
  ``every`` (round stride) and ``interval`` (min seconds between lines).
* ``memory``    — rows kept on the instance (`.rows`), for tests and
  programmatic consumers.
* ``composite`` — fan-out to child sinks (stdout for the terminal + jsonl
  for the record is the serve-loop default).

Host-side enrichment: `emitter(tracker)` — the helper every runtime uses to
splice the callback into its jitted round — timestamps each callback and
adds two fields the device cannot know: ``sec_per_round`` (wall time
between consecutive round callbacks; the first round of a dispatch absorbs
its own compile time) and ``bytes_up_cum`` (running sum of the per-round
``bytes_up`` diagnostic, surviving checkpoint restore via `resume`).

Phase scopes: `scope(name)` wraps `jax.named_scope` with the fixed phase
vocabulary (``client_pass`` / ``encode`` / ``aggregate`` /
``server_update``) so `launch/dryrun.py` profiles and `jax.profiler` traces
map operators back to round phases.  Named scopes attach HLO metadata only
— they never change the computation.

Host spans: `span(name, **ids)` wraps `jax.profiler.TraceAnnotation`, so
the host phases of one dispatch land in a profiler trace on the same clock
as the device's ops, and an idle stretch of the device can be laid on what
the host was doing.  The vocabulary (``HOST_SPANS``):

* ``draw_batch`` — building the call's inputs on the host (round keys,
  round indices);
* ``dispatch``   — assembling the arguments and enqueueing the jitted call;
* ``block``      — waiting for the call's outputs and copying them to the
  host.

Each span carries integer ids as trace stats (``round``, the call's first
round index, on all three), so the spans of one call share an identifier.
With no profiler running a span is an inert C++ TraceMe (under a
microsecond), so the spans are always on.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
import time
import typing as tp

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# phase vocabulary for jax.profiler / HLO metadata (DESIGN.md §10.4)
# ---------------------------------------------------------------------------

CLIENT_PASS = "client_pass"
ENCODE = "encode"
AGGREGATE = "aggregate"
SERVER_UPDATE = "server_update"
PHASES = (CLIENT_PASS, ENCODE, AGGREGATE, SERVER_UPDATE)


def scope(name: str):
    """A profiler phase scope (`jax.named_scope`): free at runtime, tags the
    enclosed ops' HLO metadata so traces map back to round phases."""
    return jax.named_scope(name)


DRAW_BATCH = "draw_batch"
DISPATCH = "dispatch"
BLOCK = "block"
HOST_SPANS = (DRAW_BATCH, DISPATCH, BLOCK)


def span(name: str, **ids: int):
    """A host span (`jax.profiler.TraceAnnotation`) named from HOST_SPANS,
    with `ids` as its trace stats: marks host time on the profiler's clock
    and never touches a traced computation."""
    return jax.profiler.TraceAnnotation(name, **ids)


# Reserved aux key: with `FLConfig.track_variance` the client pass is
# wrapped (`with_grad_stats`) and every client uploads ||upload||^2 — one
# f32 scalar riding the aux dict exactly like the sampler statistics
# (fed/sampling.py NORM_KEY), counted in bytes_up honestly.
GNORM_KEY = "track_gnorm_sq"


# ---------------------------------------------------------------------------
# the Tracker protocol
# ---------------------------------------------------------------------------

class Tracker:
    """Base sink: `log(round_idx, metrics)` per round, `finish(summary)`
    once, `resume(round_idx)` on checkpoint restart.

    `log` receives a plain dict of python floats (plus the int round index)
    — it runs on the host inside an io_callback, so it must never call back
    into jax.  `resume` rewinds the sink to `round_idx` (a restored run
    re-streams rounds > round_idx) and returns the last surviving row (or
    None), which the runtime uses to restore host-side accumulators
    (`bytes_up_cum`)."""

    name = "base"

    def log(self, round_idx: int, metrics: dict) -> None:
        raise NotImplementedError

    def finish(self, summary: dict | None = None) -> None:
        pass

    def resume(self, round_idx: int) -> dict | None:
        return None


class NullTracker(Tracker):
    """`tracker="none"`: the runtimes check for this sink *statically* and
    wire no callback at all — the graph is bit-identical to an untracked
    run.  `log` still works (a no-op) so host-stepped callers need no
    branch."""

    name = "none"

    def log(self, round_idx: int, metrics: dict) -> None:
        pass


class MemoryTracker(Tracker):
    """Rows kept in memory (`.rows`: list of dicts with a "round" key) —
    the test sink, and the programmatic consumer's escape hatch."""

    name = "memory"

    def __init__(self):
        self.rows: list[dict] = []
        self.summary: dict | None = None

    def log(self, round_idx: int, metrics: dict) -> None:
        self.rows.append(dict(round=int(round_idx), **metrics))

    def finish(self, summary: dict | None = None) -> None:
        if summary is not None:
            self.summary = dict(summary)

    def resume(self, round_idx: int) -> dict | None:
        self.rows = [r for r in self.rows if r["round"] <= round_idx]
        return self.rows[-1] if self.rows else None


class JsonlTracker(Tracker):
    """Append-per-round JSON lines, flushed every row (crash-safe: a killed
    run keeps every completed round on disk; `tools/flwatch.py` tails the
    file live).  Round rows carry a "round" key; `finish(summary)` appends
    one {"summary": ...} row, which flwatch and the CI well-formedness
    check treat as terminal."""

    name = "jsonl"

    def __init__(self, path: str):
        self.path = path
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        self._f = open(path, "a", encoding="utf-8")

    def log(self, round_idx: int, metrics: dict) -> None:
        self._f.write(json.dumps(dict(round=int(round_idx), **metrics))
                      + "\n")
        self._f.flush()

    def finish(self, summary: dict | None = None) -> None:
        if summary is not None:
            self._f.write(json.dumps(dict(summary=summary)) + "\n")
        self._f.flush()
        self._f.close()

    def resume(self, round_idx: int) -> dict | None:
        """Truncate rows past the restore point: the restored run will
        re-stream rounds > round_idx, and a reader must never see the same
        round twice or a non-monotone index.  Returns the last kept row."""
        self._f.close()
        kept, last = [], None
        if os.path.exists(self.path):
            with open(self.path, encoding="utf-8") as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    row = json.loads(line)
                    # summary rows of the pre-restart run are stale too
                    if "round" in row and row["round"] <= round_idx:
                        kept.append(line)
                        last = row
        tmp = self.path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            f.write("".join(ln + "\n" for ln in kept))
        os.replace(tmp, self.path)          # atomic, like checkpoint.save
        self._f = open(self.path, "a", encoding="utf-8")
        return last


class CsvTracker(Tracker):
    """One CSV line per round; the header is fixed by the first row's keys
    (later rows write those columns; new keys are ignored — scalar diag
    layouts are static per run, so this only matters across configs)."""

    name = "csv"

    def __init__(self, path: str):
        self.path = path
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        self._f = open(path, "a", encoding="utf-8")
        self._keys: tuple[str, ...] | None = None

    def log(self, round_idx: int, metrics: dict) -> None:
        if self._keys is None:
            self._keys = ("round",) + tuple(sorted(metrics))
            if self._f.tell() == 0:
                self._f.write(",".join(self._keys) + "\n")
        row = dict(metrics, round=int(round_idx))
        self._f.write(",".join(repr(row[k]) if isinstance(row[k], str)
                               else f"{row[k]:g}" if k != "round"
                               else str(row[k])
                               for k in self._keys if k in row) + "\n")
        self._f.flush()

    def finish(self, summary: dict | None = None) -> None:
        self._f.flush()
        self._f.close()


class StdoutTracker(Tracker):
    """Rate-limited human-readable line per round: at most one line per
    `every` rounds AND per `interval` seconds (both gates must pass; the
    first row always prints)."""

    name = "stdout"

    def __init__(self, every: int = 1, interval: float = 0.0, stream=None):
        self.every = max(int(every), 1)
        self.interval = float(interval)
        self._stream = stream or sys.stdout
        self._last_t: float | None = None

    def _fmt(self, k: str, v) -> str:
        if k == "bytes_up" or k == "bytes_up_cum":
            return f"{k}={v / 1024.0:.1f}KiB"
        return f"{k}={v:.4g}"

    def log(self, round_idx: int, metrics: dict) -> None:
        now = time.perf_counter()
        first = self._last_t is None
        if not first:
            if round_idx % self.every != 0:
                return
            if now - self._last_t < self.interval:
                return
        self._last_t = now
        line = f"round {round_idx:5d}  " + "  ".join(
            self._fmt(k, metrics[k]) for k in sorted(metrics))
        print(line, file=self._stream, flush=True)

    def finish(self, summary: dict | None = None) -> None:
        if summary is not None:
            line = "finish  " + "  ".join(
                f"{k}={v}" for k, v in sorted(summary.items()))
            print(line, file=self._stream, flush=True)


class CompositeTracker(Tracker):
    """Fan-out to child sinks in order (stdout for the terminal + jsonl for
    the record is the serve-loop default)."""

    name = "composite"

    def __init__(self, children: tp.Sequence[Tracker]):
        self.children = tuple(children)

    def log(self, round_idx: int, metrics: dict) -> None:
        for c in self.children:
            c.log(round_idx, metrics)

    def finish(self, summary: dict | None = None) -> None:
        for c in self.children:
            c.finish(summary)

    def resume(self, round_idx: int) -> dict | None:
        last = None
        for c in self.children:
            row = c.resume(round_idx)
            last = row if row is not None else last
        return last


# ---------------------------------------------------------------------------
# registry (mirrors fed.api / fed.sampling / fed.aggregators / fed.faults)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TrackerSpec:
    """A registered sink: `factory(opts) -> Tracker`, with the same typed
    option contract as every other strategy registry — `options` names what
    `FLConfig.make` accepts, `defaults` fills the omitted ones, `validate`
    rejects bad values at construction (never at round time)."""
    name: str
    factory: tp.Callable
    options: tuple = ()
    defaults: dict = dataclasses.field(default_factory=dict)
    validate: tp.Callable | None = None
    description: str = ""


_REGISTRY: dict[str, TrackerSpec] = {}


def register_tracker(spec: TrackerSpec, *,
                     overwrite: bool = False) -> TrackerSpec:
    """Register `spec` under `spec.name`; returns it for chaining."""
    if not overwrite and spec.name in _REGISTRY:
        raise ValueError(f"tracker '{spec.name}' is already registered")
    if set(spec.defaults) - set(spec.options):
        raise ValueError(
            f"tracker '{spec.name}' has defaults for undeclared options: "
            f"{sorted(set(spec.defaults) - set(spec.options))}")
    _REGISTRY[spec.name] = spec
    return spec


def get_tracker(name: str) -> TrackerSpec:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown tracker '{name}'; registered: "
                       f"{sorted(_REGISTRY)}") from None


def registered_trackers() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def resolve_opts(spec: TrackerSpec, opts: dict | None) -> dict:
    """Merge user options over the sink's defaults, rejecting unknown names
    and bad values — the same contract as every other registry."""
    opts = dict(opts or {})
    bad = sorted(set(opts) - set(spec.options))
    if bad:
        raise TypeError(
            f"option(s) {bad} are not used by tracker '{spec.name}'; "
            f"valid options: {sorted(spec.options)}")
    resolved = {**spec.defaults, **opts}
    if spec.validate is not None:
        spec.validate(resolved)
    return resolved


def make_tracker(name: str, **opts) -> Tracker:
    """Validated construction: `make_tracker("jsonl", path="run.jsonl")`."""
    spec = get_tracker(name)
    return spec.factory(resolve_opts(spec, opts))


def _composite_factory(opts) -> CompositeTracker:
    children = []
    for c in opts["children"]:
        children.append(c if isinstance(c, Tracker) else make_tracker(c))
    return CompositeTracker(children)


def _composite_validate(opts):
    for c in opts["children"]:
        if not isinstance(c, (Tracker, str)):
            raise TypeError(f"composite children must be Tracker instances "
                            f"or registered names, got {type(c).__name__}")
        if isinstance(c, str):
            get_tracker(c)


register_tracker(TrackerSpec(
    name="none", factory=lambda opts: NullTracker(),
    description="no sink; the graph is bit-identical to an untracked run"))
register_tracker(TrackerSpec(
    name="memory", factory=lambda opts: MemoryTracker(),
    description="rows kept on the instance (.rows) — tests/programmatic"))
register_tracker(TrackerSpec(
    name="jsonl", factory=lambda opts: JsonlTracker(opts["path"]),
    options=("path",), defaults=dict(path="track.jsonl"),
    description="append-per-round JSON lines, flushed per row"))
register_tracker(TrackerSpec(
    name="csv", factory=lambda opts: CsvTracker(opts["path"]),
    options=("path",), defaults=dict(path="track.csv"),
    description="one CSV line per round (header from the first row)"))
def _stdout_validate(opts):
    if int(opts["every"]) < 1:
        raise ValueError(f"stdout tracker 'every' must be >= 1, got "
                         f"{opts['every']}")
    if float(opts["interval"]) < 0.0:
        raise ValueError(f"stdout tracker 'interval' must be >= 0, got "
                         f"{opts['interval']}")


register_tracker(TrackerSpec(
    name="stdout",
    factory=lambda opts: StdoutTracker(every=opts["every"],
                                       interval=opts["interval"]),
    options=("every", "interval"), defaults=dict(every=1, interval=0.0),
    validate=_stdout_validate,
    description="rate-limited human-readable line per round"))
register_tracker(TrackerSpec(
    name="composite", factory=_composite_factory,
    options=("children",), defaults=dict(children=()),
    validate=_composite_validate,
    description="fan-out to child sinks (instances or registered names)"))


def composite(*children: Tracker) -> CompositeTracker:
    """`composite(stdout_t, jsonl_t)` — programmatic fan-out shorthand."""
    return CompositeTracker(children)


# ---------------------------------------------------------------------------
# the in-jit emission splice (used by Simulator and fed/distributed)
# ---------------------------------------------------------------------------

def emitter(tracker: Tracker, ordered: bool = True):
    """Build `emit(r, metrics)` — callable at TRACE time inside a jitted
    round body — that streams the round's scalar metrics to `tracker`
    through one `jax.experimental.io_callback`.

    `emit` returns a dummy f32 scalar produced BY the host callback.  The
    effect token (ordered) and the callback's own sequencing only fix the
    *relative order* of callbacks — nothing stops XLA from scheduling the
    whole compute chain first and the callback chain at the very end of
    the dispatch (the CPU backend does exactly that, bunching every row
    into the last millisecond of a minutes-long scan).  Streaming needs a
    *data* dependency: the round runtimes thread the returned scalar into
    the next round's inputs via `tether`, so round r+1's compute cannot
    start until round r's row has reached the sink.

    `ordered=True` (default) threads a token through the callbacks, so
    under a `lax.scan` (and the async staleness=1 pipeline) rows arrive in
    round order, one per round, while the scan is still executing.  The
    metric *names* are a static trace-time fact (scalar diag layouts are
    fixed per configuration), so only the values cross the host boundary.

    Pass `ordered=False` on mesh paths: under jax 0.9.0 an ordered
    callback in a jit that holds shard_map collectives on several devices
    still fails in the SPMD partitioner ("side-effect HLO cannot have a
    replicated sharding": its effect token is replicated while the call
    is placed on device 0), with or without the device pin below.  The
    unordered callback is then pinned to device 0 (see below) so it
    still fires exactly once per round; on the single pinned device rows
    arrive in program order in practice, and every row carries its round
    index regardless.

    Host-side enrichment per callback:
      * ``sec_per_round`` — wall time since the previous round's callback.
        `emit.reset()` (called by the runtimes at each dispatch) restarts
        the clock so host work *between* dispatches (evaluation,
        checkpointing) is not charged to the next round; the first round
        after a reset absorbs its own dispatch + compile time.
      * ``bytes_up_cum`` — running sum of the ``bytes_up`` diagnostic.
        `emit.resume(last_row)` restores the accumulator from a sink's
        surviving row after a checkpoint restart.

    Call `emit(r, metrics)` with `r` the traced (1-based) round number and
    `metrics` a dict of traced scalars; it appends the callback to the
    traced computation and returns the dummy scalar to `tether` into the
    next round's inputs.
    """
    import numpy as np
    from jax.experimental import io_callback

    state = {"t": None, "bytes": 0.0, "host": {}}

    def emit(r, metrics):
        names = tuple(sorted(metrics))

        def cb(r_, *vals):
            now = time.perf_counter()
            m = {k: float(v) for k, v in zip(names, vals)}
            m["sec_per_round"] = (now - state["t"]
                                  if state["t"] is not None else 0.0)
            state["t"] = now
            state["bytes"] += m.get("bytes_up", 0.0)
            m["bytes_up_cum"] = state["bytes"]
            # host-side enrichment from the store pipeline (host_mem_peak,
            # prefetch_overlap_frac — DESIGN.md §11.4): values the driver
            # published before dispatching the round, so they lag the
            # device metrics by at most one dispatch
            m.update(state["host"])
            tracker.log(int(r_), m)
            return np.float32(0.0)    # the tether: see docstring

        # on a multi-device backend, pin the callback to device 0: under
        # SPMD an unplaced unordered callback may fire once per device —
        # the metrics are replicated scalars, one firing is the contract
        kw = {}
        if len(jax.devices()) > 1:
            kw["sharding"] = jax.sharding.SingleDeviceSharding(
                jax.devices()[0])
        return io_callback(cb, jax.ShapeDtypeStruct((), jnp.float32),
                           r, *[metrics[k] for k in names],
                           ordered=ordered, **kw)

    def reset():
        state["t"] = time.perf_counter()

    def resume(last_row: dict | None):
        state["t"] = None
        state["bytes"] = float((last_row or {}).get("bytes_up_cum", 0.0))

    def set_host_metrics(metrics: dict):
        """Publish host-side metrics to merge into every subsequent row.
        Merge semantics (update, not replace): independent publishers —
        the host-store driver's memory/overlap gauges and the serve
        coordinator's queue/admission counters — each own their keys and
        refresh them once per round before dispatch without clobbering
        the other's."""
        state["host"].update({k: float(v) for k, v in metrics.items()})

    emit.reset = reset
    emit.resume = resume
    emit.set_host_metrics = set_host_metrics
    return emit


def tether(params, z):
    """Make one leaf of `params` data-depend on `z` without changing any
    value, so the next round's compute waits for `z`.  The round runtimes
    tie the emitter's callback result into the next round's params — the
    only thing that actually forces XLA to run round r's callback before
    round r+1's compute (effect tokens alone fix relative callback order,
    not callback-vs-compute placement, and the CPU backend otherwise
    defers every callback to the end of the dispatch).

    Implementation notes:

    * `lax.optimization_barrier` is NOT enough — XLA's barrier expander
      strips the op before scheduling, and in the simulator's unrolled
      CPU scan the callbacks then collapse back to the dispatch tail
      (measured: 8 rows in the last 3 ms of a 12 s dispatch).  Instead
      the gated leaf becomes `where(z == 0, leaf, 0)`: the callback
      always returns 0.0 so the select always takes the leaf unchanged,
      but `z` is the result of an opaque custom call, so no
      simplification pass can fold the select away and the data
      dependency survives to the scheduler.
    * Only the *smallest* leaf is gated, not the whole tree.  Every
      client's forward pass consumes every params leaf, so gating one is
      enough: round r+1's backward/aggregation transitively waits on
      round r's row (the wall-clock-spread test pins this), while the
      rest of the compute graph keeps its exact untracked fusion.  Any
      inserted op can shift XLA's fusion clusters and hence float
      reassociation — gating one small bias keeps that perturbation
      minimal, but a tracked run is still only schedule-equivalent, not
      always bit-equal, to an untracked one (quantizing codecs can latch
      a last-ulp difference into a visibly different trajectory; the
      `tracker="none"` build stages neither callback nor select and
      stays exactly bit-identical — DESIGN.md §10.2)."""
    leaves, treedef = jax.tree.flatten(params)
    idx = min(range(len(leaves)), key=lambda i: leaves[i].size)
    pred = z == jnp.float32(0.0)   # runtime-true; opaque to the compiler
    leaves[idx] = jnp.where(pred, leaves[idx],
                            jnp.zeros((), leaves[idx].dtype))
    return jax.tree.unflatten(treedef, leaves)


def with_grad_stats(client_fn):
    """Compose a ctx-signature client fn with the telemetry upload: the
    squared norm of the raw (pre-codec) f32 upload rides the aux dict under
    `GNORM_KEY` — one extra reduction per client, 4 uploaded bytes, and the
    server derives the cohort gradient-variance proxy
    E_w ||g_u||^2 - ||agg||^2 from it (DESIGN.md §10.3).  Applied before
    the codec wrapper, like `sampling.with_stats`."""
    from repro.utils.tree_math import tree_norm_sq

    def fn(ctx, params, cstate, batches, key):
        out = client_fn(ctx, params, cstate, batches, key)
        aux = dict(out.aux, **{GNORM_KEY: tree_norm_sq(out.grad)})
        return out._replace(aux=aux)
    return fn
