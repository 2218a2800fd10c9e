"""Reduction of one profiler trace (an `.xplane.pb`) to intervals.

The device planes are those named `/device:TPU:<n>`; their "XLA Ops"
line holds one event per executed HLO operation (a Pallas kernel is one
such event, a `tpu_custom_call` named after its kernel).  Host spans are
the `jax.profiler.TraceAnnotation`s the harness writes: `window` around
the traced steps, and `draw_batch`, `dispatch`, `block` inside it.
Every time below is a measure of a union of intervals, clipped to the
window, so nested or overlapping events never count twice.  A scope or
kernel is looked for in an op's name and in all of its text stats.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

HOST_SPANS = ("draw_batch", "dispatch", "block")
DEVICE_RE = re.compile(r"^/device:TPU:(\d+)$")
COLLECTIVE_RE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all",
    re.IGNORECASE)


@dataclasses.dataclass
class Op:
    start: int          # ns
    end: int            # ns
    name: str           # hlo op name, e.g. "fusion.12"
    scope: str          # the event's text stats (source scope, long name,
                        # kernel name), joined by "|"


@dataclasses.dataclass
class Reduction:
    window: tuple                # (start_ns, end_ns) of the `window` span
    ops: dict                    # device id -> [Op] inside the window
    spans: list                  # [(start_ns, end_ns, name)] host spans

    @property
    def window_s(self):
        return (self.window[1] - self.window[0]) * 1e-9

    def devices(self):
        return sorted(self.ops)

    def time_s(self, pred, device=None):
        """Union measure (s) of the ops matching pred(op), averaged over
        the devices (or on one device)."""
        devs = [device] if device is not None else self.devices()
        if not devs:
            return 0.0
        tot = sum(union_ns([(o.start, o.end) for o in self.ops[d]
                            if pred(o)]) for d in devs)
        return tot * 1e-9 / len(devs)

    def busy_s(self):
        return self.time_s(lambda o: True)

    def scope_s(self, scope):
        return self.time_s(lambda o: scope in o.scope)

    def kernel_s(self, kernel):
        return self.time_s(lambda o: kernel in o.name or kernel in o.scope)

    def collective_s(self):
        return self.time_s(lambda o: bool(COLLECTIVE_RE.search(o.name)))

    def top_ops(self, n=10):
        """[(name, seconds per device)] of the ops that took most time."""
        tot = {}
        for d in self.devices():
            for o in self.ops[d]:
                tot[o.name] = tot.get(o.name, 0) + (o.end - o.start)
        k = max(len(self.ops), 1)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns * 1e-9 / k] for name, ns in top]

    def idle_gaps(self, n=10):
        """[(host span during the gap, seconds)] of the longest stretches
        of the window in which the first device ran nothing."""
        devs = self.devices()
        if not devs:
            return []
        busy = merge([(o.start, o.end) for o in self.ops[devs[0]]])
        gaps, t = [], self.window[0]
        for s, e in busy + [(self.window[1], self.window[1])]:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[self.host_span_at((s + e) // 2), (e - s) * 1e-9]
                for s, e in gaps[:n]]

    def host_span_at(self, t):
        inner = [(e - s, name) for s, e, name in self.spans
                 if s <= t < e and name in HOST_SPANS]
        return min(inner)[1] if inner else "host_other"


def merge(iv):
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def union_ns(iv):
    return sum(e - s for s, e in merge(iv))


def find_xplane(trace_dir):
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def _text(ev):
    """Every text stat of an event: where the op's `jax.named_scope`
    path, its long HLO name and a custom call's kernel name are kept."""
    return "|".join(v for _, v in ev.stats if isinstance(v, str))


def reduce_file(path):
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans, raw = [], {}
    for plane in pd.planes:
        m = DEVICE_RE.match(plane.name)
        if m:
            lines = [ln for ln in plane.lines if ln.name == "XLA Ops"]
            raw[int(m.group(1))] = [
                Op(int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                   ev.name, _text(ev))
                for ln in lines for ev in ln.events]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name == "window" or ev.name in HOST_SPANS:
                        spans.append((int(ev.start_ns),
                                      int(ev.start_ns + ev.duration_ns),
                                      ev.name))
    windows = [(s, e) for s, e, n in spans if n == "window"]
    if not windows:
        raise ValueError(f"{path}: no `window` host span")
    w = windows[0]
    ops = {d: [Op(max(o.start, w[0]), min(o.end, w[1]), o.name, o.scope)
               for o in lst if o.end > w[0] and o.start < w[1]]
           for d, lst in raw.items()}
    return Reduction(window=w, ops=ops, spans=spans)
