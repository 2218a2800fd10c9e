"""Device idle time laid on the program's host spans, and the check that
the host and device timelines of a trace share one clock.

The program writes `draw_batch`, `dispatch` and `block` host spans
(`repro.track.span`) around each dispatch.  For one device, its idle
time is the traced window less the union of its ops; the idle time in a
span name is the measure of that idle time inside the union of the
spans of that name, averaged over the devices (`idle_in_span_s`, read
from a `trace.Reduction`).

That measure means something only where the device's ops and the host's
spans are on one clock.  `device_lead_ns` reads how far apart they are
from the trace itself: each program the host enqueues carries a
`run_id`, on the host's `DoEnqueueProgram` and `CompleteCallbacks`
events and on the device's "XLA Modules" event, and a program can
neither start on the device before its enqueue began nor be seen
complete by the host before it ended on the device.
"""
from __future__ import annotations

from chipbench import trace as tr


def _clip(iv, window):
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def _overlap_ns(a, b):
    """Measure of the intersection of two merged, sorted interval lists."""
    tot, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        tot += max(0, e - s)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def idle_in_spans_s(red, names):
    """Seconds per device in which the device ran nothing while a host
    span named in `names` was open, inside the window; None without a
    device plane or without such a span in the window."""
    if not red.ops:
        return None
    spans = tr.merge(_clip([(s, e) for s, e, n in red.spans if n in names],
                           red.window))
    if not spans:
        return None
    width = tr.union_ns(spans)
    idle = sum(width - _overlap_ns(spans, tr.merge(
        _clip([(o.start, o.end) for o in red.ops[d]], red.window)))
        for d in red.devices())
    return idle * 1e-9 / len(red.ops)


def idle_in_span_s(red, name):
    """`idle_in_spans_s` for the spans of one name."""
    return idle_in_spans_s(red, (name,))


def device_lead_ns(path):
    """(lo, hi): bounds, in ns, on how far the device's timestamps in the
    trace at `path` run ahead of the host's.  lo is the largest lead of a
    program's device start over its host enqueue's start; hi the
    smallest gap from its device end to the host's completion callback.
    One clock reads lo <= 0 <= hi; None where no program pairs up."""
    from jax.profiler import ProfileData
    enq, done, dev = {}, {}, {}
    for plane in ProfileData.from_file(path).planes:
        on_device = tr.DEVICE_RE.match(plane.name)
        for line in plane.lines:
            for ev in line.events:
                if on_device and line.name == "XLA Modules":
                    table = dev
                elif ev.name == "DoEnqueueProgram":
                    table = enq
                elif ev.name == "CompleteCallbacks":
                    table = done
                else:
                    continue
                run = dict(ev.stats).get("run_id")
                if run is not None:
                    s = int(ev.start_ns)
                    table[int(run)] = (s, s + int(ev.duration_ns))
    runs = [r for r in dev if r in enq]
    if not runs:
        return None
    lo = max(enq[r][0] - dev[r][0] for r in runs)
    hi = min((done[r][0] - dev[r][1] for r in runs if r in done),
             default=None)
    return lo, hi
