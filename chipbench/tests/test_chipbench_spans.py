"""Device idle time laid on the program's host spans (`spans.py`), the
span names the program and the benchmark share, and a recorded v5e
trace."""
import collections
import gzip
import os
import re

import pytest
from pytest import approx

from chipbench import harness, spans, trace as tr


def reduction():
    # window 0..100 ns.  device 0 busy 10-40 (two overlapping ops) and
    # 50-60; device 1 busy 0-50.  draw_batch starts before the window,
    # the two dispatch spans overlap, block ends after the window.
    ops = {0: [tr.Op(10, 30, "fusion.1", ""), tr.Op(20, 40, "fusion.2", ""),
               tr.Op(50, 60, "rloo_combine", "")],
           1: [tr.Op(0, 50, "while.4", "")]}
    sp = [(0, 100, "window"), (-20, 10, "draw_batch"), (5, 25, "dispatch"),
          (15, 45, "dispatch"), (60, 120, "block")]
    return tr.Reduction(window=(0, 100), ops=ops, spans=sp)


def test_idle_in_each_span_is_clipped_merged_and_averaged():
    r = reduction()
    # device 0 idle 0-10, 40-50, 60-100; device 1 idle 50-100
    assert spans.idle_in_span_s(r, "draw_batch") == approx((10 + 0) / 2e9)
    assert spans.idle_in_span_s(r, "dispatch") == approx((5 + 5) / 2e9)
    assert spans.idle_in_span_s(r, "block") == approx((40 + 40) / 2e9)
    assert spans.idle_in_spans_s(r, tr.HOST_SPANS) == approx(
        (55 + 40) / 2e9)


def test_idle_outside_the_spans_completes_the_idle_share():
    r = reduction()
    idle = r.window_s - r.busy_s()
    outside = idle - spans.idle_in_spans_s(r, tr.HOST_SPANS)
    assert outside == approx((5 + 10) / 2e9)   # device 0 45-50, 1 50-60


def test_busy_span_reads_zero_and_missing_span_or_device_reads_none():
    r = reduction()
    r.ops = {0: [tr.Op(0, 100, "while.4", "")]}
    assert spans.idle_in_span_s(r, "block") == 0.0
    assert spans.idle_in_span_s(r, "collect") is None
    r.spans = [(0, 100, "window"), (100, 120, "block")]
    assert spans.idle_in_span_s(r, "block") is None   # outside the window
    r.ops = {}
    r.spans = reduction().spans
    assert spans.idle_in_span_s(r, "block") is None   # no device plane


def test_program_writes_the_spans_the_benchmark_reads():
    from repro import track
    assert track.HOST_SPANS == tr.HOST_SPANS
    assert (track.DRAW_BATCH, track.DISPATCH, track.BLOCK) == tr.HOST_SPANS


# ---- a recorded v5e trace: one `run_rounds(10)` call of paper-lenet5-c10
# (`run.py --workload paper-lenet5-c10 --seed 1 --seconds 0.01 --trace 1
# --keep-trace <dir>` on a TPU v5 lite), gzipped

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "paper-lenet5-c10.one-call.xplane.pb.gz")
ROUNDS = 10


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "recorded.xplane.pb"
    with gzip.open(RECORDED) as f:
        path.write_bytes(f.read())
    return str(path)


def span_ids(path):
    """{name: ids} of the program's host spans in the trace."""
    from jax.profiler import ProfileData
    return {ev.name: dict(ev.stats)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name in tr.HOST_SPANS}


def scan_op(red):
    """The call's rolled scan: the one `while` op on the device."""
    op, = [o for o in red.ops[0] if o.name.startswith("%while")]
    return op


def test_recorded_trace_holds_the_program_spans(recorded):
    red = tr.reduce_file(recorded)
    assert [n for _, _, n in sorted(red.spans)] == ["window",
                                                    *tr.HOST_SPANS]
    assert span_ids(recorded) == {"draw_batch": {"round": 30},
                                  "dispatch": {"round": 30, "rounds": 10},
                                  "block": {"round": 30, "arrays": 2}}


def test_recorded_trace_device_clock_leads_the_host(recorded):
    """In this v5e trace the device's timestamps run 1.54-1.79 ms ahead
    of the host's: the scan seems to start before its `dispatch` span.
    Moved by the lead the programs' run_ids give, it starts after that
    span starts and ends before its `block` span ends."""
    red = tr.reduce_file(recorded)
    lo, hi = spans.device_lead_ns(recorded)
    assert 1.4e6 < lo <= hi < 2.0e6
    (d0, _), (_, b1) = [(s, e) for s, e, n in red.spans
                        if n in ("dispatch", "block")]
    op = scan_op(red)
    assert op.start < d0
    for lead in (lo, hi):
        assert d0 <= op.start + lead and op.end + lead <= b1


def test_recorded_idle_split_adds_up_to_the_idle_share(recorded):
    red = tr.reduce_file(recorded)
    reading = harness.Reading(run=None, red=red, steps=1, flops_per_step=0,
                              chips=1, peak={}, rounds_per_step=ROUNDS)
    per_span = [spans.idle_in_span_s(red, n) for n in tr.HOST_SPANS]
    host = tr.merge([(s, e) for s, e, n in red.spans if n in tr.HOST_SPANS])
    busy = tr.merge([(o.start, o.end) for o in red.ops[0]])
    outside, t = 0, red.window[0]
    for s, e in busy + [(red.window[1], red.window[1])]:
        if s > t:     # an idle gap t..s, less the host spans inside it
            outside += (s - t) - sum(max(0, min(s, he) - max(t, hs))
                                     for hs, he in host)
        t = max(t, e)
    ms = [1e3 * v / ROUNDS for v in per_span + [outside * 1e-9]]
    want = reading.idle_share() / 100 * red.window_s * 1e3 / ROUNDS
    assert sum(ms) == approx(want, rel=1e-6)
    assert ms[-1] < 0.1 * want


def test_recorded_kernels_are_found_by_exact_name(recorded):
    red = tr.reduce_file(recorded)
    hlo = collections.Counter(re.match(r"%([\w-]+?)(\.\d+)? = ", o.name)[1]
                              for o in red.ops[0])
    # cohort 10 x 2 local epochs of the client pass, one aggregation a
    # round, over 10 rounds
    assert hlo["rloo_combine"] == 2 * ROUNDS
    assert hlo["ncv_weighted_sum"] == ROUNDS
