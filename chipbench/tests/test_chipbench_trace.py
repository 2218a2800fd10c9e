"""The trace reduction: unions of device-op intervals, phase and kernel
times, idle gaps by host span."""
from pytest import approx

from chipbench import trace as tr


def reduction():
    # window 0..100 ns; device 0 busy 10-40 (two overlapping ops), 50-60;
    # device 1 busy 0-100; host spans cover the gaps
    ops = {0: [tr.Op(10, 30, "fusion.1", "jit(round)/client_pass/dot"),
               tr.Op(20, 40, "all-reduce.2", "jit(round)/aggregate/psum"),
               tr.Op(50, 60, "_rloo_kernel", "jit(round)/client_pass")],
           1: [tr.Op(0, 100, "fusion.1", "jit(round)/client_pass/dot")]}
    spans = [(0, 100, "window"), (0, 10, "draw_batch"), (40, 50, "dispatch"),
             (60, 100, "block")]
    return tr.Reduction(window=(0, 100), ops=ops, spans=spans)


def test_union_counts_overlap_once():
    r = reduction()
    assert r.window_s == approx(100e-9)
    assert r.time_s(lambda o: True, device=0) == approx(40e-9)
    assert r.busy_s() == approx((40 + 100) * 1e-9 / 2)


def test_scopes_collectives_and_kernels():
    r = reduction()
    assert r.scope_s("client_pass") == approx((30 + 100) * 1e-9 / 2)
    assert r.scope_s("aggregate") == approx(20e-9 / 2)
    assert r.collective_s() == approx(20e-9 / 2)
    assert r.kernel_s("rloo") == approx(10e-9 / 2)


def test_idle_gaps_are_named_by_host_span():
    gaps = reduction().idle_gaps()
    assert [g[0] for g in gaps] == ["block", "draw_batch", "dispatch"]
    assert [g[1] for g in gaps] == approx([40e-9, 10e-9, 10e-9])


def test_top_ops_per_device():
    top = reduction().top_ops(2)
    assert top[0][0] == "fusion.1"
    assert top[0][1] == approx((20 + 100) * 1e-9 / 2)
