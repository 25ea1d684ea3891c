import os

import pytest

from benchmark.harness import xtrace

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "small.xplane.pb")


def made_up():
    """Two devices over a 100 ns window; host spans nest."""
    return xtrace.DeviceTrace(
        window=(0.0, 100.0),
        modules={0: [("jit_one(1)", 10.0, 30.0), ("jit_two(2)", 60.0, 20.0)],
                 1: [("jit_one(1)", 0.0, 50.0)]},
        ops={0: [("fusion.1", 10.0, 10.0), ("fusion.2", 15.0, 15.0),
                 ("while.3", 60.0, 20.0), ("fusion.1", 95.0, 10.0)],
             1: [("fusion.1", 0.0, 50.0)]},
        host=[("job", 0.0, 100.0), ("stage_write", 40.0, 15.0)],
    )


def test_union_and_busy_share():
    assert xtrace.union([(5, 8), (0, 2), (1, 3), (8, 9)]) == [(0, 3), (5, 9)]
    tr = made_up()
    # device 0: [10, 30) + [60, 80) + [95, 100) = 45; device 1: 50
    assert xtrace.busy_intervals(tr, 0) == [(10, 30), (60, 80), (95, 100)]
    assert xtrace.busy_ns(tr) == pytest.approx((45 + 50) / 2)
    assert xtrace.window_ns(tr) == 100.0
    assert xtrace.idle_gaps(tr, 0) == [(0, 10), (30, 60), (80, 95)]


def test_program_time_and_top_ops():
    tr = made_up()
    assert xtrace.program_time(tr, ("jit_one",)) == (2, 80.0)
    assert xtrace.program_time(tr, ("jit_two", "jit_one")) == (3, 100.0)
    assert xtrace.program_time(tr, ("jit_three",)) == (0, 0.0)
    ops = dict(xtrace.top_ops(tr))
    assert ops["fusion.1"] == pytest.approx((10 + 10 + 50) / 2 / 1e9)
    assert list(dict(xtrace.top_ops(tr, 1))) == ["fusion.1"]


def test_idle_gaps_go_to_the_innermost_host_span():
    tr = made_up()
    gaps = dict(xtrace.attribute_gaps(tr))
    # gaps 0-10, 30-60, 80-95; stage_write covers 40-55 of them
    assert gaps["stage_write"] == pytest.approx(15 / 1e9)
    assert gaps["job"] == pytest.approx((10 + 15 + 15) / 1e9)
    tr.host = []
    assert dict(xtrace.attribute_gaps(tr)) == {"no host span":
                                               pytest.approx(55 / 1e9)}


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace")
def test_recorded_trace_reduces():
    """A trace recorded on a v5e by ``record_trace.py``: three job
    annotations around two programs each, one sync marker.  Its device
    timeline runs about 1.4 ms ahead of the host's (each program starts on
    the device before the host's launch of it), so the window is the jobs'
    widened by 5 ms."""
    tr = xtrace.load(os.path.dirname(RECORDED))
    assert tr.devices == [0]
    window = xtrace.host_window(tr, "ctt_bench_job")
    assert window is not None
    tr.window = (window[0] - 5e6, window[1] + 5e6)
    n_a, ns_a = xtrace.program_time(tr, ("jit_small_a",))
    n_b, ns_b = xtrace.program_time(tr, ("jit_small_b",))
    assert (n_a, n_b) == (3, 3)
    busy = xtrace.busy_ns(tr)
    assert 0 < busy < xtrace.window_ns(tr)
    assert ns_a + ns_b <= xtrace.window_ns(tr)
    # the 10 ms sleeps between the programs are idle, inside a job
    gaps = dict(xtrace.attribute_gaps(tr))
    assert gaps["ctt_bench_job"] >= 0.03
    ops = dict(xtrace.top_ops(tr))
    assert ops and all(n.startswith(("jit_small_a/", "jit_small_b/"))
                       for n in ops)
