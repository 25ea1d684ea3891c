from benchmark.harness import window


def test_roi_grid_is_z_major_and_clipped():
    rois = window.roi_grid((5, 10, 10), (2, 4, 4), (1, 2, 1))
    assert rois[0] == ((0, 0, 0), (2, 8, 4))
    assert rois[1] == ((0, 0, 4), (2, 8, 8))
    assert rois[2] == ((0, 0, 8), (2, 8, 10))
    assert rois[3] == ((0, 8, 0), (2, 10, 4))
    assert rois[-1] == ((4, 8, 8), (5, 10, 10))
    assert len(rois) == 3 * 2 * 3
    assert sum(window.voxels(r) for r in rois) == 5 * 10 * 10


def fake_clock(durations):
    """A clock that advances by the next duration on every job end."""
    state = {"t": 100.0, "i": 0}

    def clock():
        return state["t"]

    def run_job(index, roi):
        state["t"] += durations[state["i"]]
        state["i"] += 1
        return True

    return clock, run_job


def test_window_closes_at_first_job_ending_at_or_after_seconds():
    clock, run_job = fake_clock([4.0, 4.0, 2.0, 9.0, 9.0])
    seq = window.roi_grid((10, 4, 4), (1, 4, 4), (1, 1, 1))
    records = window.run(seq, 10.0, run_job, clock)
    # ends at 4, 8, 10: the third job ends exactly at 10 s and closes it
    assert len(records) == 3
    assert window.window_seconds(records) == 10.0
    clock, run_job = fake_clock([4.0, 4.0, 1.0, 9.0, 9.0])
    records = window.run(seq, 10.0, run_job, clock)
    assert [r["index"] for r in records] == [0, 1, 2, 3]
    assert window.window_seconds(records) == 18.0


def test_rate_is_over_the_whole_window():
    clock, run_job = fake_clock([1.0, 3.0])
    seq = [((0, 0, 0), (10, 100, 100)), ((0, 0, 0), (10, 100, 50))]
    records = window.run(seq, 2.0, run_job, clock)
    assert window.mvox_per_s(records) == (1e5 + 5e4) / 4.0 / 1e6


def test_an_roi_is_never_used_twice():
    clock, run_job = fake_clock([1.0] * 10)
    seq = window.roi_grid((2, 4, 4), (1, 4, 4), (1, 1, 1))
    try:
        window.run(seq, 5.0, run_job, clock)
    except RuntimeError as e:
        assert "twice" in str(e)
    else:
        raise AssertionError("the window reused an ROI")
