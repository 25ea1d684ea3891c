"""Each per-layer reader's arithmetic on made-up readings."""

import pytest

from benchmark.harness import cell as cell_mod
from benchmark.harness import peaks, xtrace


def context():
    cell = cell_mod.Cell(
        name="x.ws", chips=1,
        config={"block_shape": [10, 100, 100], "volume_shape": [20, 200, 200]},
        traffic={"task_configs": {"watershed": {"halo": [0, 0, 0]}}},
    )
    ctx = cell_mod.Context(cell)
    ctx.jobs = [
        {"index": 0, "t0": 0.0, "t1": 10.0, "begin": (0, 0, 0),
         "end": (10, 100, 100)},
        {"index": 1, "t0": 10.0, "t1": 20.0, "begin": (0, 0, 100),
         "end": (10, 100, 200)},
    ]
    ctx.spans = [
        {"name": "build", "kind": "run", "t0": 0.5, "t1": 9.5},
        {"name": "watershed", "kind": "task", "t0": 1.0, "t1": 8.0},
        {"name": "stage_compute", "kind": "device", "t0": 2.0, "t1": 7.0},
        {"name": "watershed", "kind": "task", "t0": 11.0, "t1": 15.0},
        {"name": "write", "kind": "task", "t0": 14.0, "t1": 19.0},
        {"name": "warmup", "kind": "task", "t0": -5.0, "t1": -1.0},
    ]
    ctx.counters = {"store.bytes_read": 3.0 * 4 * 2e5}
    ctx.peaks = peaks.peaks("TPU v5 lite")
    ctx.trace = xtrace.DeviceTrace(
        window=(0.0, 20e9),
        modules={0: [("jit__lambda(3)", 1e9, 6e9), ("jit__lambda(3)", 11e9, 4e9),
                     ("jit__components_batch(4)", 16e9, 1e9)]},
        ops={0: [("fusion", 1e9, 6e9), ("while", 11e9, 4e9),
                 ("fusion", 16e9, 1e9)]},
    )
    return ctx


def read(name, ctx):
    return cell_mod.load_module("metrics", name).read(ctx)


def test_workflow_overhead_share():
    # job 0: 10 s, tasks cover 7 s; job 1: tasks cover 11..19 = 8 s
    assert read("workflow.overhead_share", context()) == pytest.approx(
        100 * (3 + 2) / 20)


def test_store_read_amp():
    assert read("store.read_amp", context()) == pytest.approx(3.0)
    ctx = context()
    ctx.counters = {}
    assert read("store.read_amp", ctx) is None


def test_device_idle_share():
    assert read("device.idle_share", context()) == pytest.approx(
        100 * (1 - 11 / 20))
    ctx = context()
    ctx.trace = None
    assert read("device.idle_share", ctx) is None


def test_rooflines_from_least_bytes():
    ctx = context()
    least = 2 * (4 + 4) * 1e5 / 819e9  # two blocks, f32 in, int32 out
    assert read("ws_program_roofline", ctx) == pytest.approx(
        100 * least / 10.0)
    ctx.trace.modules[0].append(("jit__components_batch(4)", 18e9, 1e9))
    assert read("cc_program_roofline", ctx) == pytest.approx(
        100 * least / 2.0)
    ctx.trace = None
    assert read("ws_program_roofline", ctx) is None


@pytest.mark.parametrize("modules", [
    [],  # the program did not run, or runs under another name
    [("jit__lambda_1(3)", 1e9, 6e9), ("jit__lambda_1(3)", 11e9, 4e9)],
    [("jit__lambda(3)", 1e9, 6e9)],  # one block missed
    [("jit__lambda(3)", 1e9, 6e9), ("jit__lambda(3)", 11e9, 4e9),
     ("jit__lambda(5)", 16e9, 1e9)],  # another program of the same name
])
def test_roofline_program_runs_once_per_block(modules):
    ctx = context()
    ctx.trace.modules = {0: modules}
    with pytest.raises(ValueError, match="blocks"):
        read("ws_program_roofline", ctx)


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        peaks.peaks("TPU v99")


def test_host_graph_solve_share():
    ctx = context()
    ctx.spans += [
        {"name": "initial_sub_graphs", "kind": "task", "t0": 1.0, "t1": 3.0},
        {"name": "solve_subproblems_s0", "kind": "task", "t0": 12.0,
         "t1": 13.0},
        {"name": "solve_global", "kind": "task", "t0": 12.5, "t1": 14.0},
    ]
    assert read("host.graph_solve_share", ctx) == pytest.approx(
        100 * (2 + 2) / 20)
    assert read("host.graph_solve_share", context()) is None


def test_executor_exposed_io_share():
    ctx = context()
    assert read("executor.exposed_io_share", ctx) is None  # no dispatch
    ctx.spans[2]["attrs"] = {"task": "watershed"}
    ctx.spans.append({"name": "dispatch", "kind": "dispatch", "t0": 1.5,
                      "t1": 9.5, "attrs": {"task": "watershed"}})
    ctx.spans.append({"name": "dispatch", "kind": "dispatch", "t0": 14.0,
                      "t1": 18.0, "attrs": {"task": "write"}})
    # compute 5 s of the watershed task's 8 s dispatch wall
    assert read("executor.exposed_io_share", ctx) == pytest.approx(
        100 * 3 / 8)
