"""The readers of the collective components cell
(``cremi_ap_b50x512.cc_sharded``) on made-up traces, spans and counters,
and the cell's traffic run end to end on the CPU."""

import json
import os

import pytest
from test_scopes import _xplane, scoped_context

from benchmark.harness import cell as cell_mod
from benchmark.harness import peaks, scopes, xtrace

PROGRAM = "jit__sharded_cc(9)"
PATH = "jit(_sharded_cc)/shard_map/"
PHASES = ("local", "exchange", "resolve", "unscoped")


def read(name, ctx):
    return cell_mod.load_module("metrics", name).read(ctx)


def two_chips():
    """Two chips, each running the program once per job of
    ``scoped_context`` (at 0 and 200): a ``while`` of ``scc.local`` with
    ``cc.tiles`` nested, the exchange, the resolve and an unscoped copy."""
    def chip(k):
        return scopes.DeviceOps(
            modules=[(PROGRAM, 0.0, 100.0), (PROGRAM, 200.0, 100.0)],
            ops=[(0.0, 40.0 + k, ""),  # the compiler names no while
                 (1.0, 30.0, PATH + "scc.local/cc.tiles/fusion:"),
                 (50.0, 5.0, PATH + "scc.exchange/all-gather:"),
                 (60.0, 20.0, PATH + "scc.resolve/while:"),
                 (90.0, 2.0, "copy:"),
                 (200.0, 40.0, PATH + "scc.local/cc.merge/x:"),
                 (250.0, 5.0, PATH + "scc.exchange/collective-permute:"),
                 (260.0, 20.0, PATH + "scc.resolve/fusion:")])
    return {0: chip(0), 1: chip(1)}


def test_phases_sum_over_the_chips(tmp_path):
    ctx = scoped_context(tmp_path, two_chips())
    want = {"local": 40 + 41 + 40 + 40, "exchange": 4 * 5,
            "resolve": 4 * 20, "unscoped": 2 * 2}
    for phase, ns in want.items():  # ns over the context's 0.2 Mvox
        assert read(f"sharded_cc.{phase}_ms_per_mvox", ctx) == \
            pytest.approx(ns * 1e-6 / 0.2), phase


def test_phases_silent_without_scopes_or_trace(tmp_path):
    bare = {0: scopes.DeviceOps(modules=[(PROGRAM, 0.0, 100.0)],
                                ops=[(0.0, 50.0, PATH + "while:")])}
    ctx = scoped_context(tmp_path, bare)  # the parent's program
    for phase in PHASES:
        assert read(f"sharded_cc.{phase}_ms_per_mvox", ctx) is None
    ctx.trace = None
    for phase in PHASES:
        assert read(f"sharded_cc.{phase}_ms_per_mvox", ctx) is None


def roofline_context(modules):
    cell = cell_mod.Cell(name="x.cc_sharded", chips=4, config={
        "block_shape": [50, 512, 512], "volume_shape": [200, 1536, 1536]},
        traffic={})
    ctx = cell_mod.Context(cell)
    ctx.jobs = [{"index": i, "t0": 0.0, "t1": 1.0, "begin": (0, 0, 512 * i),
                 "end": (200, 512, 512 * (i + 1))} for i in range(2)]
    ctx.peaks = peaks.peaks("TPU v5 lite")
    ctx.trace = xtrace.DeviceTrace(window=(0.0, 1e12), modules=modules)
    return ctx


def test_roofline_over_the_time_averaged_over_chips():
    mod = cell_mod.load_module("metrics", "sharded_cc_program_roofline")
    plane = 512 * 512
    assert mod.shard_bytes((200, 512, 512), 4) == 50 * plane * 5 + plane * 5
    assert mod.shard_bytes((10, 512, 512), 4) == 3 * plane * 5 + plane * 5
    runs = {d: [(PROGRAM, 0.0, (1 + d) * 1e9), (PROGRAM, 5e9, 1e9)]
            for d in range(4)}
    ctx = roofline_context(runs)
    seconds = sum((1 + d) + 1 for d in range(4)) / 4
    least = 2 * mod.shard_bytes((200, 512, 512), 4) / 819e9
    assert read("sharded_cc_program_roofline", ctx) == pytest.approx(
        100 * least / seconds)
    ctx.trace = None
    assert read("sharded_cc_program_roofline", ctx) is None


@pytest.mark.parametrize("modules", [
    {d: [] for d in range(4)},  # the program did not run, or was renamed
    {d: [(PROGRAM, 0.0, 1e9)] for d in range(4)},  # one job missed
    {d: [(PROGRAM, 0.0, 1e9)] * 3 for d in range(4)},  # another of its name
])
def test_roofline_program_runs_once_per_job_and_chip(modules):
    with pytest.raises(ValueError, match="jobs on 4 chips"):
        read("sharded_cc_program_roofline", roofline_context(modules))


def test_host_share_and_rounds_per_shard():
    ctx = roofline_context({})
    ctx.jobs = [{"t0": 0.0, "t1": 10.0}, {"t0": 10.0, "t1": 20.0}]
    ctx.spans = [
        {"name": "scc:ingest", "kind": "host_io", "t0": 1.0, "t1": 3.0},
        {"name": "scc:program", "kind": "collective", "t0": 3.0, "t1": 6.0},
        {"name": "scc:relabel", "kind": "host", "t0": 6.0, "t1": 7.0},
        {"name": "scc:write", "kind": "host_io", "t0": 7.0, "t1": 9.0},
        {"name": "scc:write", "kind": "host_io", "t0": 12.0, "t1": 15.0},
        {"name": "scc:ingest", "kind": "host_io", "t0": -3.0, "t1": -1.0},
    ]
    assert read("sharded_cc.host_share", ctx) == pytest.approx(
        100 * (2 + 1 + 2 + 3) / 20)
    ctx.counters = {"scc.shards": 8.0, "scc.local_rounds": 60.0}
    assert read("sharded_cc.rounds_per_shard", ctx) == pytest.approx(7.5)
    ctx.spans, ctx.counters = [], {"store.bytes_read": 1.0}
    assert read("sharded_cc.host_share", ctx) is None
    assert read("sharded_cc.rounds_per_shard", ctx) is None


def test_traced_rehearsal_of_the_collective_traffic(rehearsal, capsys,
                                                    monkeypatch, tmp_path):
    """The cell's traffic on the tiny configuration, traced, on the CPU's
    one device: every job labels its ROI alone (``correct``), and the
    program counters and spans read; the device readers find no TPU plane
    there and stay silent."""
    import jax
    from conftest import DATA, last_json

    jax.clear_caches()
    with open(os.path.join(DATA, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"] = [{"name": "tiny.cc_sharded", "config": "tiny",
                           "traffic": "cc_sharded", "chips": 1,
                           "why": "test"}]
    with open(os.path.join(cell_mod.ROOT, "BENCHMARK.json")) as f:
        ours = [m for m in json.load(f)["per_layer"]
                if m["name"].startswith("sharded_cc")]
    assert len(ours) == 7
    for m in ours:
        m["workloads"] = ["tiny.cc_sharded"]
    bench["per_layer"] += ours
    bench_file = tmp_path / "BENCHMARK.json"
    bench_file.write_text(json.dumps(bench))
    monkeypatch.setattr(rehearsal, "BENCH_FILE", str(bench_file))
    assert rehearsal.main(["--workload", "tiny.cc_sharded", "--seed",
                           "2147483901", "--seconds", "0.05",
                           "--trace", "1"]) == 0
    out = capsys.readouterr()
    result = last_json(out.out)
    assert result["correct"] is True, result
    assert result["checks"]["cc_mismatch_share"]["value"] == 0.0
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert got["sharded_cc.rounds_per_shard"] > 0
    assert 0 < got["sharded_cc.host_share"] < 100
    assert got["store.read_amp"] == pytest.approx(1.0, rel=1e-3)  # headers
    assert not [k for k in got if k.endswith("ms_per_mvox")]
    assert "roi ((0, 0, 0), (24, 48, 48))" in out.err  # one ROI, z whole


def test_guard_refuses_a_program_that_labels_the_whole_volume(
        rehearsal, capsys, monkeypatch, tmp_path):
    """The cell on a collective task that ignores the ROI (labels the
    whole volume under one, as the task did before it honoured the ROI):
    the probe finds labels outside its ROI, the warm-up job fails and the
    run exits 1 without a result, before any job of the window."""
    import jax

    from benchmark.harness import roi_workflow
    from cluster_tools_tpu.tasks.thresholded_components import \
        ShardedComponentsTask
    from conftest import DATA

    jax.clear_caches()
    monkeypatch.setattr(roi_workflow, "_VERDICT", None)
    monkeypatch.setattr(ShardedComponentsTask, "_roi", staticmethod(
        lambda conf, shape: ((0,) * len(shape), tuple(shape))))
    with open(os.path.join(DATA, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"] = [{"name": "tiny.cc_sharded", "config": "tiny",
                           "traffic": "cc_sharded", "chips": 1,
                           "why": "test"}]
    bench_file = tmp_path / "BENCHMARK.json"
    bench_file.write_text(json.dumps(bench))
    monkeypatch.setattr(rehearsal, "BENCH_FILE", str(bench_file))
    assert rehearsal.main(["--workload", "tiny.cc_sharded", "--seed",
                           "2147483901", "--seconds", "0.05",
                           "--trace", "0"]) == 1
    out = capsys.readouterr()
    assert out.out.strip() == ""
    assert "labels the whole volume, not the job's ROI" in out.err
    assert "cannot run: the warm-up job failed" in out.err
    assert "window:" not in out.err


def test_guard_passes_the_program_and_probes_once(monkeypatch, tmp_path):
    """The program's collective task passes the probe; the verdict is
    kept for the process, so later jobs build without probing again."""
    from benchmark.harness import roi_workflow
    from cluster_tools_tpu.runtime import config as cfg

    calls = []
    real = roi_workflow.probe_roi
    monkeypatch.setattr(roi_workflow, "_VERDICT", None)
    monkeypatch.setattr(roi_workflow, "probe_roi",
                        lambda *a: calls.append(a) or real(*a))
    config_dir = str(tmp_path / "configs")
    cfg.write_global_config(config_dir, {"target": "tpu"})
    for job in range(2):
        wf = roi_workflow.ThresholdedComponentsWorkflow(
            str(tmp_path / f"job{job}" / "tmp"), config_dir,
            input_path="x.n5", input_key="raw", output_path="x.n5",
            output_key="cc", sharded=True)
        assert len(wf.requires()) == 1
    assert len(calls) == 1 and roi_workflow._VERDICT == ""
