"""ctt-watch: incremental tailer, heartbeats, stragglers, OpenMetrics.

Covers the live-path contract:
  * cursor correctness across appends, including a torn trailing line
    (not consumed until the newline lands) and complete-but-corrupt lines
    (skipped + counted, never fatal — the watcher outlives bad records);
  * stale-heartbeat detection against a faked reader clock, and the
    ``exiting`` beat that distinguishes clean exit from death;
  * straggler flagging (in-flight block age vs k x median);
  * z-slab heatmap determinism (golden text);
  * OpenMetrics exposition validity (prometheus_client parser when
    importable, exposition-grammar regex fallback otherwise);
  * disabled-overhead smoke: no heartbeat thread / no files without
    ``CTT_TRACE_DIR``;
  * the ``watch`` CLI exit-code contract (0 progress / 1 none / 4 stall);
  * golden machine-readable output for ``summarize --json`` and
    ``diff --json`` (the bench/CI interface — satellite);
  * SIGTERM preemption flush (metrics + shards + final exiting beat).
"""

import json
import os
import re
import signal
import subprocess
import sys
import threading
import time

import pytest

from cluster_tools_tpu.obs import heartbeat, metrics, trace
from cluster_tools_tpu.obs.live import (
    LiveRun,
    format_heatmap,
    format_watch,
    render_openmetrics,
    resolve_live_dir,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WALL0, MONO0 = 1000.0, 10.0


def _obs_cli(*args):
    return subprocess.run(
        [sys.executable, "-m", "cluster_tools_tpu.obs", *args],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )


def _header(run_id="live", pid=1, tid=1, wall=WALL0, mono=MONO0):
    return json.dumps({
        "type": "header", "run": run_id, "pid": pid, "tid": tid,
        "host": "synth", "wall": wall, "mono": mono,
    })


def _block_span(sid, task, bid, t0, dur, name="block", kind="host",
                pid=1, tid=1, error=None, block_ids=None):
    attrs = {"task": task}
    if block_ids is not None:
        attrs["block_ids"] = block_ids
    else:
        attrs["block"] = bid
    if error:
        attrs["error"] = error
    return json.dumps({
        "type": "span", "id": sid, "parent": None, "name": name,
        "kind": kind, "t0": t0, "t1": t0 + dur, "pid": pid, "tid": tid,
        "attrs": attrs,
    })


def _task_span(sid, name, t0, dur, pid=1, tid=1):
    return json.dumps({
        "type": "span", "id": sid, "parent": None, "name": name,
        "kind": "task", "t0": t0, "t1": t0 + dur, "pid": pid, "tid": tid,
    })


def _write_hb(run_dir, pid, wall, mono=500.0, interval=1.0, exiting=False,
              task=None, total=0, done=0, failed=0, current=(),
              role="worker", job_id=None, grid=None, mem=None):
    os.makedirs(run_dir, exist_ok=True)
    with open(os.path.join(run_dir, f"hb.p{pid}.json"), "w") as f:
        json.dump({
            "pid": pid, "host": "synth", "role": role, "job_id": job_id,
            "run": "live", "wall": wall, "mono": mono,
            "interval_s": interval, "seq": 1, "exiting": exiting,
            "task": task, "blocks_total": total, "blocks_done": done,
            "blocks_failed": failed, "blocks_retried": 0, "grid": grid,
            "current_blocks": [
                {"id": b, "start_mono": m} for b, m in current
            ],
            "device_mem_peak_bytes": mem,
        }, f)


# --------------------------------------------------------------------------
# incremental cursors


class TestIncrementalCursor:
    def test_appends_accumulate_across_polls(self, tmp_path):
        run = str(tmp_path / "r")
        os.makedirs(run)
        shard = os.path.join(run, "spans.p1.t1.jsonl")
        with open(shard, "w") as f:
            f.write(_header() + "\n")
            f.write(_block_span(1, "t", 0, 11.0, 1.0) + "\n")
            f.write(_block_span(2, "t", 1, 12.0, 1.0) + "\n")
        live = LiveRun(run)
        snap = live.poll()
        assert snap["run_id"] == "live"
        assert snap["tasks"]["t"]["blocks_done"] == 2
        size_after_first = os.path.getsize(shard)

        with open(shard, "a") as f:
            f.write(_block_span(3, "t", 2, 13.0, 1.0) + "\n")
        snap = live.poll()
        assert snap["tasks"]["t"]["blocks_done"] == 3
        # the cursor moved past everything consumed
        assert live._offsets[shard] == os.path.getsize(shard)
        assert live._offsets[shard] > size_after_first

    def test_torn_trailing_line_not_consumed_until_complete(self, tmp_path):
        run = str(tmp_path / "r")
        os.makedirs(run)
        shard = os.path.join(run, "spans.p1.t1.jsonl")
        full_line = _block_span(2, "t", 1, 12.0, 1.0)
        with open(shard, "w") as f:
            f.write(_header() + "\n")
            f.write(_block_span(1, "t", 0, 11.0, 1.0) + "\n")
            f.write(full_line[:25])  # a writer mid-write
        live = LiveRun(run)
        snap = live.poll()
        assert snap["tasks"]["t"]["blocks_done"] == 1
        assert snap["malformed_lines"] == 0  # torn != malformed
        offset_before = live._offsets[shard]

        # the writer finishes the line: the SAME bytes now parse
        with open(shard, "a") as f:
            f.write(full_line[25:] + "\n")
        snap = live.poll()
        assert snap["tasks"]["t"]["blocks_done"] == 2
        assert snap["malformed_lines"] == 0
        assert live._offsets[shard] > offset_before

    def test_complete_garbage_line_skipped_not_fatal(self, tmp_path):
        run = str(tmp_path / "r")
        os.makedirs(run)
        shard = os.path.join(run, "spans.p1.t1.jsonl")
        with open(shard, "w") as f:
            f.write(_header() + "\n")
            f.write("this is not json\n")
            f.write(_block_span(1, "t", 0, 11.0, 1.0) + "\n")
        snap = LiveRun(run).poll()
        # the watcher keeps going where the post-mortem exporter raises
        assert snap["malformed_lines"] == 1
        assert snap["tasks"]["t"]["blocks_done"] == 1

    def test_batch_spans_attribute_per_block(self, tmp_path):
        run = str(tmp_path / "r")
        os.makedirs(run)
        with open(os.path.join(run, "spans.p1.t1.jsonl"), "w") as f:
            f.write(_header() + "\n")
            f.write(_block_span(
                1, "t", None, 11.0, 2.0, name="block_batch", kind="host",
                block_ids=[0, 1, 2, 3],
            ) + "\n")
        live = LiveRun(run)
        snap = live.poll()
        assert snap["tasks"]["t"]["blocks_done"] == 4
        hm = live.heatmap("t")
        # the 2 s batch wall splits evenly over its 4 blocks
        assert hm["durations"] == {0: 0.5, 1: 0.5, 2: 0.5, 3: 0.5}

    def test_progress_and_eta(self, tmp_path):
        run = str(tmp_path / "r")
        os.makedirs(run)
        with open(os.path.join(run, "spans.p1.t1.jsonl"), "w") as f:
            f.write(_header() + "\n")
            for i in range(4):  # 4 blocks, 1 block/s
                f.write(_block_span(i + 1, "t", i, 11.0 + i, 1.0) + "\n")
        _write_hb(run, pid=1, wall=WALL0 + 5, task="t", total=8, done=4,
                  role="driver")
        snap = LiveRun(run).poll()
        row = snap["tasks"]["t"]
        assert row["blocks_total"] == 8
        assert row["blocks_done"] == 4
        assert row["throughput_bps"] == pytest.approx(1.0)
        assert row["eta_s"] == pytest.approx(4.0)
        assert snap["progress"] is True


# --------------------------------------------------------------------------
# heartbeat staleness + stragglers (faked reader clock)


class TestStaleAndStragglers:
    def test_stale_heartbeat_flags_suspected_dead(self, tmp_path, monkeypatch):
        run = str(tmp_path / "r")
        now = 2000.0
        monkeypatch.setattr("cluster_tools_tpu.obs.live._now_wall",
                            lambda: now)
        _write_hb(run, pid=7, wall=now - 10.0, interval=1.0, task="t",
                  job_id=2)
        snap = LiveRun(run).poll()
        assert snap["n_stale"] == 1
        (w,) = snap["stale_workers"]
        assert (w["pid"], w["job_id"]) == (7, 2)
        assert "STALE" in format_watch(snap)

    def test_fresh_and_exiting_heartbeats_are_not_stale(
        self, tmp_path, monkeypatch
    ):
        run = str(tmp_path / "r")
        now = 2000.0
        monkeypatch.setattr("cluster_tools_tpu.obs.live._now_wall",
                            lambda: now)
        _write_hb(run, pid=1, wall=now - 0.5, interval=1.0, task="t")
        # a clean exit beats `exiting` and then ages forever — never stale
        _write_hb(run, pid=2, wall=now - 500.0, interval=1.0, exiting=True)
        snap = LiveRun(run).poll()
        assert snap["n_stale"] == 0

    def test_stale_threshold_scales_with_promised_interval(
        self, tmp_path, monkeypatch
    ):
        run = str(tmp_path / "r")
        now = 2000.0
        monkeypatch.setattr("cluster_tools_tpu.obs.live._now_wall",
                            lambda: now)
        # 10 s old but the writer promised a 60 s cadence: healthy
        _write_hb(run, pid=1, wall=now - 10.0, interval=60.0, task="t")
        assert LiveRun(run).poll()["n_stale"] == 0

    def test_straggler_in_flight_beyond_k_median(self, tmp_path, monkeypatch):
        run = str(tmp_path / "r")
        os.makedirs(run)
        now = 2000.0
        monkeypatch.setattr("cluster_tools_tpu.obs.live._now_wall",
                            lambda: now)
        with open(os.path.join(run, "spans.p1.t1.jsonl"), "w") as f:
            f.write(_header() + "\n")
            for i in range(5):  # median completed duration = 1.0 s
                f.write(_block_span(i + 1, "t", i, 11.0 + i, 1.0) + "\n")
        # fresh heartbeat, but block 9 has been in flight 10 s > 4 x 1 s
        _write_hb(run, pid=3, wall=now, mono=500.0, interval=1.0, task="t",
                  total=8, done=5, current=[(9, 490.0)])
        snap = LiveRun(run).poll()
        (s,) = snap["stragglers"]
        assert (s["block"], s["pid"]) == (9, 3)
        assert s["in_flight_s"] == pytest.approx(10.0)
        assert s["median_s"] == pytest.approx(1.0)
        assert snap["tasks"]["t"]["stragglers"] == [s]
        # a straggler is NOT a stall: the worker still heartbeats
        assert snap["n_stale"] == 0
        assert "straggler" in format_watch(snap)

    def test_straggler_k_is_configurable(self, tmp_path, monkeypatch):
        run = str(tmp_path / "r")
        os.makedirs(run)
        now = 2000.0
        monkeypatch.setattr("cluster_tools_tpu.obs.live._now_wall",
                            lambda: now)
        with open(os.path.join(run, "spans.p1.t1.jsonl"), "w") as f:
            f.write(_header() + "\n")
            f.write(_block_span(1, "t", 0, 11.0, 1.0) + "\n")
        _write_hb(run, pid=3, wall=now, mono=500.0, interval=1.0, task="t",
                  current=[(9, 497.0)])  # 3 s in flight
        assert LiveRun(run, straggler_k=4.0).poll()["stragglers"] == []
        assert len(LiveRun(run, straggler_k=2.0).poll()["stragglers"]) == 1


# --------------------------------------------------------------------------
# heatmap


class TestHeatmap:
    def _run_with_grid(self, tmp_path, durs, grid=(2, 2, 2)):
        run = str(tmp_path / "r")
        os.makedirs(run)
        with open(os.path.join(run, "spans.p1.t1.jsonl"), "w") as f:
            f.write(_header() + "\n")
            for i, (bid, dur) in enumerate(durs):
                f.write(_block_span(i + 1, "t", bid, 11.0, dur) + "\n")
        _write_hb(run, pid=1, wall=WALL0, task="t", grid=list(grid),
                  total=8, done=len(durs))
        return run

    def test_z_slab_golden_and_deterministic(self, tmp_path):
        durs = [(i, 1.0 + 0.1 * i) for i in range(8)]
        run = self._run_with_grid(tmp_path, durs)
        live = LiveRun(run)
        live.poll()
        text = format_heatmap(live.heatmap("t"))
        expected = "\n".join([
            "task t  block-duration heatmap  (8 blocks, 1.000s..1.700s, "
            "' '=fastest '@'=slowest '_'=pending)",
            "z-slab 0:",
            "   .",
            "  -=",
            "z-slab 1:",
            "  +*",
            "  %@",
        ])
        assert text == expected
        # determinism: a second reader over the same files agrees exactly
        live2 = LiveRun(run)
        live2.poll()
        assert format_heatmap(live2.heatmap("t")) == expected

    def test_pending_blocks_render_as_underscore(self, tmp_path):
        durs = [(i, 1.0 + 0.1 * i) for i in range(8) if i != 3]
        run = self._run_with_grid(tmp_path, durs)
        live = LiveRun(run)
        live.poll()
        text = format_heatmap(live.heatmap("t"))
        assert text.splitlines()[3] == "  -_"  # block 3 missing

    def test_no_grid_falls_back_to_strip(self, tmp_path):
        run = str(tmp_path / "r")
        os.makedirs(run)
        with open(os.path.join(run, "spans.p1.t1.jsonl"), "w") as f:
            f.write(_header() + "\n")
            f.write(_block_span(1, "t", 0, 11.0, 1.0) + "\n")
            f.write(_block_span(2, "t", 1, 12.0, 2.0) + "\n")
        live = LiveRun(run)
        live.poll()
        text = format_heatmap(live.heatmap())
        assert text.splitlines()[1] == " @"


# --------------------------------------------------------------------------
# OpenMetrics exposition

_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? -?[0-9.+eEinfa]+$"
)
_META_RE = re.compile(r"^# (TYPE [a-zA-Z_:][a-zA-Z0-9_:]* \w+|HELP .+|EOF)$")


def _assert_valid_exposition(text: str):
    try:
        from prometheus_client.openmetrics.parser import (
            text_string_to_metric_families,
        )
    except ImportError:
        # grammar fallback: every line is metadata or a valid sample, and
        # the exposition terminates with # EOF
        lines = text.splitlines()
        assert lines[-1] == "# EOF"
        for line in lines:
            assert _SAMPLE_RE.match(line) or _META_RE.match(line), line
        return None
    return list(text_string_to_metric_families(text))


class TestOpenMetrics:
    def test_exposition_parses_and_carries_series(self, tmp_path, monkeypatch):
        run = str(tmp_path / "r")
        os.makedirs(run)
        now = 2000.0
        monkeypatch.setattr("cluster_tools_tpu.obs.live._now_wall",
                            lambda: now)
        with open(os.path.join(run, "spans.p1.t1.jsonl"), "w") as f:
            f.write(_header() + "\n")
            f.write(_block_span(1, "t", 0, 11.0, 1.0) + "\n")
        with open(os.path.join(run, "metrics.p1.json"), "w") as f:
            json.dump({
                "counters": {"store.bytes_read": 10,
                             "faults.injected.store.write": 2},
                "gauges": {"compile_cache.entries_at_enable": 3,
                           "textual_gauge": "skipped"},
            }, f)
        _write_hb(run, pid=5, wall=now - 100.0, interval=1.0, task="t",
                  total=4, done=1, job_id=1, mem=4096)
        text = render_openmetrics(LiveRun(run).poll())
        assert text.endswith("# EOF\n")
        fams = _assert_valid_exposition(text)
        if fams is not None:
            by_name = {f.name: f for f in fams}
            assert by_name["ctt_store_bytes_read"].type == "counter"
            (sample,) = by_name["ctt_store_bytes_read"].samples
            assert sample.value == 10.0
            (stale,) = by_name["ctt_worker_stale"].samples
            assert stale.labels == {"pid": "5", "role": "worker", "job": "1"}
            assert stale.value == 1.0  # 100 s old on a 1 s cadence
            (mem,) = by_name["ctt_worker_device_mem_peak_bytes"].samples
            assert mem.value == 4096.0
            (done,) = by_name["ctt_task_blocks_done"].samples
            assert done.labels == {"task": "t"} and done.value == 1.0

    def test_weird_counter_names_sanitize(self):
        snap = {
            "counters": {"weird name!": 1, "a.b-c/d": 2},
            "gauges": {}, "workers": [], "tasks": {}, "malformed_lines": 0,
        }
        text = render_openmetrics(snap)
        _assert_valid_exposition(text)
        assert "ctt_a_b_c_d_total 2.0" in text


# --------------------------------------------------------------------------
# disabled overhead: no thread, no files, no state


class TestDisabledOverhead:
    def test_heartbeat_never_starts_without_trace_dir(self, tmp_path):
        # earlier traced tests may have left the (inert) daemon thread
        # alive — clear it so this asserts "disabled never STARTS one"
        heartbeat.stop(final=False)
        assert not trace.enabled()
        assert heartbeat.ensure_started() is False
        assert heartbeat.running() is False
        assert "ctt-heartbeat" not in [
            t.name for t in threading.enumerate()
        ]
        # the note hooks are no-ops too
        heartbeat.note_task("t", 8)
        heartbeat.note_block_start(0)
        heartbeat.note_blocks_done()
        heartbeat.beat()
        heartbeat.stop()
        assert list(tmp_path.iterdir()) == []

    def test_executor_construction_stays_clean_when_disabled(self):
        from cluster_tools_tpu.runtime.executor import LocalExecutor

        heartbeat.stop(final=False)
        assert not trace.enabled()
        LocalExecutor({"max_jobs": 1})
        assert heartbeat.running() is False

    def test_heartbeat_starts_and_beats_when_enabled(self, tmp_path):
        metrics.reset()
        trace.enable(str(tmp_path / "trace"), "hb_run", export_env=False)
        try:
            assert heartbeat.ensure_started(role="driver") is True
            assert heartbeat.running() is True
            heartbeat.note_task("t", 4, grid=(2, 2))
            heartbeat.note_block_start(3)
            heartbeat.beat()
            hb_path = os.path.join(
                str(tmp_path / "trace"), "hb_run", f"hb.p{os.getpid()}.json"
            )
            with open(hb_path) as f:
                hb = json.load(f)
            assert hb["task"] == "t"
            assert hb["blocks_total"] == 4
            assert hb["grid"] == [2, 2]
            assert hb["current_blocks"][0]["id"] == 3
            assert hb["exiting"] is False
            heartbeat.stop(final=True)
            assert heartbeat.running() is False
            with open(hb_path) as f:
                assert json.load(f)["exiting"] is True
        finally:
            heartbeat.stop(final=False)
            trace.disable()
            metrics.reset()


# --------------------------------------------------------------------------
# watch CLI exit-code contract


class TestWatchCli:
    def test_once_progress_exits_zero(self, tmp_path):
        run = str(tmp_path / "r")
        os.makedirs(run)
        with open(os.path.join(run, "spans.p1.t1.jsonl"), "w") as f:
            f.write(_header() + "\n")
            f.write(_block_span(1, "t", 0, 11.0, 1.0) + "\n")
            f.write(_task_span(2, "t", 11.0, 1.0) + "\n")
        r = _obs_cli("watch", "--once", run)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "t" in r.stdout

    def test_once_no_progress_exits_one(self, tmp_path):
        run = str(tmp_path / "r")
        os.makedirs(run)
        with open(os.path.join(run, "spans.p1.t1.jsonl"), "w") as f:
            f.write(_header() + "\n")
        r = _obs_cli("watch", "--once", run)
        assert r.returncode == 1
        assert "no progress" in r.stdout

    def test_once_missing_dir_exits_one(self, tmp_path):
        r = _obs_cli("watch", "--once", str(tmp_path / "nope"))
        assert r.returncode == 1

    def test_fail_on_stall_exits_four(self, tmp_path):
        run = str(tmp_path / "r")
        os.makedirs(run)
        with open(os.path.join(run, "spans.p1.t1.jsonl"), "w") as f:
            f.write(_header() + "\n")
            f.write(_block_span(1, "t", 0, 11.0, 1.0) + "\n")
        _write_hb(run, pid=9, wall=time.time() - 3600.0, interval=1.0,
                  task="t", job_id=0)
        # progress exists, but the stale worker dominates the exit code
        r = _obs_cli("watch", "--once", "--fail-on-stall", run)
        assert r.returncode == 4
        assert "STALE" in r.stdout
        # without the flag the same state reports but exits 0
        assert _obs_cli("watch", "--once", run).returncode == 0

    def test_once_json_snapshot(self, tmp_path):
        run = str(tmp_path / "r")
        os.makedirs(run)
        with open(os.path.join(run, "spans.p1.t1.jsonl"), "w") as f:
            f.write(_header() + "\n")
            f.write(_block_span(1, "t", 0, 11.0, 1.0) + "\n")
        r = _obs_cli("watch", "--once", "--json", run)
        assert r.returncode == 0
        snap = json.loads(r.stdout)
        assert snap["tasks"]["t"]["blocks_done"] == 1
        assert snap["progress"] is True

    def test_prom_cli_round_trip(self, tmp_path):
        run = str(tmp_path / "r")
        os.makedirs(run)
        with open(os.path.join(run, "metrics.p1.json"), "w") as f:
            json.dump({"counters": {"store.bytes_read": 7}, "gauges": {}}, f)
        r = _obs_cli("prom", run)
        assert r.returncode == 0
        _assert_valid_exposition(r.stdout)
        assert "ctt_store_bytes_read_total 7.0" in r.stdout

    def test_resolve_descends_single_run(self, tmp_path):
        run = str(tmp_path / "trace" / "only")
        os.makedirs(run)
        with open(os.path.join(run, "spans.p1.t1.jsonl"), "w") as f:
            f.write(_header() + "\n")
        assert resolve_live_dir(str(tmp_path / "trace")) == run
        assert resolve_live_dir(run) == run
        # descent is one level only (the export.resolve_run_dir contract)
        assert resolve_live_dir(str(tmp_path)) is None
        assert resolve_live_dir(str(tmp_path / "missing")) is None


# --------------------------------------------------------------------------
# golden machine-readable output (satellite: summarize --json / diff --json)


def _write_task_run(run_dir, run_id, tasks, counters=None):
    os.makedirs(run_dir, exist_ok=True)
    with open(os.path.join(run_dir, "spans.p1.t1.jsonl"), "w") as f:
        f.write(_header(run_id=run_id) + "\n")
        t, sid = MONO0, 1
        for name, secs in tasks:
            f.write(_task_span(sid, name, t, secs) + "\n")
            t += secs
            sid += 1
    if counters:
        with open(os.path.join(run_dir, "metrics.p1.json"), "w") as f:
            json.dump({"counters": counters, "gauges": {}}, f)


_GOLDEN_ROW = {
    "collective_s": 0.0, "dispatch_wall_s": 0.0, "host_compute_s": 0.0,
    "host_io_s": 0.0, "host_s": 0.0, "n_spans": 1,
    "overlap_hidden_s": 0.0,
}


class TestGoldenJsonOutput:
    def test_summarize_json_golden(self, tmp_path):
        run = str(tmp_path / "g")
        _write_task_run(run, "g", [("taskA", 1.0), ("taskB", 2.0)],
                        {"store.bytes_read": 10})
        r = _obs_cli("summarize", "--json", run)
        assert r.returncode == 0, r.stderr
        assert json.loads(r.stdout) == {
            "counters": {"store.bytes_read": 10.0},
            "gauges": {},
            "n_processes": 1,
            "n_task_spans": 2,
            "run_id": "g",
            "tasks": {
                "taskA": {**_GOLDEN_ROW, "wall_s": 1.0},
                "taskB": {**_GOLDEN_ROW, "wall_s": 2.0},
            },
        }

    def test_summarize_human_golden_stays_default(self, tmp_path):
        run = str(tmp_path / "g")
        _write_task_run(run, "g", [("taskA", 1.0), ("taskB", 2.0)],
                        {"store.bytes_read": 10})
        r = _obs_cli("summarize", run)
        assert r.returncode == 0, r.stderr
        assert r.stdout == (
            "run g  (2 task spans, 1 processes)\n"
            "task      wall_s  host_io_s  host_compute_s  collective_s"
            "     host_s  overlap_hidden_s    n_spans\n"
            "taskB      2.000      0.000           0.000         0.000"
            "      0.000             0.000          1\n"
            "taskA      1.000      0.000           0.000         0.000"
            "      0.000             0.000          1\n"
            "counters:\n"
            "  store.bytes_read = 10\n"
        )

    def test_diff_json_golden(self, tmp_path):
        base = str(tmp_path / "g")
        cand = str(tmp_path / "h")
        _write_task_run(base, "g", [("taskA", 1.0), ("taskB", 2.0)])
        _write_task_run(cand, "h", [("taskA", 1.0), ("taskB", 3.0)])
        r = _obs_cli("diff", "--json", base, cand)
        assert r.returncode == 3  # regression → nonzero, json or not
        assert json.loads(r.stdout) == {
            "a": "g",
            "b": "h",
            "n_regressed": 1,
            "rows": [
                {"a_wall_s": 1.0, "b_wall_s": 1.0, "note": "",
                 "ratio": 1.0, "regressed": False, "task": "taskA"},
                {"a_wall_s": 2.0, "b_wall_s": 3.0, "note": "",
                 "ratio": 1.5, "regressed": True, "task": "taskB"},
            ],
            "threshold": 0.2,
        }

    def test_diff_human_golden_stays_default(self, tmp_path):
        base = str(tmp_path / "g")
        cand = str(tmp_path / "h")
        _write_task_run(base, "g", [("taskA", 1.0), ("taskB", 2.0)])
        _write_task_run(cand, "h", [("taskA", 1.0), ("taskB", 3.0)])
        r = _obs_cli("diff", base, cand)
        assert r.returncode == 3
        assert r.stdout == (
            "diff g -> h (threshold 20%)\n"
            "task      base_s     cand_s    ratio  flag\n"
            "taskA      1.000      1.000    1.00x\n"
            "taskB      2.000      3.000    1.50x  REGRESSED\n"
            "1 task(s) regressed beyond the threshold\n"
        )


# --------------------------------------------------------------------------
# SIGTERM preemption flush (satellite)


class TestSigtermFlush:
    def test_sigterm_flushes_metrics_trace_and_final_heartbeat(
        self, tmp_path
    ):
        trace_dir = str(tmp_path / "trace")
        script = str(tmp_path / "victim.py")
        with open(script, "w") as f:
            f.write(
                "import sys, time\n"
                "from cluster_tools_tpu.obs import heartbeat, metrics, trace\n"
                "heartbeat.install_sigterm_flush()\n"
                "heartbeat.ensure_started(role='worker', job_id=1)\n"
                "metrics.inc('store.bytes_read', 42)\n"
                "with trace.span('setup', kind='host'):\n"
                "    pass\n"
                "with trace.span('victim_task', kind='task'):\n"
                "    print('ready', flush=True)\n"
                "    time.sleep(60)\n"
            )
        env = {**os.environ, "JAX_PLATFORMS": "cpu",
               "CTT_TRACE_DIR": trace_dir, "CTT_RUN_ID": "preempt",
               "CTT_HEARTBEAT_S": "0.1",
               "PYTHONPATH": REPO + os.pathsep
               + os.environ.get("PYTHONPATH", "")}
        proc = subprocess.Popen(
            [sys.executable, script], env=env, cwd=REPO,
            stdout=subprocess.PIPE, text=True,
        )
        assert proc.stdout.readline().strip() == "ready"
        proc.send_signal(signal.SIGTERM)
        proc.wait(timeout=30)
        # default disposition re-raised: the exit says "killed by SIGTERM"
        assert proc.returncode == -signal.SIGTERM
        run_dir = os.path.join(trace_dir, "preempt")
        with open(os.path.join(
            run_dir, f"hb.p{proc.pid}.json"
        )) as f:
            hb = json.load(f)
        assert hb["exiting"] is True
        with open(os.path.join(
            run_dir, f"metrics.p{proc.pid}.json"
        )) as f:
            snap = json.load(f)
        assert snap["counters"]["store.bytes_read"] == 42
        # shard flushed: the completed span made it to disk (the open
        # victim_task span dies with the process — spans record at exit)
        (shard,) = [n for n in os.listdir(run_dir) if n.startswith("spans.")]
        with open(os.path.join(run_dir, shard)) as f:
            names = [json.loads(ln).get("name") for ln in f if ln.strip()]
        assert "setup" in names


# --------------------------------------------------------------------------
# end to end: a real traced workflow is watchable


@pytest.mark.timeout(120)
def test_traced_workflow_watch_heatmap_prom(tmp_path, rng, monkeypatch):
    import numpy as np

    from cluster_tools_tpu.runtime import build, config as cfg
    from cluster_tools_tpu.utils import file_reader
    from cluster_tools_tpu.workflows import UniqueWorkflow

    monkeypatch.setenv("CTT_HEARTBEAT_S", "0.2")
    metrics.reset()
    trace.enable(str(tmp_path / "trace"), "watch_e2e", export_env=False)
    try:
        labels = rng.integers(0, 100, (8, 16, 16)).astype(np.uint64)
        path = str(tmp_path / "d.n5")
        file_reader(path).create_dataset("seg", data=labels, chunks=(4, 8, 8))
        config_dir = str(tmp_path / "configs")
        cfg.write_global_config(
            config_dir, {"block_shape": [4, 8, 8], "target": "tpu"}
        )
        wf = UniqueWorkflow(
            str(tmp_path / "tmp"), config_dir,
            input_path=path, input_key="seg",
            output_path=path, output_key="u",
        )
        assert build([wf])
        trace.flush()
        heartbeat.beat()
        run_dir = os.path.join(str(tmp_path / "trace"), "watch_e2e")

        live = LiveRun(run_dir)
        snap = live.poll()
        assert snap["progress"] is True
        row = snap["tasks"]["find_uniques"]
        assert row["blocks_done"] == 8
        assert row["blocks_total"] == 8
        assert row["complete"] is True
        # the heartbeat carried the blocking geometry
        hm = live.heatmap("find_uniques")
        assert hm["grid"] == [2, 2, 2]
        assert sorted(hm["durations"]) == list(range(8))
        text = render_openmetrics(snap)
        _assert_valid_exposition(text)
        assert 'ctt_task_blocks_done{task="find_uniques"} 8.0' in text
    finally:
        heartbeat.stop(final=False)
        trace.disable()
        metrics.reset()
