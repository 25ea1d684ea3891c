"""ctt-obs: span recorder, cross-process shard merge, CLI contract.

Covers the subsystem's hard requirements:
  * disabled fast path records nothing and allocates nothing;
  * a two-REAL-process workflow run (mirroring test_cluster_executor's
    multi-host test) merges into ONE run with a consistent run id and
    non-overlapping span ids;
  * summarize exits 0 with >= 1 task span, 1 with none, 2 on malformed
    shards; diff exits 3 on regression beyond the threshold;
  * the record_timing bridge leaves the status-file schema untouched.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from cluster_tools_tpu.obs import metrics, trace
from cluster_tools_tpu.obs.export import (
    TraceFormatError,
    diff,
    load_run,
    summarize,
    to_chrome_trace,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def traced(tmp_path):
    """Enable tracing into a tmp dir for one test, restore cleanly."""
    metrics.reset()
    run_id = trace.enable(str(tmp_path / "trace"), "t_run", export_env=False)
    yield os.path.join(str(tmp_path / "trace"), run_id)
    trace.disable()
    metrics.reset()


def _obs_cli(*args):
    return subprocess.run(
        [sys.executable, "-m", "cluster_tools_tpu.obs", *args],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )


# --------------------------------------------------------------------------
# disabled fast path


def test_disabled_is_noop_and_allocation_free(tmp_path):
    assert not trace.enabled()
    # values left by an earlier traced test in this worker process
    metrics.reset()
    s1 = trace.span("a", kind="task")
    s2 = trace.span("b", kind="host_compute", blocks=8)
    # the disabled path returns ONE shared singleton: no per-call objects,
    # no clock reads, no file IO
    assert s1 is s2
    with s1:
        s1.set(anything="goes")
    trace.event("x", "timing", 1.0)
    metrics.inc("store.bytes_read", 100)
    assert metrics.snapshot() == {"counters": {}, "gauges": {}}
    trace.flush()
    assert not (tmp_path / "trace").exists()


def test_disabled_overhead_smoke():
    import timeit as _timeit

    # 50k no-op spans in well under a second: the enabled-check fast path
    # (one global load + one identity return) cannot cost more
    secs = _timeit.timeit(lambda: trace.span("x", kind="host"), number=50_000)
    assert secs < 1.0, f"disabled span() path too slow: {secs:.3f}s"


# --------------------------------------------------------------------------
# in-process recording + export


def test_span_nesting_buckets_and_chrome_export(traced):
    with trace.span("mytask", kind="task"):
        with trace.span("dispatch", kind="dispatch", task="mytask"):
            with trace.span("read", kind="host_io"):
                pass
            with trace.span("batch", kind="host_compute"):
                with trace.span("read2", kind="host_io"):
                    pass
    trace.flush()
    run = load_run(traced)
    s = summarize(run)
    assert s["run_id"] == "t_run"
    assert s["n_task_spans"] == 1
    row = s["tasks"]["mytask"]
    # distinct buckets exist and nested host_io is not double-counted
    # into host_compute (self-time accounting)
    for col in ("wall_s", "host_io_s", "host_compute_s", "collective_s",
                "host_s"):
        assert col in row
    assert row["n_spans"] == 5
    assert row["wall_s"] >= row["host_compute_s"]

    chrome = to_chrome_trace(run)
    events = chrome["traceEvents"]
    assert any(e["ph"] == "X" and e["cat"] == "host_compute" for e in events)
    # valid trace_event JSON: every X event carries ts/dur/pid/tid
    for e in events:
        if e["ph"] == "X":
            assert {"ts", "dur", "pid", "tid", "name"} <= set(e)
    json.dumps(chrome)  # serializable end to end


def test_error_inside_span_is_recorded(traced):
    with pytest.raises(ValueError):
        with trace.span("boom", kind="task"):
            raise ValueError("x")
    trace.flush()
    (span,) = load_run(traced)["spans"]
    assert span["attrs"]["error"] == "ValueError"


def test_parent_links_within_thread(traced):
    with trace.span("outer", kind="task"):
        with trace.span("inner", kind="host"):
            pass
    trace.flush()
    spans = {s["name"]: s for s in load_run(traced)["spans"]}
    assert spans["inner"]["parent"] == spans["outer"]["id"]
    assert spans["outer"]["parent"] is None


# --------------------------------------------------------------------------
# traced workflow run: task spans, record_timing bridge, schema stability


def test_traced_workflow_status_schema_and_task_spans(tmp_path, rng, traced):
    from cluster_tools_tpu.runtime import build, config as cfg
    from cluster_tools_tpu.utils import file_reader
    from cluster_tools_tpu.workflows import UniqueWorkflow

    labels = rng.integers(0, 100, (16, 24, 24)).astype(np.uint64)
    path = str(tmp_path / "d.n5")
    file_reader(path).create_dataset("seg", data=labels, chunks=(8, 12, 12))
    config_dir = str(tmp_path / "configs")
    tmp_folder = str(tmp_path / "tmp")
    cfg.write_global_config(
        config_dir, {"block_shape": [8, 12, 12], "target": "tpu"}
    )
    wf = UniqueWorkflow(
        tmp_folder, config_dir,
        input_path=path, input_key="seg",
        output_path=path, output_key="uniques",
    )
    assert build([wf])

    # satellite: the status-file schema is UNCHANGED by the span bridge —
    # resume/retry keep reading these exact keys
    status = json.load(
        open(os.path.join(tmp_folder, "status", "find_uniques.status.json"))
    )
    assert status["complete"] is True
    assert set(status) >= {
        "task", "n_blocks", "done", "failed", "block_runtimes", "timings",
        "blocks_done", "complete",
    }
    for t in status["timings"]:
        assert set(t) == {"label", "blocks", "seconds"}

    run = load_run(traced)
    s = summarize(run)
    assert s["n_task_spans"] >= 1
    assert "find_uniques" in s["tasks"]
    # _timings bridge: the same dispatch labels appear as timing spans
    timing_names = {
        sp["name"] for sp in run["spans"] if sp["kind"] == "timing"
    }
    assert {t["label"] for t in status["timings"]} <= timing_names
    # store counters flowed through metrics
    assert run["counters"].get("store.chunks_read", 0) > 0


# --------------------------------------------------------------------------
# cross-process merge: two real OS processes, one run


def test_two_process_run_merges_into_one_trace(tmp_path, rng):
    from cluster_tools_tpu.runtime import config as cfg
    from cluster_tools_tpu.utils import file_reader

    labels = rng.integers(0, 500, (16, 24, 24)).astype(np.uint64) * 3
    path = str(tmp_path / "d.n5")
    file_reader(path).create_dataset("seg", data=labels, chunks=(4, 12, 12))
    config_dir = str(tmp_path / "configs")
    tmp_folder = str(tmp_path / "tmp")
    trace_dir = str(tmp_path / "trace")
    cfg.write_global_config(
        config_dir,
        {"block_shape": [4, 12, 12], "num_processes": 2,
         "peer_wait_timeout_s": 120.0},
    )
    script = str(tmp_path / "driver.py")
    with open(script, "w") as f:
        f.write(
            "import jax; jax.config.update('jax_platforms', 'cpu')\n"
            "from cluster_tools_tpu.runtime import build\n"
            "from cluster_tools_tpu.workflows import UniqueWorkflow\n"
            f"wf = UniqueWorkflow({tmp_folder!r}, {config_dir!r},\n"
            f"    input_path={path!r}, input_key='seg',\n"
            f"    output_path={path!r}, output_key='uniques')\n"
            "assert build([wf])\n"
        )
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["CTT_TRACE_DIR"] = trace_dir
    env["CTT_RUN_ID"] = "two_proc"
    pkg_root = os.path.dirname(REPO)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")

    procs = []
    for pid in range(2):
        penv = dict(env)
        penv["CTT_PROCESS_ID"] = str(pid)
        procs.append(subprocess.Popen(
            [sys.executable, script], env=penv,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        ))
    for p in procs:
        _, err = p.communicate(timeout=300)
        assert p.returncode == 0, err.decode()[-2000:]

    run = load_run(os.path.join(trace_dir, "two_proc"))
    # one consistent run id across every shard (load_run rejects mixes)
    assert run["run_id"] == "two_proc"
    pids = {h["pid"] for h in run["headers"]}
    assert len(pids) == 2
    # non-overlapping span ids across processes
    ids = [s["id"] for s in run["spans"]]
    assert len(ids) == len(set(ids))
    # both processes recorded task spans (p1 ran its block shard)
    task_pids = {s["pid"] for s in run["spans"] if s["kind"] == "task"}
    assert task_pids == pids
    # and the merge barrier is visible from the waiting process
    assert any(s["kind"] == "barrier" for s in run["spans"])

    r = _obs_cli("summarize", os.path.join(trace_dir, "two_proc"))
    assert r.returncode == 0, r.stderr
    assert "find_uniques" in r.stdout


# --------------------------------------------------------------------------
# CLI contract


def _write_synthetic_run(run_dir, run_id, tasks):
    """Minimal hand-rolled run: one shard, one task span per (name, secs)."""
    os.makedirs(run_dir, exist_ok=True)
    with open(os.path.join(run_dir, "spans.p1.t1.jsonl"), "w") as f:
        f.write(json.dumps({
            "type": "header", "run": run_id, "pid": 1, "tid": 1,
            "host": "synth", "wall": 1000.0, "mono": 10.0,
        }) + "\n")
        t, sid = 10.0, 1
        for name, secs in tasks:
            f.write(json.dumps({
                "type": "span", "id": sid, "parent": None, "name": name,
                "kind": "task", "t0": t, "t1": t + secs, "pid": 1, "tid": 1,
            }) + "\n")
            t += secs
            sid += 1


def test_cli_summarize_exit_codes(tmp_path):
    run = str(tmp_path / "r1")
    _write_synthetic_run(run, "r1", [("taskA", 1.0)])
    r = _obs_cli("summarize", run)
    assert r.returncode == 0
    assert "taskA" in r.stdout

    # no task spans -> exit 1 (a run that recorded nothing must not pass CI)
    empty = str(tmp_path / "r_empty")
    os.makedirs(empty)
    with open(os.path.join(empty, "spans.p1.t1.jsonl"), "w") as f:
        f.write(json.dumps({
            "type": "header", "run": "r_empty", "pid": 1, "tid": 1,
            "host": "synth", "wall": 1000.0, "mono": 10.0,
        }) + "\n")
        f.write(json.dumps({
            "type": "span", "id": 1, "parent": None, "name": "io",
            "kind": "host_io", "t0": 10.0, "t1": 11.0, "pid": 1, "tid": 1,
        }) + "\n")
    assert _obs_cli("summarize", empty).returncode == 1


def test_cli_malformed_event_file_exits_nonzero(tmp_path):
    run = str(tmp_path / "bad")
    _write_synthetic_run(run, "bad", [("taskA", 1.0)])
    with open(os.path.join(run, "spans.p1.t1.jsonl"), "a") as f:
        f.write("this is not json\n")
    with pytest.raises(TraceFormatError):
        load_run(run)
    r = _obs_cli("summarize", run)
    assert r.returncode == 2
    assert "malformed" in r.stderr


def test_cli_diff_flags_regression(tmp_path):
    base = str(tmp_path / "base")
    fast = str(tmp_path / "fast")
    slow = str(tmp_path / "slow")
    _write_synthetic_run(base, "base", [("taskA", 1.0), ("taskB", 2.0)])
    _write_synthetic_run(fast, "fast", [("taskA", 1.05), ("taskB", 1.9)])
    _write_synthetic_run(slow, "slow", [("taskA", 1.0), ("taskB", 3.0)])

    ok = _obs_cli("diff", base, fast, "--threshold", "0.2")
    assert ok.returncode == 0, ok.stdout + ok.stderr

    bad = _obs_cli("diff", base, slow, "--threshold", "0.2")
    assert bad.returncode == 3
    assert "REGRESSED" in bad.stdout
    assert "taskB" in bad.stdout

    # programmatic API agrees
    d = diff(load_run(base), load_run(slow), threshold=0.2)
    assert d["n_regressed"] == 1
    (reg,) = [r for r in d["rows"] if r["regressed"]]
    assert reg["task"] == "taskB"


def test_diff_absolute_floor_ignores_jitter(tmp_path):
    a = str(tmp_path / "a")
    b = str(tmp_path / "b")
    # 10x relative growth but only 90 µs absolute — jitter, not regression
    _write_synthetic_run(a, "a", [("tiny", 1e-5)])
    _write_synthetic_run(b, "b", [("tiny", 1e-4)])
    d = diff(load_run(a), load_run(b), threshold=0.2, min_seconds=0.01)
    assert d["n_regressed"] == 0


def test_resolve_single_run_from_trace_dir(tmp_path):
    run = str(tmp_path / "trace" / "only_run")
    _write_synthetic_run(run, "only_run", [("taskA", 1.0)])
    # passing the parent trace dir resolves to the single run inside
    assert summarize(load_run(str(tmp_path / "trace")))["run_id"] == "only_run"
