"""ctt-steal: dynamic work-stealing block scheduler tests.

Covers the lease protocol end to end:

  * lease/manifest/result file grammar + renewal semantics;
  * the claim race between two REAL processes (os.link exclusivity:
    every block computed exactly once, never lost);
  * expiry → requeue after a ``CTT_FAULTS`` worker kill, with output
    byte-identical to a fault-free run and ZERO task-level retry rounds;
  * an elastic late-joining worker draining the queue;
  * straggler duplicate dispatch with first-writer-wins results;
  * ``CTT_SCHED=static`` byte-identity with the frozen round-robin split
    (and the disabled-overhead contract: no queue directory at all);
  * aggregation attribution from ownership records, not frozen slices.
"""

import hashlib
import json
import os
import stat
import subprocess
import sys
import time

import numpy as np
import pytest

from cluster_tools_tpu.runtime import build, config as cfg
from cluster_tools_tpu.runtime.queue import (
    STALE_INTERVALS, Claim, WorkQueue, drain, publish_once, resolve_sched,
)
from cluster_tools_tpu.utils import file_reader

PKG_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(cfg.__file__)))
)


@pytest.fixture
def traced_metrics(tmp_path):
    """Counters are live only while tracing is enabled (the one ctt-obs
    switch); flip it on for tests asserting sched.* metric movement."""
    from cluster_tools_tpu.obs import metrics as obs_metrics
    from cluster_tools_tpu.obs import trace as obs_trace

    was_on = obs_trace.enabled()
    if not was_on:
        obs_trace.enable(str(tmp_path / "trace"), "sched_unit",
                         export_env=False)
    try:
        yield obs_metrics
    finally:
        if not was_on:
            obs_trace.disable()


def _write_stub_scheduler(folder):
    """Synchronous sbatch/squeue stand-in (the fake-scheduler seam)."""
    os.makedirs(folder, exist_ok=True)
    submit = os.path.join(folder, "stub_submit")
    with open(submit, "w") as f:
        f.write(
            "#!/bin/bash\n"
            'script="${@: -1}"\n'
            'bash "$script" > /dev/null 2>&1\n'
            'echo "Submitted batch job 1"\n'
        )
    queue = os.path.join(folder, "stub_queue")
    with open(queue, "w") as f:
        f.write("#!/bin/bash\nexit 0\n")
    for p in (submit, queue):
        os.chmod(p, os.stat(p).st_mode | stat.S_IEXEC)
    return submit, queue


WORKER_ENV = {
    "JAX_PLATFORMS": "cpu",
}


def _digest_tree(root):
    h = hashlib.sha256()
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames.sort()
        for name in sorted(filenames):
            p = os.path.join(dirpath, name)
            h.update(os.path.relpath(p, root).encode())
            with open(p, "rb") as f:
                h.update(f.read())
    return h.hexdigest()


# --------------------------------------------------------------------------
# queue-layer unit tests


class TestLeaseGrammar:
    def test_manifest_items_and_claim_lease_schema(self, tmp_path):
        q = WorkQueue.create(
            str(tmp_path / "q"), "ws", list(range(7)), 3, 0.5
        )
        assert q.items == [[0, 1, 2], [3, 4, 5], [6]]
        m = json.load(open(str(tmp_path / "q" / "manifest.json")))
        assert m["task"] == "ws" and m["lease_s"] == 0.5 and m["duplicate"]

        claim = q.claim(job_id=2)
        assert claim.item == 0 and claim.block_ids == [0, 1, 2]
        assert claim.gen == 0 and not claim.duplicate
        lease = json.load(open(claim.lease_path))
        assert lease["item"] == 0 and lease["gen"] == 0
        assert lease["blocks"] == [0, 1, 2]
        assert lease["owner_pid"] == os.getpid() and lease["job_id"] == 2
        assert lease["claim_wall"] <= lease["wall"]
        assert "mono" in lease and "host" in lease

    def test_renew_restamps_wall_keeps_claim_wall(self, tmp_path):
        q = WorkQueue.create(str(tmp_path / "q"), "t", [0, 1], 1, 0.5)
        claim = q.claim(job_id=0)
        before = json.load(open(claim.lease_path))
        time.sleep(0.05)
        q.renew(claim, job_id=0)
        after = json.load(open(claim.lease_path))
        assert after["wall"] > before["wall"]
        assert after["claim_wall"] == pytest.approx(before["claim_wall"])

    def test_result_publish_first_writer_wins(self, tmp_path):
        q = WorkQueue.create(str(tmp_path / "q"), "t", [0, 1], 2, 0.5)
        claim = q.claim(job_id=0)
        assert q.complete(claim, [0, 1], [], {}, 0.1, job_id=0)
        # a racing duplicate loses the result slot; the record keeps the
        # first writer's attribution
        dup = Claim(item=0, block_ids=[0, 1], gen=0, lease_path=None,
                    duplicate=True)
        assert not q.complete(dup, [0, 1], [], {}, 0.2, job_id=9)
        rec = json.load(open(str(tmp_path / "q" / "result.0.json")))
        assert rec["job_id"] == 0 and not rec["duplicate"]
        assert q.all_resolved()

    def test_publish_once_is_exclusive_and_atomic(self, tmp_path):
        p = str(tmp_path / "slot")
        assert publish_once(p, b"first")
        assert not publish_once(p, b"second")
        assert open(p, "rb").read() == b"first"
        # no tmp litter
        assert os.listdir(str(tmp_path)) == ["slot"]

    def test_resolve_sched_defaults_and_guards(self):
        class Retryable:
            allow_retry = True

        class Fragile:
            allow_retry = False

        assert resolve_sched({}, Retryable(), 3) == "steal"
        assert resolve_sched({}, Retryable(), 1) == "static"
        # requeue/duplication re-run blocks: non-retryable tasks keep the
        # frozen split even when steal is requested
        assert resolve_sched({}, Fragile(), 3) == "static"
        assert resolve_sched({"sched": "steal"}, Fragile(), 3) == "static"
        assert resolve_sched({"sched": "static"}, Retryable(), 3) == "static"
        with pytest.raises(ValueError, match="unknown scheduler mode"):
            resolve_sched({"sched": "steel"}, Retryable(), 3)

    def test_sched_metrics_registered(self):
        from cluster_tools_tpu.obs import registry

        for name in (
            "sched.leases_claimed", "sched.leases_expired",
            "sched.leases_requeued", "sched.leases_stolen",
            "sched.driver_drain_blocks",
        ):
            assert registry.is_known_counter(name), name
        assert registry.is_known_gauge("sched.queue_depth")


class TestExpiryAndRequeue:
    def test_expired_lease_requeues_at_next_generation(
        self, tmp_path, traced_metrics
    ):
        # injected reader clock (WorkQueue._now) instead of real sleeps:
        # with a sub-second cadence a loaded CI host could age the fresh
        # lease past 3x BEFORE the freshness assertion ran — the timing
        # flake this test used to carry.  A wide cadence makes "fresh"
        # unbreakable and the advanced clock makes "expired" exact.
        obs_metrics = traced_metrics
        lease_s = 30.0
        q = WorkQueue.create(str(tmp_path / "q"), "t", [0, 1], 2, lease_s)
        dead = q.claim(job_id=0)  # owner "dies": never renews, never completes
        assert dead is not None
        before = obs_metrics.snapshot()["counters"]
        assert q.claim(job_id=1) is None  # lease still fresh
        q._now = lambda: time.time() + STALE_INTERVALS * lease_s + 1.0
        takeover = q.claim(job_id=1)
        assert takeover is not None
        assert takeover.item == dead.item and takeover.gen == 1
        after = obs_metrics.snapshot()["counters"]
        assert after.get("sched.leases_expired", 0) > before.get(
            "sched.leases_expired", 0
        )
        assert after.get("sched.leases_requeued", 0) > before.get(
            "sched.leases_requeued", 0
        )
        # both generations remain as ownership history
        names = sorted(os.listdir(str(tmp_path / "q")))
        assert "lease.0.g0.json" in names and "lease.0.g1.json" in names

    def test_torn_lease_still_expires_via_mtime(self, tmp_path):
        from cluster_tools_tpu import faults

        lease_s = 30.0
        q = WorkQueue.create(str(tmp_path / "q"), "t", [0], 1, lease_s)
        faults.configure("sched.write:torn:bytes=5;seed=1")
        try:
            torn = q.claim(job_id=0)
        finally:
            faults.reset()
        # the lease payload was truncated mid-write
        raw = open(torn.lease_path, "rb").read()
        assert len(raw) == 5
        with pytest.raises(json.JSONDecodeError):
            json.loads(raw)
        # torn leases age from file mtime; the injected reader clock
        # (WorkQueue._now) expires it without sleeping 3x the cadence
        assert q.claim(job_id=1) is None  # still fresh by mtime
        q._now = lambda: time.time() + STALE_INTERVALS * lease_s + 1.0
        takeover = q.claim(job_id=1)
        assert takeover is not None and takeover.gen == 1

    def test_unresolved_item_attributed_to_real_owner(self, tmp_path):
        """Satellite: aggregation blames the ACTUAL lease owner, not the
        job a frozen round-robin slice would have assigned the blocks."""
        q = WorkQueue.create(str(tmp_path / "q"), "t", [0, 1, 2, 3], 2, 0.5)
        a = q.claim(job_id=7)     # job 7 owns item 0 ... and dies
        b = q.claim(job_id=1)     # job 1 completes item 1
        q.complete(b, b.block_ids, [], {}, 0.01, job_id=1)
        done, failed, errors, owners = q.aggregate()
        assert sorted(done) == [2, 3]
        assert failed == [0, 1]
        assert "job 7" in errors[0] and "never produced a result" in errors[0]
        assert owners[a.item]["job_id"] == 7
        assert owners[b.item]["job_id"] == 1


class TestStragglerDuplication:
    def test_duplicate_oldest_inflight_first_writer_wins(
        self, tmp_path, traced_metrics
    ):
        obs_metrics = traced_metrics
        q = WorkQueue.create(
            str(tmp_path / "q"), "t", list(range(8)), 2, 60.0
        )
        straggler = q.claim(job_id=0)  # holds item 0, runs "forever"
        fast = WorkQueue(str(tmp_path / "q"))
        for _ in range(3):
            c = fast.claim(job_id=1)
            fast.complete(c, c.block_ids, [], {}, 0.01, job_id=1)
        # nothing unclaimed, lease fresh, claim too young -> no duplicate yet
        assert fast.claim(job_id=1) is None
        # age the straggler's CLAIM (not its renewal stamp: the lease is
        # alive, its owner just isn't finishing) beyond 4 x median
        lease = json.load(open(straggler.lease_path))
        lease["claim_wall"] -= 3600.0
        with open(straggler.lease_path, "w") as f:
            json.dump(lease, f)
        before = obs_metrics.snapshot()["counters"]
        dup = fast.claim(job_id=1)
        assert dup is not None and dup.duplicate and dup.item == 0
        assert dup.lease_path is None  # duplication takes no lease
        after = obs_metrics.snapshot()["counters"]
        assert after.get("sched.leases_stolen", 0) > before.get(
            "sched.leases_stolen", 0
        )
        # the same client never duplicates the same item twice
        assert fast.claim(job_id=1, skip_duplicates={0}) is None
        # first writer (the duplicate) wins the result slot; the straggling
        # owner's late publish is a no-op
        assert fast.complete(dup, dup.block_ids, [], {}, 0.01, job_id=1)
        assert not q.complete(
            straggler, straggler.block_ids, [], {}, 99.0, job_id=0
        )
        done, failed, errors, owners = q.aggregate()
        assert failed == [] and sorted(done) == list(range(8))
        assert owners[0]["job_id"] == 1 and owners[0]["duplicate"]

    def test_duplicate_fires_from_live_median_before_first_result(
        self, tmp_path, traced_metrics
    ):
        """Lease-aware straggler thresholds (ROADMAP item 1 follow-up,
        landed with ctt-serve): when the live trace already carries
        completed block durations for this task, duplication uses
        obs.live's per-task median (scaled by the item's block count) —
        so it can fire before ANY item result record exists, where the
        queue's own median was previously blind."""
        from cluster_tools_tpu.obs import trace as obs_trace

        q = WorkQueue.create(
            str(tmp_path / "q"), "t", list(range(4)), 2, 60.0
        )
        straggler = q.claim(job_id=0)   # item 0, runs "forever"
        fast = WorkQueue(str(tmp_path / "q"))
        other = fast.claim(job_id=1)    # item 1, also in flight
        assert other is not None and other.item == 1
        # zero results and no trace data: no baseline, no duplicate
        assert fast.claim(job_id=1) is None
        # completed block spans land in the live trace (the obs watch
        # straggler baseline): median block 0.01 s
        run_dir = obs_trace.run_dir()
        os.makedirs(run_dir, exist_ok=True)
        with open(os.path.join(run_dir, "spans.p1.t1.jsonl"), "w") as f:
            f.write(json.dumps({
                "type": "header", "run": "sched_unit", "pid": 1, "tid": 1,
                "host": "synth", "wall": 1000.0, "mono": 10.0,
            }) + "\n")
            for i in range(5):
                f.write(json.dumps({
                    "type": "span", "id": i + 1, "parent": None,
                    "name": "block", "kind": "host",
                    "t0": 10.0 + i, "t1": 10.01 + i, "pid": 1, "tid": 1,
                    "attrs": {"task": "t", "block": 100 + i},
                }) + "\n")
        # age the straggler's CLAIM well past 4 x (median x item blocks)
        lease = json.load(open(straggler.lease_path))
        lease["claim_wall"] -= 3600.0
        with open(straggler.lease_path, "w") as f:
            json.dump(lease, f)
        dup = WorkQueue(str(tmp_path / "q")).claim(job_id=1)
        assert dup is not None and dup.duplicate and dup.item == 0
        # a different task's spans are not a baseline for this queue
        q2 = WorkQueue.create(
            str(tmp_path / "q2"), "other_task", [0, 1], 1, 60.0
        )
        s2 = q2.claim(job_id=0)
        assert q2.claim(job_id=0) is not None  # item 1 also in flight
        lease = json.load(open(s2.lease_path))
        lease["claim_wall"] -= 3600.0
        with open(s2.lease_path, "w") as f:
            json.dump(lease, f)
        assert WorkQueue(str(tmp_path / "q2")).claim(job_id=1) is None


# --------------------------------------------------------------------------
# real-process tests: claim race + elastic late joiner

_WORKER_SCRIPT = """\
import json, os, sys, time
sys.path.insert(0, {pkg_root!r})
from cluster_tools_tpu.runtime.queue import WorkQueue, drain

queue_dir, job_id, sleep_s, out = (
    sys.argv[1], int(sys.argv[2]), float(sys.argv[3]), sys.argv[4]
)
q = WorkQueue(queue_dir)


def run_item(claim):
    if sleep_s:
        time.sleep(sleep_s)
    return list(claim.block_ids), [], {{}}


stats = drain(q, run_item, job_id=job_id)
with open(out, "w") as f:
    json.dump(stats, f)
"""


def _spawn_worker(tmp_path, queue_dir, job_id, sleep_s, extra_env=None):
    script = str(tmp_path / "queue_worker.py")
    if not os.path.exists(script):
        with open(script, "w") as f:
            f.write(_WORKER_SCRIPT.format(pkg_root=PKG_ROOT))
    out = str(tmp_path / f"stats_{job_id}.json")
    env = dict(os.environ)
    env.update(WORKER_ENV)
    env.pop("CTT_TRACE_DIR", None)
    if extra_env:
        env.update(extra_env)
    proc = subprocess.Popen(
        [sys.executable, script, queue_dir, str(job_id), str(sleep_s), out],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
    )
    return proc, out


class TestRealProcesses:
    def test_claim_race_exactly_once_never_lost(self, tmp_path):
        """Two real processes hammer the same queue (with injected claim
        stalls widening the selection→link window): os.link exclusivity
        must hand every item to exactly one owner, and every block must
        land in exactly one result."""
        n_blocks = 30
        q = WorkQueue.create(
            str(tmp_path / "q"), "t", list(range(n_blocks)), 2, 5.0,
            duplicate=False,
        )
        race_env = {"CTT_FAULTS": "sched.claim:stall:p=0.4,s=0.02;seed=3"}
        procs = [
            _spawn_worker(tmp_path, q.dir, j, 0.0, extra_env=race_env)
            for j in range(2)
        ]
        stats = []
        for proc, out in procs:
            _, err = proc.communicate(timeout=120)
            assert proc.returncode == 0, err.decode()[-2000:]
            stats.append(json.load(open(out)))
        all_done = stats[0]["done"] + stats[1]["done"]
        assert sorted(all_done) == list(range(n_blocks))  # exactly once
        assert not set(stats[0]["items"]) & set(stats[1]["items"])
        # one gen-0 lease per item, no requeues, one result per item
        names = os.listdir(q.dir)
        leases = [n for n in names if n.startswith("lease.")]
        assert len(leases) == len(q.items)
        assert all(n.endswith(".g0.json") for n in leases)
        assert len([n for n in names if n.startswith("result.")]) == len(
            q.items
        )
        done, failed, errors, _ = q.aggregate()
        assert failed == [] and errors == {}

    def test_elastic_late_joiner_drains_queue(self, tmp_path):
        """A process pointed at the queue AFTER the run started just
        starts pulling — no registration, no resubmission."""
        q = WorkQueue.create(
            str(tmp_path / "q"), "t", list(range(12)), 1, 5.0,
            duplicate=False,
        )
        early, early_out = _spawn_worker(tmp_path, q.dir, 0, 0.15)
        time.sleep(1.0)  # the early worker is mid-drain by now
        late, late_out = _spawn_worker(tmp_path, q.dir, 1, 0.0)
        for proc, out in ((early, early_out), (late, late_out)):
            _, err = proc.communicate(timeout=120)
            assert proc.returncode == 0, err.decode()[-2000:]
        s_early = json.load(open(early_out))
        s_late = json.load(open(late_out))
        assert s_late["items"], "late joiner pulled nothing"
        assert s_early["items"], "early worker pulled nothing"
        assert sorted(s_early["done"] + s_late["done"]) == list(range(12))
        assert q.all_resolved()


# --------------------------------------------------------------------------
# integration: stub-scheduler workflows


def _threshold_run(tmp_path, rng_data, tag, *, sched=None, faults_spec=None,
                   state_dir=None, trace_run=None, max_jobs=3,
                   extra_global=None):
    """One ThresholdTask run through the stub scheduler; returns the n5
    output dataset dir (for byte digests) and the task status path."""
    from cluster_tools_tpu.tasks.threshold import ThresholdTask

    submit, queue = _write_stub_scheduler(str(tmp_path / f"sched_{tag}"))
    path = str(tmp_path / f"{tag}.n5")
    file_reader(path).create_dataset(
        "x", data=rng_data, chunks=(4, 16, 16)
    )
    config_dir = str(tmp_path / f"configs_{tag}")
    gconf = {
        "block_shape": [4, 16, 16],
        "target": "slurm",
        "max_jobs": max_jobs,
        "max_num_retries": 2,
        "retry_failure_fraction": 0.9,
        "poll_interval_s": 0.05,
        # a full-second cadence (expiry at 3 s): the renewer stamps every
        # 0.5 s, so ~6 consecutive starved renewals would be needed for a
        # LIVE lease to expire spuriously — the worker-kill test was flaky
        # under full-suite load at 0.2 s (PR 9 review)
        "steal_lease_s": 1.0,
        "steal_batch_size": 2,
        "sbatch_cmd": submit,
        "squeue_cmd": queue,
        "worker_env": dict(WORKER_ENV),
    }
    if sched is not None:
        gconf["sched"] = sched
    if extra_global:
        gconf.update(extra_global)
    cfg.write_global_config(config_dir, gconf)
    cfg.write_config(config_dir, "threshold", {"threshold": 0.5})
    env_keys = {}
    if faults_spec is not None:
        env_keys["CTT_FAULTS"] = faults_spec
        env_keys["CTT_FAULT_STATE_DIR"] = state_dir
    if trace_run is not None:
        env_keys["CTT_TRACE_DIR"] = str(tmp_path / "trace")
        env_keys["CTT_RUN_ID"] = trace_run
    old = {k: os.environ.get(k) for k in env_keys}
    os.environ.update(env_keys)
    try:
        task = ThresholdTask(
            str(tmp_path / f"tmp_{tag}"), config_dir, max_jobs=max_jobs,
            input_path=path, input_key="x",
            output_path=path, output_key="y",
        )
        assert build([task])
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    status = json.load(open(os.path.join(
        str(tmp_path / f"tmp_{tag}"), "status", "threshold.status.json"
    )))
    return os.path.join(path, "y"), status, str(tmp_path / f"tmp_{tag}")


@pytest.fixture
def vol(rng):
    return rng.random((16, 32, 32)).astype("float32")


class TestStubSchedulerIntegration:
    def test_static_steal_byte_identical_and_static_overhead(
        self, tmp_path, vol
    ):
        """CTT_SCHED=static is the pre-PR frozen split, byte-identical to
        the stealing path; static runs build no queue at all (disabled-
        overhead contract)."""
        out_static, st_static, tmp_static = _threshold_run(
            tmp_path, vol, "static", sched="static"
        )
        out_steal, st_steal, tmp_steal = _threshold_run(
            tmp_path, vol, "steal", sched="steal"
        )
        assert _digest_tree(out_static) == _digest_tree(out_steal)
        assert st_static["complete"] and st_steal["complete"]
        # static: frozen round-robin recorded in the job configs, no queue
        job_dir = os.path.join(tmp_static, "cluster_jobs", "threshold")
        ids = sorted(st_static["done"])
        for jf in sorted(os.listdir(job_dir)):
            if jf.startswith("job_") and jf.endswith(".json") \
                    and "status" not in jf:
                job_id = int(jf.split("_")[1].split(".")[0])
                conf = json.load(open(os.path.join(job_dir, jf)))
                assert conf["block_ids"] == ids[job_id::3]
                assert "queue_dir" not in conf
        assert not os.path.isdir(os.path.join(job_dir, "queue"))
        # steal: queue manifest + results exist, job statuses say so
        steal_q = os.path.join(
            tmp_steal, "cluster_jobs", "threshold", "queue"
        )
        assert os.path.exists(os.path.join(steal_q, "manifest.json"))
        assert any(
            n.startswith("result.") for n in os.listdir(steal_q)
        )

    def test_worker_kill_selfheals_via_requeue_byte_identical(
        self, tmp_path, vol
    ):
        """A worker hard-killed mid-item (executor.block kill) loses its
        lease; a surviving worker requeues it after expiry.  The run
        completes in ONE dispatch round (zero task-level retries) and the
        output is byte-identical to a fault-free run.

        Duplication is disabled for the chaos run: straggler duplication
        and lease expiry RACE to recover a killed item (both are correct,
        first writer wins), so with it enabled the ``leases_expired >= 1``
        assertion was a coin flip under load — the PR 9 tier-1 flake.
        With ``steal_duplicate: false`` the expiry path is the only
        recovery route and the assertion is deterministic.

        The remaining flake was the expiry wait itself: the surviving
        worker must age the dead lease past ``3 x steal_lease_s`` of REAL
        time, racing its own drain give-up against CI load.  The chaos
        workers therefore run with ``CTT_SCHED_CLOCK_SKEW_S`` (the
        injected-clock seam from the PR 10 review) beyond the staleness
        horizon, so a dead lease is expired on the very first scan.  The
        skew shifts only the reader clock of those subprocesses; stamps
        stay real, and a worker never scans while holding a live lease
        (``drain`` is claim->execute->complete, jobs are sequential under
        the stub scheduler), so no live lease can be mis-expired."""
        out_ref, _, _ = _threshold_run(tmp_path, vol, "ref", sched="steal")
        out_chaos, status, tmp_chaos = _threshold_run(
            tmp_path, vol, "chaos", sched="steal",
            faults_spec="executor.block:kill:ids=5,once;seed=11",
            state_dir=str(tmp_path / "fault_state"),
            trace_run="steal_chaos",
            extra_global={
                "steal_duplicate": False,
                # > stale_after_s = 3 * steal_lease_s (1.0 s above)
                "worker_env": dict(
                    WORKER_ENV, CTT_SCHED_CLOCK_SKEW_S="4.0"
                ),
            },
        )
        assert _digest_tree(out_ref) == _digest_tree(out_chaos)
        # the kill really fired (cross-process latch)
        latches = os.listdir(str(tmp_path / "fault_state"))
        assert any(l.startswith("executor.block") for l in latches), latches
        # zero task-level retry rounds: one dispatch, nothing re-submitted
        assert status["complete"]
        assert len(status["block_runtimes"]) == 1
        # recovery is visible: a worker recorded the expiry + requeue
        totals = {}
        run_dir = str(tmp_path / "trace" / "steal_chaos")
        for name in os.listdir(run_dir):
            if name.startswith("metrics.p"):
                with open(os.path.join(run_dir, name)) as f:
                    for k, v in json.load(f)["counters"].items():
                        totals[k] = totals.get(k, 0) + v
        assert totals.get("sched.leases_expired", 0) >= 1, totals
        assert totals.get("sched.leases_requeued", 0) >= 1, totals
        assert totals.get("task.blocks_retried", 0) == 0, totals


class TestAggregationAttribution:
    def test_static_aggregate_uses_recorded_assignment(self, tmp_path):
        """Satellite fix: a statusless job's blocks come from its RECORDED
        job_N.json assignment, not a re-derived slice — so attribution
        stays truthful if the formation rule and the aggregation ever
        disagree."""
        from cluster_tools_tpu.runtime.cluster_executor import SlurmExecutor

        job_dir = str(tmp_path / "jobs")
        os.makedirs(job_dir)
        # deliberately NOT the round-robin slice of [1, 5, 7]
        with open(os.path.join(job_dir, "job_0.json"), "w") as f:
            json.dump({"block_ids": [5, 7]}, f)
        with open(os.path.join(job_dir, "job_1.json"), "w") as f:
            json.dump({"block_ids": [1]}, f)
        with open(os.path.join(job_dir, "job_1.status.json"), "w") as f:
            json.dump({"done": [1], "failed": [], "errors": {}}, f)
        ex = SlurmExecutor({})
        done, failed, errors = ex._aggregate(job_dir, 2, [1, 5, 7])
        assert done == [1]
        assert failed == [5, 7]
        # the no-status diagnostic anchors on job 0's REAL first block (5);
        # the frozen slice would have blamed block 1, which job 1 finished
        assert 5 in errors and "job 0" in errors[5]
        assert 1 not in errors
