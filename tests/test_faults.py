"""ctt-fault chaos suite: deterministic fault injection + the resilience it
validates.

Covers the acceptance contract of the fault subsystem:

  * spec grammar (loud on malformed specs) + deterministic seeded schedules
    — identical injection sequence across two real processes;
  * CTT_FAULTS unset ⇒ the injection sites are the no-op fast path;
  * store IO faults (transient errors, torn chunk writes) heal through the
    shared backoff retry / CorruptChunk classification — outputs stay
    byte-identical to a fault-free run, recovery visible in obs counters;
  * the executor's soft-deadline watchdog converts hung blocks into failed
    blocks that the task retry loop re-runs;
  * a killed scheduler job (no status file) recovers through resubmission,
    and a corrupt task.pkl/job config writes a machine-readable failed
    status instead of dying silently;
  * collective-init failure degrades sharded kernels to the single-device
    local kernel with identical output (never a silent wrong answer).
"""

import hashlib
import json
import os
import pickle
import stat
import subprocess
import sys
import time

import numpy as np
import pytest

from cluster_tools_tpu import faults
from cluster_tools_tpu.obs import metrics as obs_metrics
from cluster_tools_tpu.obs import trace as obs_trace
from cluster_tools_tpu.runtime import build, config as cfg
from cluster_tools_tpu.utils import file_reader

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _disarm():
    """Every test starts and ends with the harness disarmed."""
    faults.reset()
    yield
    faults.reset()


@pytest.fixture
def obs_run(tmp_path):
    """Enable tracing (counters only count when obs is on) without exporting
    the env vars to other tests."""
    obs_metrics.reset()
    obs_trace.enable(str(tmp_path / "_trace"), "faults_test",
                     export_env=False)
    yield
    obs_trace.disable()
    obs_metrics.reset()


def counters():
    return obs_metrics.snapshot()["counters"]


# --------------------------------------------------------------------------
# spec grammar + determinism


class TestSpec:
    def test_example_spec_parses(self):
        entries, seed = faults.parse_spec(
            "store.write:io_error:p=0.05;worker.job:kill:ids=1;"
            "collective.init:fail:once;seed=42"
        )
        assert seed == 42
        assert [(e.site, e.action) for e in entries] == [
            ("store.write", "io_error"),
            ("worker.job", "kill"),
            ("collective.init", "fail"),
        ]
        assert entries[0].p == 0.05
        assert entries[1].ids == frozenset({1})
        assert entries[2].times == 1

    @pytest.mark.parametrize("spec", [
        "nosuch.site:fail",              # unknown site
        "store.write:explode",           # unknown action
        "store.write:io_error:p=nan2",   # malformed param
        "store.write:io_error:p=1.5",    # out-of-range probability
        "store.read:torn",               # torn is write-only
        "store.write",                   # missing action
        "seed=7",                        # no entries at all
    ])
    def test_malformed_specs_are_loud(self, spec):
        with pytest.raises(faults.FaultSpecError):
            faults.parse_spec(spec)

    def test_ids_and_after_gate_firing(self):
        faults.configure("executor.block:fail:ids=2|5,after=1;seed=0")
        fired = []
        for rnd in range(3):
            for bid in range(6):
                try:
                    faults.check("executor.block", id=bid)
                except faults.FaultInjected:
                    fired.append((rnd, bid))
        # ids gate to blocks 2 and 5; after=1 skips each entry's first match
        assert (0, 2) not in fired and (0, 5) in fired
        assert (1, 2) in fired and (2, 5) in fired

    def test_same_seed_same_schedule_in_process(self):
        def run():
            faults.configure("store.write:io_error:p=0.4;seed=11")
            out = []
            for _ in range(32):
                try:
                    faults.check("store.write")
                    out.append(0)
                except OSError:
                    out.append(1)
            return out
        a, b = run(), run()
        assert a == b and 0 < sum(a) < 32

    def test_determinism_across_two_processes(self, tmp_path):
        """Same CTT_FAULTS spec + seed ⇒ identical injection sequence in two
        real interpreter instances (the cross-process chaos contract)."""
        script = (
            "from cluster_tools_tpu import faults\n"
            "for i in range(40):\n"
            "    try:\n"
            "        faults.check('store.write', id=i % 4)\n"
            "    except OSError:\n"
            "        pass\n"
            "    faults.mangle('store.write', b'x' * 64)\n"
            "print(faults.decision_log())\n"
        )
        env = {
            **os.environ,
            "CTT_FAULTS": (
                "store.write:io_error:p=0.3;store.write:torn:p=0.2;seed=13"
            ),
            "JAX_PLATFORMS": "cpu",
        }
        env.pop("CTT_FAULT_STATE_DIR", None)
        outs = [
            subprocess.run(
                [sys.executable, "-c", script], capture_output=True,
                text=True, env=env, cwd=REPO,
            )
            for _ in range(2)
        ]
        for proc in outs:
            assert proc.returncode == 0, proc.stderr
        assert outs[0].stdout == outs[1].stdout
        assert "store.write" in outs[0].stdout  # something actually fired


class TestNoopFastPath:
    def test_unset_means_disabled(self, monkeypatch):
        monkeypatch.delenv(faults.ENV_SPEC, raising=False)
        faults.configure()
        assert not faults.enabled()
        assert faults.check("store.read") is None
        assert faults.mangle("store.write", b"abc") is None
        assert faults.decision_log() == []

    def test_disabled_overhead_smoke(self):
        """The no-op path is one global load + compare: 100k site checks
        must cost (generously) under a second — no measurable cost to a
        block batch's handful of checks."""
        assert not faults.enabled()
        t0 = time.perf_counter()
        for _ in range(100_000):
            faults.check("store.write")
        assert time.perf_counter() - t0 < 1.0


# --------------------------------------------------------------------------
# store resilience


def _roundtrip(tmp_path, name, data, chunks=(4, 8, 8)):
    path = str(tmp_path / name)
    file_reader(path).create_dataset("x", data=data, chunks=chunks)
    return path


class TestStoreResilience:
    def test_transient_write_errors_retry_to_byte_identical(
        self, tmp_path, rng, obs_run
    ):
        data = rng.integers(0, 1000, (16, 16, 16)).astype("uint32")
        ref = _roundtrip(tmp_path, "ref.n5", data)
        faults.configure("store.write:io_error:p=0.3;seed=1")
        chaos = _roundtrip(tmp_path, "chaos.n5", data)
        faults.reset()
        np.testing.assert_array_equal(
            file_reader(chaos, "r")["x"][:], file_reader(ref, "r")["x"][:]
        )
        assert counters().get("store.io_retries", 0) > 0
        assert counters().get("faults.injected.store.write", 0) > 0

    def test_transient_read_errors_retry(
        self, tmp_path, rng, obs_run, monkeypatch
    ):
        data = rng.integers(0, 1000, (16, 16, 16)).astype("uint32")
        path = _roundtrip(tmp_path, "r.zarr", data)
        # deep retry budget: at p=0.4 a 4-attempt default can (seeded,
        # deterministically) exhaust on one of the 8 chunks
        monkeypatch.setenv("CTT_IO_RETRIES", "8")
        monkeypatch.setenv("CTT_IO_BACKOFF_BASE_S", "0.001")
        faults.configure("store.read:io_error:p=0.4;seed=2")
        got = file_reader(path, "r")["x"][:]
        faults.reset()
        np.testing.assert_array_equal(got, data)
        assert counters().get("store.io_retries", 0) > 0

    def test_torn_write_is_rewritten(self, tmp_path, rng, obs_run):
        """The torn action truncates the payload on disk and raises
        CorruptChunk; the shared retry rewrites the chunk in full."""
        data = rng.integers(0, 1000, (16, 16, 16)).astype("uint32")
        faults.configure("store.write:torn:once;seed=3")
        path = _roundtrip(tmp_path, "t.n5", data)
        faults.reset()
        np.testing.assert_array_equal(file_reader(path, "r")["x"][:], data)
        assert counters().get("faults.injected.store.write", 0) == 1
        assert counters().get("store.io_retries", 0) > 0

    def test_torn_chunk_on_disk_reads_as_corrupt_chunk(
        self, tmp_path, rng, monkeypatch
    ):
        """A truly torn chunk (crashed writer, no rewrite coming) fails the
        read as CorruptChunk — a clean, retryable block failure, not a
        numpy shape error deep in decode."""
        from cluster_tools_tpu.utils.store import CorruptChunk

        monkeypatch.setenv("CTT_IO_RETRIES", "1")
        monkeypatch.setenv("CTT_IO_BACKOFF_BASE_S", "0.001")
        data = rng.integers(0, 1000, (8, 8, 8)).astype("uint32")
        path = _roundtrip(tmp_path, "c.zarr", data, chunks=(8, 8, 8))
        chunk = os.path.join(path, "x", "0.0.0")
        payload = open(chunk, "rb").read()
        with open(chunk, "wb") as f:
            f.write(payload[: max(1, len(payload) // 3)])
        ds = file_reader(path, "r")["x"]
        with pytest.raises(CorruptChunk):
            ds.read_chunk((0, 0, 0))

    def test_atomic_write_unlinks_tmp_on_failure(self, tmp_path, monkeypatch):
        from cluster_tools_tpu.utils.store import atomic_write_bytes

        target = str(tmp_path / "meta.json")

        def boom(src, dst):
            raise OSError("replace failed")

        monkeypatch.setattr(os, "replace", boom)
        with pytest.raises(OSError):
            atomic_write_bytes(target, b"{}")
        monkeypatch.undo()
        # failed writes must not litter .tmpPID.TID files in shared stores
        assert os.listdir(str(tmp_path)) == []

    def test_atomic_write_fsyncs_tmp(self, tmp_path, monkeypatch):
        from cluster_tools_tpu.utils import store as store_mod

        synced = []
        monkeypatch.setattr(os, "fsync", lambda fd: synced.append(fd))
        monkeypatch.setattr(store_mod, "_FSYNC", True)
        store_mod.atomic_write_bytes(str(tmp_path / "s.json"), b"{}")
        assert synced, "tmp file must be fsynced before os.replace"


# --------------------------------------------------------------------------
# executor watchdog


class TestWatchdog:
    def test_hung_block_becomes_failed_block_then_retries(
        self, tmp_path, obs_run
    ):
        from cluster_tools_tpu.runtime.task import BlockTask

        class Hang(BlockTask):
            task_name = "hang"

            def get_shape(self):
                return (16, 16, 16)

            def process_block(self, block_id, blocking, config):
                pass  # the stall is injected at the executor.block site

        cfg.write_global_config(
            str(tmp_path / "configs"),
            {"block_shape": [8, 16, 16], "max_num_retries": 2,
             "retry_failure_fraction": 0.9, "block_deadline_s": 0.4},
        )
        # one stalled block must trip the watchdog (blocks queued behind
        # the hung worker may time out too — they all feed the retry loop),
        # then everything succeeds on retry (the `once` is consumed)
        faults.configure("executor.block:stall:ids=1,once,s=3;seed=5")
        t0 = time.monotonic()
        assert build([Hang(str(tmp_path / "tmp"), str(tmp_path / "configs"))])
        elapsed = time.monotonic() - t0
        assert elapsed < 3.0, "watchdog must not wait out the hung block"
        assert counters().get("executor.blocks_timed_out", 0) >= 1
        assert counters().get("task.blocks_retried", 0) >= 1
        status = json.load(open(
            str(tmp_path / "tmp" / "status" / "hang.status.json")
        ))
        assert status["complete"] and len(status["done"]) == 2

    def test_deadline_off_by_default(self):
        from cluster_tools_tpu.runtime.executor import block_deadline_s

        assert block_deadline_s({}) == 0.0
        assert block_deadline_s({"block_deadline_s": "garbage"}) == 0.0
        assert block_deadline_s({"block_deadline_s": 2.5}) == 2.5


# --------------------------------------------------------------------------
# peer barrier


class TestBarrier:
    def test_barrier_stall_is_survived_until_timeout(self, tmp_path):
        from cluster_tools_tpu.runtime.task import (
            FailedBlocksError, Target, Task,
        )

        class D(Task):
            task_name = "d"

        t = D(str(tmp_path / "tmp"))
        missing = Target(str(tmp_path / "tmp/status/peer.status.json"))
        faults.configure("task.barrier:stall:s=0.2,times=2;seed=0")
        t0 = time.monotonic()
        with pytest.raises(FailedBlocksError, match="timed out"):
            t._peer_wait([missing], 0.3, "peer that never comes")
        # both stalls fired before the (monotonic) deadline tripped
        assert time.monotonic() - t0 >= 0.4
        assert [s for s, _, _ in faults.decision_log()] == [
            "task.barrier", "task.barrier"
        ]


# --------------------------------------------------------------------------
# cluster: killed jobs + corrupt control files


def _write_stub_scheduler(folder):
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


class TestClusterChaos:
    def test_killed_job_recovers_via_resubmission(
        self, tmp_path, rng, monkeypatch
    ):
        """worker.job:kill dies before the status write (hard os._exit).
        The submitter's no-status branch marks the job's blocks failed and
        the task retry resubmits them; the cross-process once-latch
        (CTT_FAULT_STATE_DIR) keeps the resubmitted job alive."""
        from cluster_tools_tpu.workflows import UniqueWorkflow

        state_dir = str(tmp_path / "fault_state")
        monkeypatch.setenv(
            "CTT_FAULTS", "worker.job:kill:ids=0,once;seed=9"
        )
        monkeypatch.setenv("CTT_FAULT_STATE_DIR", state_dir)
        submit, queue = _write_stub_scheduler(str(tmp_path / "sched"))
        labels = rng.integers(0, 100, (16, 24, 24)).astype(np.uint64)
        path = str(tmp_path / "d.n5")
        file_reader(path).create_dataset(
            "seg", data=labels, chunks=(8, 12, 12)
        )
        config_dir = str(tmp_path / "configs")
        cfg.write_global_config(
            config_dir,
            {
                "block_shape": [8, 12, 12],
                "target": "slurm",
                "max_jobs": 3,
                "max_num_retries": 2,
                "retry_failure_fraction": 0.6,
                "poll_interval_s": 0.05,
                "sbatch_cmd": submit,
                "squeue_cmd": queue,
                "worker_env": WORKER_ENV,
            },
        )
        wf = UniqueWorkflow(
            str(tmp_path / "tmp"), config_dir, max_jobs=3,
            input_path=path, input_key="seg",
            output_path=path, output_key="uniques",
        )
        assert build([wf])
        np.testing.assert_array_equal(
            file_reader(path, "r")["uniques"][:], np.unique(labels)
        )
        # the kill really fired exactly once (latched across processes)
        latches = os.listdir(state_dir)
        assert latches == ["worker.job.0.fired0"]

    def test_corrupt_task_pkl_writes_failed_status(self, tmp_path):
        from cluster_tools_tpu.runtime.cluster_worker import (
            job_paths, run_job,
        )

        job_dir = str(tmp_path / "jobs")
        os.makedirs(job_dir)
        task_path, config_path, status_path = job_paths(job_dir, 0)
        with open(task_path, "wb") as f:
            f.write(b"this is not a pickle")
        with open(config_path, "w") as f:
            f.write('{"block_ids": [0], "shape": [8], "block_shape": [8]}')
        assert run_job(job_dir, 0) == 1
        status = json.load(open(status_path))
        assert status["setup_failed"] is True
        assert status["done"] == []
        assert "Traceback" in status["errors"]["setup"]

    def test_corrupt_job_config_writes_failed_status(self, tmp_path):
        from cluster_tools_tpu.runtime.cluster_worker import (
            job_paths, run_job,
        )

        job_dir = str(tmp_path / "jobs")
        os.makedirs(job_dir)
        task_path, config_path, status_path = job_paths(job_dir, 0)
        with open(task_path, "wb") as f:
            f.write(pickle.dumps("any picklable placeholder"))
        with open(config_path, "w") as f:
            f.write('{"block_ids": [0], TORN')
        assert run_job(job_dir, 0) == 1
        status = json.load(open(status_path))
        assert status["setup_failed"] is True and status["done"] == []

    def test_aggregate_surfaces_setup_error_on_job_blocks(self, tmp_path):
        from cluster_tools_tpu.runtime.cluster_executor import SlurmExecutor
        from cluster_tools_tpu.runtime.cluster_worker import job_paths

        job_dir = str(tmp_path / "jobs")
        os.makedirs(job_dir)
        _, _, status_path = job_paths(job_dir, 0)
        with open(status_path, "w") as f:
            json.dump({
                "done": [], "failed": [],
                "errors": {"setup": "Traceback: corrupt task.pkl"},
                "setup_failed": True,
            }, f)
        done, failed, errors = SlurmExecutor({})._aggregate(
            job_dir, 1, [3, 7]
        )
        assert done == [] and failed == [3, 7]
        assert "corrupt task.pkl" in errors[3]


# --------------------------------------------------------------------------
# collective fallback


class TestCollectiveFallback:
    def test_cc_falls_back_to_identical_local_labels(self, rng, obs_run):
        from cluster_tools_tpu.parallel.sharded import (
            sharded_connected_components,
        )

        mask = rng.random((16, 8, 8)) > 0.5
        ref = np.asarray(sharded_connected_components(mask, connectivity=1))
        faults.configure("collective.init:fail:once;seed=0")
        with pytest.warns(RuntimeWarning, match="falling back"):
            got = np.asarray(
                sharded_connected_components(mask, connectivity=1)
            )
        np.testing.assert_array_equal(got, ref)
        assert counters().get("sharded.fallback_local", 0) == 1
        assert counters().get("faults.injected.collective.init", 0) == 1

    def test_watershed_falls_back_to_identical_labels(self, rng, obs_run):
        from cluster_tools_tpu.parallel.sharded import (
            sharded_seeded_watershed,
        )

        hmap = rng.random((16, 8, 8)).astype("float32")
        seeds = np.zeros((16, 8, 8), dtype="int32")
        seeds[2, 2, 2] = 1
        seeds[12, 5, 5] = 2
        ref = np.asarray(sharded_seeded_watershed(hmap, seeds))
        faults.configure("collective.init:fail:once;seed=0")
        with pytest.warns(RuntimeWarning, match="falling back"):
            got = np.asarray(sharded_seeded_watershed(hmap, seeds))
        np.testing.assert_array_equal(got, ref)
        assert counters().get("sharded.fallback_local", 0) == 1

    def test_collective_execute_failure_is_loud(self, rng):
        from cluster_tools_tpu.parallel.sharded import (
            sharded_connected_components,
        )

        mask = rng.random((16, 8, 8)) > 0.5
        faults.configure("collective.execute:fail:once;seed=0")
        # a failure INSIDE the collective never silently degrades — peers
        # may already be in the program; it propagates to the task layer
        with pytest.raises(faults.FaultInjected):
            sharded_connected_components(mask, connectivity=1)


# --------------------------------------------------------------------------
# chaos end-to-end: workflow under seeded faults, byte-identical output


def _dir_digest(root):
    """Order-stable digest of every file under ``root`` (relpath + bytes):
    byte-identity of the chunk store, not just array equality."""
    h = hashlib.sha256()
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames.sort()
        for name in sorted(filenames):
            p = os.path.join(dirpath, name)
            h.update(os.path.relpath(p, root).encode())
            with open(p, "rb") as f:
                h.update(f.read())
    return h.hexdigest()


class TestChaosEndToEnd:
    def test_watershed_under_store_faults_is_byte_identical(
        self, tmp_path, rng, obs_run
    ):
        """The acceptance run: seeded store IO errors + one torn chunk
        write + one injected block failure, against the watershed
        workflow — output byte-identical to the fault-free run, recovery
        visible in the obs counters."""
        from scipy import ndimage

        from cluster_tools_tpu.workflows.watershed import WatershedWorkflow

        raw = ndimage.gaussian_filter(
            rng.random((24, 48, 48)), (1.0, 2.0, 2.0)
        )
        raw = ((raw - raw.min()) / (raw.max() - raw.min())).astype("float32")

        def run_ws(key, spec=None):
            path = str(tmp_path / f"{key}.n5")
            file_reader(path).create_dataset(
                "bnd", data=raw, chunks=(12, 24, 24)
            )
            config_dir = str(tmp_path / f"configs_{key}")
            cfg.write_global_config(
                config_dir,
                {"block_shape": [12, 24, 24], "max_num_retries": 3,
                 "retry_failure_fraction": 0.9},
            )
            cfg.write_config(config_dir, "watershed", {
                "threshold": 0.5, "sigma_seeds": 1.6,
                "size_filter": 10, "halo": [2, 6, 6],
            })
            wf = WatershedWorkflow(
                str(tmp_path / f"tmp_{key}"), config_dir,
                input_path=path, input_key="bnd",
                output_path=path, output_key="ws",
            )
            if spec:
                faults.configure(spec)
            try:
                assert build([wf])
            finally:
                faults.reset()
            return path

        ref_path = run_ws("ref")
        chaos_path = run_ws(
            "chaos",
            "store.write:io_error:p=0.05;store.read:io_error:p=0.02;"
            "store.write:torn:once;executor.block:fail:once;seed=1234",
        )

        ref = file_reader(ref_path, "r")["ws"][:]
        got = file_reader(chaos_path, "r")["ws"][:]
        np.testing.assert_array_equal(got, ref)
        # byte-identity of the stored output, chunk files included
        assert _dir_digest(os.path.join(chaos_path, "ws")) == _dir_digest(
            os.path.join(ref_path, "ws")
        )
        c = counters()
        assert c.get("faults.injected", 0) > 0
        assert c.get("store.io_retries", 0) > 0
        assert c.get("task.blocks_retried", 0) >= 1
