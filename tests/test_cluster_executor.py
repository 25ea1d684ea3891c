"""Slurm/LSF executor tests against a stub scheduler.

The reference has no scheduler mocks ("multi-node is tested by the same code
path with the target switched", SURVEY.md §4); this is the fake-scheduler
seam it lacked: a stand-in ``sbatch``/``bsub`` runs each job script
synchronously, the stand-in queue reports empty, and the whole
submit → poll → per-job status → aggregate path is exercised for real.
"""

import os
import stat

import numpy as np
import pytest

from cluster_tools_tpu.runtime import build, config as cfg
from cluster_tools_tpu.utils import file_reader


def _write_stub_scheduler(folder):
    """sbatch/bsub stand-in: strips scheduler flags, runs the job script
    synchronously.  squeue/bjobs stand-in: reports no queued jobs."""
    os.makedirs(folder, exist_ok=True)
    submit = os.path.join(folder, "stub_submit")
    with open(submit, "w") as f:
        f.write(
            "#!/bin/bash\n"
            "# last argument is the job script\n"
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
    # workers run on the cpu backend: they must not claim a chip
    "JAX_PLATFORMS": "cpu",
}


@pytest.mark.parametrize("target", ["slurm", "lsf"])
def test_cluster_target_runs_workflow(tmp_path, rng, target):
    from cluster_tools_tpu.workflows import UniqueWorkflow

    submit, queue = _write_stub_scheduler(str(tmp_path / "sched"))
    labels = rng.integers(0, 100, (16, 24, 24)).astype(np.uint64)
    path = str(tmp_path / "d.n5")
    file_reader(path).create_dataset("seg", data=labels, chunks=(8, 12, 12))
    config_dir = str(tmp_path / "configs")
    tmp_folder = str(tmp_path / "tmp")
    cfg.write_global_config(
        config_dir,
        {
            "block_shape": [8, 12, 12],
            "target": target,
            "max_jobs": 3,
            "poll_interval_s": 0.05,
            "sbatch_cmd": submit,
            "squeue_cmd": queue,
            "bsub_cmd": submit,
            "bjobs_cmd": queue,
            "worker_env": WORKER_ENV,
        },
    )
    wf = UniqueWorkflow(
        tmp_folder, config_dir, max_jobs=3,
        input_path=path, input_key="seg",
        output_path=path, output_key="uniques",
    )
    assert build([wf])
    got = file_reader(path, "r")["uniques"][:]
    np.testing.assert_array_equal(got, np.unique(labels))
    # the per-block task really went through scheduler jobs
    job_dir = os.path.join(tmp_folder, "cluster_jobs", "find_uniques")
    statuses = [f for f in os.listdir(job_dir) if f.endswith(".status.json")]
    assert 1 <= len(statuses) <= 3


def test_cluster_failure_surfaces_failed_blocks(tmp_path, rng):
    """A worker whose task raises reports its blocks failed; the task layer
    then raises FailedBlocksError (no silent success)."""
    from cluster_tools_tpu.runtime.task import FailedBlocksError
    from cluster_tools_tpu.tasks.ilastik import IlastikPredictionTask

    submit, queue = _write_stub_scheduler(str(tmp_path / "sched"))
    path = str(tmp_path / "d.n5")
    file_reader(path).create_dataset(
        "raw", data=rng.random((8, 8, 8)).astype(np.float32)
    )
    config_dir = str(tmp_path / "configs")
    cfg.write_global_config(
        config_dir,
        {
            "block_shape": [8, 8, 8],
            "target": "slurm",
            "poll_interval_s": 0.05,
            "sbatch_cmd": submit,
            "squeue_cmd": queue,
            "worker_env": WORKER_ENV,
        },
    )
    # project exists so DAG-build passes; the executable is missing, so every
    # worker block fails at run time
    ilastik_folder = str(tmp_path / "noilastik")
    os.makedirs(ilastik_folder)
    task = IlastikPredictionTask(
        str(tmp_path / "tmp"), config_dir,
        input_path=path, input_key="raw",
        ilastik_folder=ilastik_folder,
        ilastik_project=path,
    )
    with pytest.raises((FailedBlocksError, RuntimeError)):
        task.run()


def test_multihost_topology_two_processes(tmp_path, rng):
    """Multi-host scale-out (SURVEY.md §2.9): the SAME driver script runs as
    two real OS processes sharing tmp/config dirs; blocks shard round-robin,
    per-process status files barrier the merge, the merge runs on process 0
    while process 1 waits — combined output identical to a numpy oracle."""
    import subprocess
    import sys

    labels = rng.integers(0, 500, (16, 24, 24)).astype(np.uint64) * 3
    path = str(tmp_path / "d.n5")
    file_reader(path).create_dataset("seg", data=labels, chunks=(4, 12, 12))
    config_dir = str(tmp_path / "configs")
    tmp_folder = str(tmp_path / "tmp")
    cfg.write_global_config(
        config_dir,
        {"block_shape": [4, 12, 12], "num_processes": 2,
         "peer_wait_timeout_s": 120.0},
    )
    script = str(tmp_path / "driver.py")
    with open(script, "w") as f:
        f.write(
            "import sys\n"
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
    pkg_root = os.path.dirname(
        os.path.dirname(os.path.abspath(cfg.__file__))
    )
    env["PYTHONPATH"] = (
        os.path.dirname(pkg_root) + os.pathsep + env.get("PYTHONPATH", "")
    )

    procs = []
    for pid in range(2):
        penv = dict(env)
        penv["CTT_PROCESS_ID"] = str(pid)
        procs.append(
            subprocess.Popen(
                [sys.executable, script], env=penv,
                stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            )
        )
    for p in procs:
        out, err = p.communicate(timeout=300)
        assert p.returncode == 0, err.decode()[-2000:]
    got = file_reader(path, "r")["uniques"][:]
    np.testing.assert_array_equal(got, np.unique(labels))
    # both processes really did disjoint shares
    statuses = os.listdir(os.path.join(tmp_folder, "status"))
    assert "find_uniques.p0.status.json" in statuses
    assert "find_uniques.p1.status.json" in statuses
    import json as _json

    s0 = _json.load(open(os.path.join(tmp_folder, "status",
                                      "find_uniques.p0.status.json")))
    s1 = _json.load(open(os.path.join(tmp_folder, "status",
                                      "find_uniques.p1.status.json")))
    assert s0["done"] and s1["done"]
    assert not set(s0["done"]) & set(s1["done"])


def test_peer_abort_fails_waiters_fast(tmp_path):
    """A peer that recorded an abort fails the barrier immediately (not after
    the full peer_wait_timeout_s)."""
    import time

    from cluster_tools_tpu.runtime.task import FailedBlocksError, Target, Task

    cfg.write_global_config(str(tmp_path / "configs"), {"num_processes": 2})
    t = Task(str(tmp_path / "tmp"), str(tmp_path / "configs"))
    aborted = Target(str(tmp_path / "tmp/status/task.p1.status.json"))
    aborted.write({"complete": False, "aborted": True, "error": "boom"})
    t0 = time.time()
    with pytest.raises(FailedBlocksError, match="peer process aborted"):
        t._peer_wait([aborted], timeout_s=60.0, what="peers")
    assert time.time() - t0 < 5.0  # fail-fast, not the 60s timeout
