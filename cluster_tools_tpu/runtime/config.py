"""Two-level JSON configuration: one global config + one config per task.

Keeps the reference's config ergonomics (SURVEY.md §5 "Config / flag system";
reference cluster_tasks.py:180-248): ``global.config`` carries volume decomposition
and scheduling knobs, ``<task_name>.config`` carries per-task behavior, and task
*parameters* (paths/keys) stay constructor arguments — config files carry behavior,
parameters carry wiring.

TPU-specific knobs replace the reference's Slurm fields: ``target`` selects the
execution backend (``tpu`` = batched jit dispatch over a device mesh, ``local`` =
host loop, the parity oracle), ``device_batch_size`` controls how many blocks ride
one device dispatch.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, Optional

# reference default production block shape: cluster_tasks.py:225
DEFAULT_GLOBAL_CONFIG: Dict[str, Any] = {
    "block_shape": [50, 512, 512],
    "roi_begin": None,
    "roi_end": None,
    "block_list_path": None,
    "target": "local",
    "max_jobs": 1,
    "max_num_retries": 0,
    "retry_failure_fraction": 0.5,
    # None resolves, in order: CTT_DEVICE_BATCH env, the measured pin in
    # tools/chip_modes.json (backend-tagged), then the backend default —
    # None: a measured pin, else 1 block per device per dispatch
    # (runtime/executor.py DEVICE_BATCH: vmapped while_loops run the
    # slowest block's rounds for the whole batch)
    "device_batch_size": None,
    # batches in flight on the tpu target: depth d overlaps batch i+1's host
    # chunk IO with batch i's device execution (1 = serial loop)
    "pipeline_depth": 2,
    # ctt-stream: workflows may declare fused task chains (one streaming
    # pass, elided intermediates); False forces task-at-a-time execution
    # everywhere (CTT_STREAM_FUSION=0 is the per-process override)
    "stream_fusion": True,
    # ctt-hbm aggregated dispatch: read payloads per fused device dispatch
    # in the staged pipeline (the coarse-CC (n_tiles, ...) stacked shape
    # generalized to the split-protocol kernels).  None resolves
    # CTT_HBM_STACK, else 1 — the pre-hbm one-dispatch-per-batch shape;
    # host IO granularity (read/write batches) is unchanged either way.
    "hbm_stack": None,
    # ctt-steal: cluster-job block assignment — None = auto ("steal" on
    # multi-job runs of retryable tasks, "static" otherwise); "static"
    # restores the reference's frozen round-robin split byte-identically.
    # CTT_SCHED is the per-process override.  Workers pull batches of
    # steal_batch_size blocks (None = ~4 pulls per worker) under leases
    # renewed every steal_lease_s seconds (None = the heartbeat cadence);
    # steal_duplicate enables straggler re-dispatch (first-writer-wins).
    "sched": None,
    "steal_batch_size": None,
    "steal_lease_s": None,
    "steal_duplicate": True,
    "devices": None,  # None = all jax.devices()
    "seed": 0,
    # multi-host scale-out: run the SAME driver script on every host with
    # process_id 0..num_processes-1 (or set CTT_PROCESS_ID / CTT_NUM_PROCESSES
    # in each host's environment).  Blocks shard round-robin over processes,
    # the chunked store on the shared filesystem is the data plane, and
    # single-shot merge tasks run on process 0 while peers wait on its status
    # file — the DCN-free control plane the reference uses (SURVEY.md §2.9)
    "num_processes": 1,
    "process_id": 0,
    "peer_wait_timeout_s": 3600.0,
}


def process_topology(gconf: Dict[str, Any]):
    """(process_id, num_processes) from the global config, overridable via the
    CTT_PROCESS_ID / CTT_NUM_PROCESSES environment (one driver per host)."""
    num = int(os.environ.get("CTT_NUM_PROCESSES", gconf.get("num_processes", 1) or 1))
    pid = int(os.environ.get("CTT_PROCESS_ID", gconf.get("process_id", 0) or 0))
    if not 0 <= pid < max(num, 1):
        raise ValueError(f"process_id {pid} out of range for {num} processes")
    return pid, max(num, 1)

# ctt-serve: the persistent serving daemon's knobs.  Lives here (not in
# serve/) because it follows the same two-level JSON convention: the
# daemon reads ``serve.config`` from its state dir over these defaults,
# exactly like tasks read ``<task>.config`` over DEFAULT_TASK_CONFIG.
DEFAULT_SERVE_CONFIG: Dict[str, Any] = {
    "host": "127.0.0.1",   # loopback only: the daemon is a local submission
    "port": 0,             # endpoint (0 = ephemeral, recorded in serve.json)
    # executor threads running builds concurrently.  1 keeps device
    # dispatch strictly serialized (the deterministic default); raising it
    # interleaves independent jobs' host stages on one warm process.
    "concurrency": 1,
    # admission control: total unfinished jobs (queued + running) the
    # daemon accepts before rejecting submissions with 429
    "max_queue_depth": 64,
    # per-tenant in-flight ceiling (None disables); "tenant_quotas" maps
    # tenant name -> override for heavier/lighter tenants
    "tenant_quota": 8,
    "tenant_quotas": {},
    # job-lease renewal cadence (None = the heartbeat cadence): a daemon
    # killed mid-job leaves a lease that goes stale after 3x this and is
    # requeued by the next daemon on the same state dir
    "lease_s": None,
    # SIGTERM drain: how long to wait for in-flight jobs before dying
    # anyway (queued jobs are durable either way)
    "drain_timeout_s": 300.0,
    # ctt-fleet: retry budget per job — a job may burn this many lease
    # generations (daemon deaths / crashes mid-job) before the next
    # would-be claimant quarantines it as a failed result instead of
    # re-executing (<= 0 restores unbounded retries)
    "max_job_gens": 3,
    # fleet identity (None = <host>-<pid>-<n>); stamps leases and names
    # the daemon.<id>.json fleet heartbeat in the state dir
    "daemon_id": None,
    # ctt-microbatch: cross-tenant job aggregation.  After claiming a
    # job, the executor holds it open for up to microbatch_window_s,
    # coalescing queued jobs with the same microbatch_signature (same
    # workflow/type/configs) into ONE stacked dispatch of at most
    # microbatch_max_jobs members — claimed in (-priority, seq) order at
    # window close, so a higher-priority arrival during the window beats
    # lower-priority queue residents.  p99 latency of an aggregated job
    # is bounded by the window; 0 disables (exact per-job dispatch).
    "microbatch_window_s": 0.02,
    "microbatch_max_jobs": 8,
    # ctt-hbm warm device-buffer cache budget (MB) for the daemon's
    # ExecutionContext: back-to-back jobs on the same volume reuse the
    # HBM-resident uploads instead of re-transferring.  0 disables (the
    # plain cold-process default); plain processes opt in via
    # CTT_HBM_CACHE_MB instead.
    "hbm_cache_mb": 512.0,
}


def serve_config(state_dir: Optional[str]) -> Dict[str, Any]:
    """Daemon config: ``serve.config`` in the state dir over the defaults
    (same merge discipline as :func:`global_config`)."""
    conf = dict(DEFAULT_SERVE_CONFIG)
    conf.update(read_config(state_dir, "serve"))
    return conf


DEFAULT_TASK_CONFIG: Dict[str, Any] = {
    "threads_per_job": 1,
    # host threads for a block batch's chunk reads (gzip-decode bound;
    # set 1 for backends where concurrency buys nothing, e.g. hdf5)
    "read_threads": 4,
    "time_limit": 60,
    "mem_limit": 2,
}


def _config_path(config_dir: str, name: str) -> str:
    from ..utils.store_backend import backend_for

    backend = backend_for(config_dir)
    return backend.join(config_dir, f"{name}.config")


def write_config(config_dir: str, name: str, conf: Dict[str, Any]) -> str:
    from ..utils.store_backend import backend_for

    backend = backend_for(config_dir)
    backend.makedirs(config_dir)
    path = _config_path(config_dir, name)
    # config dirs are shared state (serve daemons rewrite configs between
    # jobs, workers re-read them) — a reader must never see a torn file;
    # backend writes are atomic on POSIX and single-object PUTs remotely
    backend.write_bytes(
        path, json.dumps(conf, indent=2, sort_keys=True).encode()
    )
    return path

def write_global_config(config_dir: str, conf: Optional[Dict[str, Any]] = None) -> str:
    merged = dict(DEFAULT_GLOBAL_CONFIG)
    if conf:
        merged.update(conf)
    return write_config(config_dir, "global", merged)


def read_config(config_dir: Optional[str], name: str) -> Dict[str, Any]:
    if config_dir is None:
        return {}
    from ..utils.store_backend import backend_for

    backend = backend_for(config_dir)
    path = _config_path(config_dir, name)
    try:
        return json.loads(backend.read_bytes(path).decode())
    except FileNotFoundError:
        return {}


def global_config(config_dir: Optional[str]) -> Dict[str, Any]:
    conf = dict(DEFAULT_GLOBAL_CONFIG)
    conf.update(read_config(config_dir, "global"))
    return conf


def task_config(
    config_dir: Optional[str], task_name: str, defaults: Optional[Dict[str, Any]] = None
) -> Dict[str, Any]:
    conf = dict(DEFAULT_TASK_CONFIG)
    if defaults:
        conf.update(defaults)
    conf.update(read_config(config_dir, task_name))
    return conf
