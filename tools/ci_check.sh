#!/usr/bin/env bash
# CI gate: static analysis first (fast, catches invariant violations before
# any test runs), then the tier-1 test selection from ROADMAP.md.
#
# Usage: tools/ci_check.sh            (from the repo root or anywhere)
set -uo pipefail

repo_root="$(cd "$(dirname "${BASH_SOURCE[0]}")/.." && pwd)"
cd "$repo_root"

echo "== ctt-lint (python -m cluster_tools_tpu.analysis --fail-on-findings) =="
JAX_PLATFORMS=cpu python -m cluster_tools_tpu.analysis --fail-on-findings
lint_rc=$?
if [ "$lint_rc" -ne 0 ]; then
    echo "ctt-lint failed (rc=$lint_rc) — fix the findings or suppress" \
         "documented false positives with '# ctt: noqa[CTTxxx] reason'" >&2
    exit "$lint_rc"
fi

echo "== ctt-obs smoke (traced workflow -> summarize; malformed -> nonzero) =="
obs_tmp="$(mktemp -d)"
trap 'rm -rf "$obs_tmp"' EXIT
JAX_PLATFORMS=cpu CTT_TRACE_DIR="$obs_tmp/trace" CTT_RUN_ID=ci_smoke \
    python - <<'PY'
import numpy as np
from cluster_tools_tpu.runtime import build, config as cfg
from cluster_tools_tpu.utils import file_reader
from cluster_tools_tpu.workflows import UniqueWorkflow
import os, tempfile
td = tempfile.mkdtemp()
path = os.path.join(td, "d.n5")
rng = np.random.default_rng(0)
file_reader(path).create_dataset(
    "seg", data=rng.integers(0, 50, (8, 16, 16)).astype(np.uint64),
    chunks=(4, 8, 8),
)
config_dir = os.path.join(td, "configs")
cfg.write_global_config(config_dir, {"block_shape": [4, 8, 8]})
wf = UniqueWorkflow(os.path.join(td, "tmp"), config_dir,
                    input_path=path, input_key="seg",
                    output_path=path, output_key="u")
assert build([wf])
PY
smoke_rc=$?
if [ "$smoke_rc" -ne 0 ]; then
    echo "obs smoke workflow failed (rc=$smoke_rc)" >&2
    exit "$smoke_rc"
fi
# summarize exits 0 only when the run holds >= 1 task span
JAX_PLATFORMS=cpu python -m cluster_tools_tpu.obs summarize \
    "$obs_tmp/trace/ci_smoke"
sum_rc=$?
if [ "$sum_rc" -ne 0 ]; then
    echo "obs summarize failed (rc=$sum_rc): traced run has no task spans" \
         "or is malformed" >&2
    exit "$sum_rc"
fi
# a malformed event file must exit nonzero (truncated/corrupt traces fail
# loudly instead of summarizing garbage)
echo "not json" >> "$obs_tmp/trace/ci_smoke/$(ls "$obs_tmp/trace/ci_smoke" \
    | grep '^spans\.' | head -1)"
if JAX_PLATFORMS=cpu python -m cluster_tools_tpu.obs summarize \
    "$obs_tmp/trace/ci_smoke" >/dev/null 2>&1; then
    echo "obs summarize accepted a malformed event file" >&2
    exit 1
fi

echo "== ctt-io pipeline smoke (depth-3 staged dispatch -> stage counters) =="
JAX_PLATFORMS=cpu CTT_TRACE_DIR="$obs_tmp/trace" CTT_RUN_ID=ci_pipeline \
    python - <<'PY'
import json, os, tempfile
import numpy as np
from cluster_tools_tpu.obs import metrics as obs_metrics, trace as obs_trace
from cluster_tools_tpu.runtime import build, config as cfg
from cluster_tools_tpu.tasks.threshold import ThresholdTask
from cluster_tools_tpu.utils import file_reader

td = tempfile.mkdtemp()
path = os.path.join(td, "d.n5")
rng = np.random.default_rng(0)
file_reader(path).create_dataset(
    "x", data=rng.random((16, 16, 16)).astype("float32"), chunks=(4, 8, 8)
)
config_dir = os.path.join(td, "configs")
cfg.write_global_config(
    config_dir,
    {"block_shape": [4, 8, 8], "target": "tpu", "device_batch_size": 1,
     "devices": [0], "pipeline_depth": 3},
)
t = ThresholdTask(os.path.join(td, "tmp"), config_dir,
                  input_path=path, input_key="x",
                  output_path=path, output_key="y")
assert build([t])
snap = obs_metrics.snapshot()["counters"]
stage_keys = [k for k in snap if k.startswith("executor.stage_")]
missing = [k for k in (
    "executor.stage_batches", "executor.stage_read_s",
    "executor.stage_compute_s", "executor.stage_write_s",
) if snap.get(k, 0) <= 0]
assert not missing, f"stage counters absent/zero: {missing} (have {stage_keys})"
obs_trace.flush()
print("pipeline smoke ok:",
      json.dumps({k: round(snap[k], 4) for k in sorted(stage_keys)}))
PY
pipe_rc=$?
if [ "$pipe_rc" -ne 0 ]; then
    echo "pipeline smoke failed (rc=$pipe_rc): depth-3 staged dispatch did" \
         "not run or stage counters missing" >&2
    exit "$pipe_rc"
fi
# the traced pipeline run must summarize cleanly too
JAX_PLATFORMS=cpu python -m cluster_tools_tpu.obs summarize \
    "$obs_tmp/trace/ci_pipeline"
pipe_sum_rc=$?
if [ "$pipe_sum_rc" -ne 0 ]; then
    echo "obs summarize failed on the pipeline smoke run (rc=$pipe_sum_rc)" >&2
    exit "$pipe_sum_rc"
fi

echo "== ctt-fault chaos smoke (seeded store faults + killed worker job) =="
chaos_tmp="$(mktemp -d)"
JAX_PLATFORMS=cpu CTT_TRACE_DIR="$obs_tmp/trace" CTT_RUN_ID=ci_chaos \
CTT_FAULTS="store.write:io_error:p=0.15;store.read:io_error:p=0.05;store.write:torn:once;worker.job:kill:ids=0,once;seed=42" \
CTT_FAULT_STATE_DIR="$chaos_tmp/fault_state" \
    python - "$chaos_tmp" <<'PY'
import hashlib, json, os, stat, sys

# the baseline run must be fault-free INCLUDING its worker subprocesses,
# which inherit this process's environment — pop the spec, re-arm later
CHAOS_SPEC = os.environ.pop("CTT_FAULTS")

import numpy as np
from scipy import ndimage

from cluster_tools_tpu import faults
from cluster_tools_tpu.obs import metrics as obs_metrics, trace as obs_trace
from cluster_tools_tpu.runtime import build, config as cfg
from cluster_tools_tpu.utils import file_reader
from cluster_tools_tpu.workflows.watershed import WatershedWorkflow

td = sys.argv[1]

# stub scheduler (the fake-sbatch seam from tests/test_cluster_executor.py)
sched = os.path.join(td, "sched")
os.makedirs(sched, exist_ok=True)
submit, queue = os.path.join(sched, "submit"), os.path.join(sched, "queue")
with open(submit, "w") as f:
    f.write('#!/bin/bash\nscript="${@: -1}"\nbash "$script" >/dev/null 2>&1\n'
            'echo "Submitted batch job 1"\n')
with open(queue, "w") as f:
    f.write("#!/bin/bash\nexit 0\n")
for p in (submit, queue):
    os.chmod(p, os.stat(p).st_mode | stat.S_IEXEC)

rng = np.random.default_rng(0)
raw = ndimage.gaussian_filter(rng.random((24, 48, 48)), (1.0, 2.0, 2.0))
raw = ((raw - raw.min()) / (raw.max() - raw.min())).astype("float32")


def run_ws(key, spec=None):
    if spec is None:
        os.environ.pop("CTT_FAULTS", None)
    else:
        os.environ["CTT_FAULTS"] = spec
    faults.configure()
    path = os.path.join(td, f"{key}.n5")
    file_reader(path).create_dataset("bnd", data=raw, chunks=(12, 24, 24))
    config_dir = os.path.join(td, f"configs_{key}")
    cfg.write_global_config(config_dir, {
        "block_shape": [12, 24, 24], "target": "slurm", "max_jobs": 3,
        "max_num_retries": 3, "retry_failure_fraction": 0.7,
        "poll_interval_s": 0.05, "sbatch_cmd": submit, "squeue_cmd": queue,
        "worker_env": {"JAX_PLATFORMS": "cpu"},
    })
    cfg.write_config(config_dir, "watershed", {
        "threshold": 0.5, "sigma_seeds": 1.6, "size_filter": 10,
        "halo": [2, 6, 6],
    })
    wf = WatershedWorkflow(
        os.path.join(td, f"tmp_{key}"), config_dir, max_jobs=3,
        input_path=path, input_key="bnd",
        output_path=path, output_key="ws",
    )
    try:
        assert build([wf]), f"{key} watershed build failed"
    finally:
        faults.reset()
        os.environ.pop("CTT_FAULTS", None)
    return path


def digest(root):
    h = hashlib.sha256()
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames.sort()
        for name in sorted(filenames):
            p = os.path.join(dirpath, name)
            h.update(os.path.relpath(p, root).encode())
            with open(p, "rb") as f:
                h.update(f.read())
    return h.hexdigest()


ref = run_ws("ref")
chaos = run_ws("chaos", CHAOS_SPEC)

np.testing.assert_array_equal(
    file_reader(chaos, "r")["ws"][:], file_reader(ref, "r")["ws"][:]
)
assert digest(os.path.join(chaos, "ws")) == digest(os.path.join(ref, "ws")), \
    "chaos output not byte-identical to the fault-free run"

# recovery must be VISIBLE: sum counters over the driver + every worker
obs_metrics.flush()
totals = {}
run_dir = obs_trace.run_dir()
for name in os.listdir(run_dir):
    if name.startswith("metrics.p"):
        with open(os.path.join(run_dir, name)) as f:
            for k, v in json.load(f)["counters"].items():
                totals[k] = totals.get(k, 0) + v
assert totals.get("faults.injected", 0) > 0, f"no faults injected: {totals}"
assert totals.get("store.io_retries", 0) > 0, f"no IO retries: {totals}"
# the killed worker job really died (latched once across resubmissions)
latches = os.listdir(os.environ["CTT_FAULT_STATE_DIR"])
assert any(l.startswith("worker.job.") for l in latches), latches
print("chaos smoke ok:", json.dumps({
    k: round(v, 2) for k, v in sorted(totals.items())
    if k.startswith(("faults.", "store.io_retries"))
}))
PY
chaos_rc=$?
rm -rf "$chaos_tmp"
if [ "$chaos_rc" -ne 0 ]; then
    echo "chaos smoke failed (rc=$chaos_rc): fault-injected watershed run" \
         "did not recover to a byte-identical output" >&2
    exit "$chaos_rc"
fi

echo "== ctt-watch smoke (live watch during a stub-scheduler run; kill -> stall) =="
watch_tmp="$obs_tmp/watch"
mkdir -p "$watch_tmp"
cat > "$obs_tmp/watch_driver.py" <<'PY'
import os, stat, sys
import numpy as np
from scipy import ndimage
from cluster_tools_tpu.runtime import build, config as cfg
from cluster_tools_tpu.utils import file_reader
from cluster_tools_tpu.workflows.watershed import WatershedWorkflow

td = sys.argv[1]
sched = os.path.join(td, "sched")
os.makedirs(sched, exist_ok=True)
submit, queue = os.path.join(sched, "submit"), os.path.join(sched, "queue")
with open(submit, "w") as f:
    f.write('#!/bin/bash\nscript="${@: -1}"\nbash "$script" >/dev/null 2>&1\n'
            'echo "Submitted batch job 1"\n')
with open(queue, "w") as f:
    f.write("#!/bin/bash\nexit 0\n")
for p in (submit, queue):
    os.chmod(p, os.stat(p).st_mode | stat.S_IEXEC)

rng = np.random.default_rng(0)
raw = ndimage.gaussian_filter(rng.random((16, 32, 32)), (1.0, 2.0, 2.0))
raw = ((raw - raw.min()) / (raw.max() - raw.min())).astype("float32")
path = os.path.join(td, "ws.n5")
file_reader(path).create_dataset("bnd", data=raw, chunks=(8, 16, 16))
config_dir = os.path.join(td, "configs")
cfg.write_global_config(config_dir, {
    "block_shape": [8, 16, 16], "target": "slurm", "max_jobs": 2,
    "max_num_retries": 3, "retry_failure_fraction": 0.9,
    "poll_interval_s": 0.05, "sbatch_cmd": submit, "squeue_cmd": queue,
    "worker_env": {"JAX_PLATFORMS": "cpu"},
})
cfg.write_config(config_dir, "watershed", {
    "threshold": 0.5, "sigma_seeds": 1.6, "size_filter": 10,
    "halo": [2, 4, 4],
})
wf = WatershedWorkflow(
    os.path.join(td, "tmp"), config_dir, max_jobs=2,
    input_path=path, input_key="bnd",
    output_path=path, output_key="ws",
)
assert build([wf]), "watch smoke watershed build failed"
PY

# 1) healthy run in the background; `watch --once` must observe nonzero
#    progress (exit 0) while/after it runs — the live contract
JAX_PLATFORMS=cpu PYTHONPATH="$repo_root${PYTHONPATH:+:$PYTHONPATH}" \
CTT_TRACE_DIR="$obs_tmp/trace" CTT_RUN_ID=ci_watch CTT_HEARTBEAT_S=0.2 \
    python "$obs_tmp/watch_driver.py" "$watch_tmp/healthy" \
    > "$watch_tmp/driver.log" 2>&1 &
watch_driver_pid=$!
watch_ok=1
for _ in $(seq 1 240); do
    if JAX_PLATFORMS=cpu python -m cluster_tools_tpu.obs watch --once \
        "$obs_tmp/trace/ci_watch" >/dev/null 2>&1; then
        watch_ok=0
        break
    fi
    sleep 0.5
done
wait "$watch_driver_pid"
watch_run_rc=$?
if [ "$watch_run_rc" -ne 0 ]; then
    cat "$watch_tmp/driver.log" >&2
    echo "watch smoke watershed run failed (rc=$watch_run_rc)" >&2
    exit "$watch_run_rc"
fi
if [ "$watch_ok" -ne 0 ]; then
    echo "obs watch --once never observed progress during the run" >&2
    exit 1
fi
JAX_PLATFORMS=cpu python -m cluster_tools_tpu.obs watch --once \
    "$obs_tmp/trace/ci_watch"
# the OpenMetrics exposition must parse (prometheus_client if available,
# grammar check otherwise) — via a file: a heredoc would steal the
# validator's stdin from the pipe
JAX_PLATFORMS=cpu python -m cluster_tools_tpu.obs prom \
    "$obs_tmp/trace/ci_watch" > "$watch_tmp/exposition.txt"
prom_gen_rc=$?
if [ "$prom_gen_rc" -ne 0 ]; then
    echo "obs prom failed (rc=$prom_gen_rc)" >&2
    exit "$prom_gen_rc"
fi
python - "$watch_tmp/exposition.txt" <<'PY'
import re, sys
with open(sys.argv[1]) as f:
    text = f.read()
lines = text.splitlines()
assert lines and lines[-1] == "# EOF", "exposition must end with # EOF"
try:
    from prometheus_client.openmetrics.parser import (
        text_string_to_metric_families,
    )
    families = list(text_string_to_metric_families(text))
    assert families, "no metric families in exposition"
except ImportError:
    sample = re.compile(
        r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? -?[0-9.+eEinfa]+$")
    meta = re.compile(r"^# (TYPE [a-zA-Z_:][a-zA-Z0-9_:]* \w+|HELP .+|EOF)$")
    for line in lines:
        assert sample.match(line) or meta.match(line), f"bad line: {line}"
print("prom exposition ok")
PY
prom_rc=$?
if [ "$prom_rc" -ne 0 ]; then
    echo "obs prom output is not valid OpenMetrics (rc=$prom_rc)" >&2
    exit "$prom_rc"
fi

# 2) worker-kill run (ctt-fault and ctt-watch validating each other): the
#    killed job's heartbeat goes stale and `--fail-on-stall` must exit 4 —
#    polled DURING the run (the flag should land before task completion;
#    the stale file persists, so a post-run check is the deterministic
#    fallback if the run finishes between polls)
JAX_PLATFORMS=cpu PYTHONPATH="$repo_root${PYTHONPATH:+:$PYTHONPATH}" \
CTT_TRACE_DIR="$obs_tmp/trace" CTT_RUN_ID=ci_watch_kill CTT_HEARTBEAT_S=0.2 \
CTT_FAULTS="worker.job:kill:ids=0,once;seed=7" \
CTT_FAULT_STATE_DIR="$watch_tmp/fault_state" \
    python "$obs_tmp/watch_driver.py" "$watch_tmp/kill" \
    > "$watch_tmp/kill_driver.log" 2>&1 &
kill_driver_pid=$!
stall_seen=1
while kill -0 "$kill_driver_pid" 2>/dev/null; do
    JAX_PLATFORMS=cpu python -m cluster_tools_tpu.obs watch --once \
        --fail-on-stall "$obs_tmp/trace/ci_watch_kill" >/dev/null 2>&1
    if [ $? -eq 4 ]; then
        stall_seen=0
        echo "stale worker flagged while the run was still in flight"
        break
    fi
    sleep 0.5
done
wait "$kill_driver_pid"
kill_rc=$?
if [ "$kill_rc" -ne 0 ]; then
    cat "$watch_tmp/kill_driver.log" >&2
    echo "worker-kill watershed run did not recover (rc=$kill_rc)" >&2
    exit "$kill_rc"
fi
JAX_PLATFORMS=cpu python -m cluster_tools_tpu.obs watch --once \
    --fail-on-stall "$obs_tmp/trace/ci_watch_kill"
stall_rc=$?
if [ "$stall_rc" -ne 4 ]; then
    echo "obs watch --fail-on-stall exited $stall_rc (wanted 4): the" \
         "killed worker's stale heartbeat was not flagged" >&2
    exit 1
fi
if [ "$stall_seen" -ne 0 ]; then
    echo "note: stall only flagged post-run (run finished between polls)"
fi
echo "watch smoke ok: progress seen live, prom parsed, stale worker -> rc 4"

echo "== ctt-cc smoke (coarse kernel parity + tile-bounded rounds) =="
JAX_PLATFORMS=cpu PYTHONPATH="$repo_root${PYTHONPATH:+:$PYTHONPATH}" \
    python - <<'PY'
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
from cluster_tools_tpu.ops import cc

# parity: the coarse kernel must be BIT-exact with the numpy oracle on the
# serpentine worst case and a random fixture
for mask in (
    cc.serpentine_mask((4, 64, 64)),
    np.random.default_rng(0).random((12, 24, 24)) < 0.5,
):
    ref, n_ref = cc.connected_components_np(mask)
    got, n = cc.connected_components(jnp.asarray(mask), coarse_tile=(4, 16, 16))
    assert int(n) == n_ref, (int(n), n_ref)
    np.testing.assert_array_equal(np.asarray(got), ref)

# iteration contract: tile-bounded rounds strictly below the flat kernel's
# diameter-bounded count on the serpentine corridor
serp = jnp.asarray(cc.serpentine_mask((4, 64, 64)))
_, it_flat = cc.connected_components_raw_with_iters(serp)
_, stats = cc.connected_components_coarse_raw(serp, 1, None, False, (4, 16, 16))
it_coarse = int(stats["fixpoint_iters"])
assert it_coarse < int(it_flat), (it_coarse, int(it_flat))
print(f"cc smoke ok: parity exact, serpentine rounds {int(it_flat)} -> {it_coarse}")
PY
cc_rc=$?
if [ "$cc_rc" -ne 0 ]; then
    echo "ctt-cc smoke failed (rc=$cc_rc): coarse kernel parity or the" \
         "round contract regressed" >&2
    exit "$cc_rc"
fi

echo "== ctt-stream smoke (fused chain parity + lower store reads) =="
stream_tmp="$(mktemp -d)"
cat > "$stream_tmp/stream_driver.py" <<'PY'
import os, stat, sys
import numpy as np
from scipy import ndimage
from cluster_tools_tpu.runtime import build, config as cfg
from cluster_tools_tpu.utils import file_reader
from cluster_tools_tpu.workflows import StreamingSegmentationWorkflow

td, tag, fused = sys.argv[1], sys.argv[2], sys.argv[3] == "fused"
sched = os.path.join(td, "sched")
os.makedirs(sched, exist_ok=True)
submit, queue = os.path.join(sched, "submit"), os.path.join(sched, "queue")
with open(submit, "w") as f:
    f.write('#!/bin/bash\nscript="${@: -1}"\nbash "$script" >/dev/null 2>&1\n'
            'echo "Submitted batch job 1"\n')
with open(queue, "w") as f:
    f.write("#!/bin/bash\nexit 0\n")
for p in (submit, queue):
    os.chmod(p, os.stat(p).st_mode | stat.S_IEXEC)

rng = np.random.default_rng(0)
raw = ndimage.gaussian_filter(rng.random((24, 48, 48)), (1.0, 2.0, 2.0))
raw = ((raw - raw.min()) / (raw.max() - raw.min())).astype("float32")
path = os.path.join(td, f"{tag}.n5")
file_reader(path).create_dataset("raw", data=raw, chunks=(12, 24, 24))
config_dir = os.path.join(td, f"configs_{tag}")
cfg.write_global_config(config_dir, {
    "block_shape": [12, 24, 24], "target": "slurm", "max_jobs": 2,
    # batches spanning whole z-slab rows maximize the one-superslab-read
    # win (a 1-block batch degenerates to per-block halo'd reads)
    "stream_fusion": fused, "device_batch_size": 4,
    "poll_interval_s": 0.05, "sbatch_cmd": submit, "squeue_cmd": queue,
    "worker_env": {"JAX_PLATFORMS": "cpu"},
})
cfg.write_config(config_dir, "threshold", {"threshold": 0.55})
cfg.write_config(config_dir, "watershed", {
    "threshold": 0.5, "sigma_seeds": 1.6, "size_filter": 10,
    "halo": [2, 6, 6],
})
wf = StreamingSegmentationWorkflow(
    os.path.join(td, f"tmp_{tag}"), config_dir, max_jobs=2,
    input_path=path, input_key="raw",
    output_path=path, output_key="cc",
)
assert build([wf]), f"streaming workflow failed ({tag})"
PY

# the decoded-chunk LRU would hide exactly the cross-task re-reads the
# fusion removes at this fixture size — byte counts come from the codec
# boundary in both runs
JAX_PLATFORMS=cpu PYTHONPATH="$repo_root${PYTHONPATH:+:$PYTHONPATH}" \
CTT_CHUNK_CACHE_MB=0 CTT_TRACE_DIR="$obs_tmp/trace" \
CTT_RUN_ID=ci_stream_unfused \
    python "$stream_tmp/stream_driver.py" "$stream_tmp/unfused" u unfused
unfused_rc=$?
JAX_PLATFORMS=cpu PYTHONPATH="$repo_root${PYTHONPATH:+:$PYTHONPATH}" \
CTT_CHUNK_CACHE_MB=0 CTT_TRACE_DIR="$obs_tmp/trace" \
CTT_RUN_ID=ci_stream_fused \
    python "$stream_tmp/stream_driver.py" "$stream_tmp/fused" f fused
fused_rc=$?
if [ "$unfused_rc" -ne 0 ] || [ "$fused_rc" -ne 0 ]; then
    echo "streaming smoke runs failed (unfused rc=$unfused_rc," \
         "fused rc=$fused_rc)" >&2
    exit 1
fi
JAX_PLATFORMS=cpu python - "$stream_tmp" "$obs_tmp/trace" <<'PY'
import json, os, sys
import numpy as np
from cluster_tools_tpu.utils import file_reader

td, trace = sys.argv[1], sys.argv[2]
f_un = file_reader(os.path.join(td, "unfused", "u.n5"), "r")
f_fu = file_reader(os.path.join(td, "fused", "f.n5"), "r")
np.testing.assert_array_equal(f_fu["cc"][:], f_un["cc"][:])
np.testing.assert_array_equal(f_fu["cc_ws"][:], f_un["cc_ws"][:])
assert "cc_mask" in f_un, "unfused run must materialize the mask"
assert "cc_mask" not in f_fu, "fused run must elide the mask"


def totals(run_id):
    out = {}
    rdir = os.path.join(trace, run_id)
    for name in os.listdir(rdir):
        if name.startswith("metrics.p"):
            with open(os.path.join(rdir, name)) as fh:
                for k, v in json.load(fh)["counters"].items():
                    out[k] = out.get(k, 0) + v
    return out


t_un, t_fu = totals("ci_stream_unfused"), totals("ci_stream_fused")
r_un, r_fu = t_un.get("store.bytes_read", 0), t_fu.get("store.bytes_read", 0)
assert r_un > 0 and r_fu > 0, (r_un, r_fu)
assert r_fu < r_un, f"fused read bytes {r_fu} not < unfused {r_un}"
assert t_fu.get("stream.chains", 0) >= 1, t_fu
assert t_fu.get("stream.elided_bytes", 0) > 0, t_fu
print("stream smoke ok:", json.dumps({
    "bytes_read_unfused": round(r_un), "bytes_read_fused": round(r_fu),
    "reduction": round(r_un / r_fu, 2),
    "slabs": t_fu.get("stream.slabs"),
}))
PY
stream_rc=$?
rm -rf "$stream_tmp"
if [ "$stream_rc" -ne 0 ]; then
    echo "streaming smoke failed (rc=$stream_rc): fused chain output or" \
         "store-read reduction regressed" >&2
    exit "$stream_rc"
fi
# the fused trace must summarize cleanly (spans + chain tags well-formed)
JAX_PLATFORMS=cpu python -m cluster_tools_tpu.obs summarize \
    "$obs_tmp/trace/ci_stream_fused"
stream_sum_rc=$?
if [ "$stream_sum_rc" -ne 0 ]; then
    echo "obs summarize failed on the fused streaming trace" \
         "(rc=$stream_sum_rc)" >&2
    exit "$stream_sum_rc"
fi

echo "== ctt-steal smoke (worker kill -> lease requeue, digest == static run) =="
steal_tmp="$(mktemp -d)"
JAX_PLATFORMS=cpu CTT_TRACE_DIR="$obs_tmp/trace" CTT_RUN_ID=ci_steal \
CTT_FAULT_STATE_DIR="$steal_tmp/fault_state" \
    python - "$steal_tmp" <<'PY'
import hashlib, json, os, stat, sys

# the chaos spec must reach only the STEALING run's workers (the static
# baseline stays fault-free); armed per-run below via worker_env-inherited
# process environment
CHAOS_SPEC = "executor.block:kill:ids=2,once;seed=21"

import numpy as np
from scipy import ndimage

from cluster_tools_tpu.obs import trace as obs_trace
from cluster_tools_tpu.runtime import build, config as cfg
from cluster_tools_tpu.utils import file_reader
from cluster_tools_tpu.workflows.watershed import WatershedWorkflow

td = sys.argv[1]
sched = os.path.join(td, "sched")
os.makedirs(sched, exist_ok=True)
submit, queue = os.path.join(sched, "submit"), os.path.join(sched, "queue")
with open(submit, "w") as f:
    f.write('#!/bin/bash\nscript="${@: -1}"\nbash "$script" >/dev/null 2>&1\n'
            'echo "Submitted batch job 1"\n')
with open(queue, "w") as f:
    f.write("#!/bin/bash\nexit 0\n")
for p in (submit, queue):
    os.chmod(p, os.stat(p).st_mode | stat.S_IEXEC)

rng = np.random.default_rng(0)
raw = ndimage.gaussian_filter(rng.random((16, 32, 32)), (1.0, 2.0, 2.0))
raw = ((raw - raw.min()) / (raw.max() - raw.min())).astype("float32")


def run_ws(key, sched_mode, spec=None):
    if spec is None:
        os.environ.pop("CTT_FAULTS", None)
    else:
        os.environ["CTT_FAULTS"] = spec
    path = os.path.join(td, f"{key}.n5")
    file_reader(path).create_dataset("bnd", data=raw, chunks=(8, 16, 16))
    config_dir = os.path.join(td, f"configs_{key}")
    cfg.write_global_config(config_dir, {
        "block_shape": [8, 16, 16], "target": "slurm", "max_jobs": 3,
        "sched": sched_mode, "steal_lease_s": 0.2, "steal_batch_size": 2,
        "max_num_retries": 2, "retry_failure_fraction": 0.9,
        "poll_interval_s": 0.05, "sbatch_cmd": submit, "squeue_cmd": queue,
        "worker_env": {"JAX_PLATFORMS": "cpu"},
    })
    cfg.write_config(config_dir, "watershed", {
        "threshold": 0.5, "sigma_seeds": 1.6, "size_filter": 10,
        "halo": [2, 4, 4],
    })
    wf = WatershedWorkflow(
        os.path.join(td, f"tmp_{key}"), config_dir, max_jobs=3,
        input_path=path, input_key="bnd",
        output_path=path, output_key="ws",
    )
    try:
        assert build([wf]), f"{key} watershed build failed"
    finally:
        os.environ.pop("CTT_FAULTS", None)
    return path


def digest(root):
    h = hashlib.sha256()
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames.sort()
        for name in sorted(filenames):
            p = os.path.join(dirpath, name)
            h.update(os.path.relpath(p, root).encode())
            with open(p, "rb") as f:
                h.update(f.read())
    return h.hexdigest()


static = run_ws("static", "static")
steal = run_ws("steal", "steal", CHAOS_SPEC)

np.testing.assert_array_equal(
    file_reader(steal, "r")["ws"][:], file_reader(static, "r")["ws"][:]
)
assert digest(os.path.join(steal, "ws")) == digest(
    os.path.join(static, "ws")
), "stealing chaos output not byte-identical to the static run"

# the kill latched (a worker really died mid-item, once across processes)
latches = os.listdir(os.environ["CTT_FAULT_STATE_DIR"])
assert any(l.startswith("executor.block") for l in latches), latches

# recovery went through lease requeue, NOT a task-level retry round
status = json.load(open(os.path.join(
    td, "tmp_steal", "status", "watershed.status.json")))
assert status["complete"] and len(status["block_runtimes"]) == 1, status

from cluster_tools_tpu.obs import metrics as obs_metrics

obs_metrics.flush()  # the driver's own counters (task.blocks_retried) too
totals = {}
run_dir = obs_trace.run_dir()
for name in os.listdir(run_dir):
    if name.startswith("metrics.p"):
        with open(os.path.join(run_dir, name)) as f:
            for k, v in json.load(f)["counters"].items():
                totals[k] = totals.get(k, 0) + v
assert totals.get("sched.leases_expired", 0) >= 1, totals
assert totals.get("sched.leases_requeued", 0) >= 1, totals
assert totals.get("task.blocks_retried", 0) == 0, totals
print("steal smoke ok:", json.dumps({
    k: round(v, 2) for k, v in sorted(totals.items())
    if k.startswith("sched.")
}))
PY
steal_rc=$?
rm -rf "$steal_tmp"
if [ "$steal_rc" -ne 0 ]; then
    echo "steal smoke failed (rc=$steal_rc): killed worker did not" \
         "self-heal via lease requeue to a byte-identical output" >&2
    exit "$steal_rc"
fi

echo "== ctt-serve smoke (two jobs -> warm hit, /metrics parses, SIGTERM drain) =="
serve_tmp="$(mktemp -d)"
JAX_PLATFORMS=cpu PYTHONPATH="$repo_root${PYTHONPATH:+:$PYTHONPATH}" \
    python - "$serve_tmp" <<'PY'
import json, os, re, signal, subprocess, sys, time

td = sys.argv[1]
state_dir = os.path.join(td, "state")
env = {**os.environ, "JAX_PLATFORMS": "cpu",
       "CTT_HEARTBEAT_S": "0.2"}
for k in ("CTT_TRACE_DIR", "CTT_RUN_ID"):
    env.pop(k, None)

import numpy as np
from cluster_tools_tpu.serve import JobQueue, ServeClient
from cluster_tools_tpu.utils import file_reader

path = os.path.join(td, "d.n5")
rng = np.random.default_rng(0)
file_reader(path).create_dataset(
    "seg", data=rng.integers(0, 50, (8, 16, 16)).astype(np.uint64),
    chunks=(4, 8, 8),
)

daemon = subprocess.Popen(
    [sys.executable, "-m", "cluster_tools_tpu.serve",
     "--state-dir", state_dir, "--lease-s", "0.5"],
    env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
)
try:
    client = None
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        assert daemon.poll() is None, daemon.stderr.read()
        try:
            client = ServeClient(state_dir=state_dir)
            client.healthz()
            break
        except Exception:
            time.sleep(0.1)
    assert client is not None, "daemon never became healthy"

    # two small workflows back-to-back: the second must be served from
    # the daemon's warm compile state
    states = []
    for i in (1, 2):
        states.append(client.submit_and_wait(
            "UniqueWorkflow",
            {"tmp_folder": os.path.join(td, f"tmp{i}"),
             "config_dir": os.path.join(td, "configs"),
             "input_path": path, "input_key": "seg",
             "output_path": path, "output_key": f"u{i}"},
            configs={"global": {"block_shape": [4, 8, 8]}},
            timeout_s=300,
        ))
    assert states[0]["result"]["ok"] and states[1]["result"]["ok"]
    assert not states[0]["result"]["warm"], states[0]["result"]
    assert states[1]["result"]["warm"], states[1]["result"]

    text = client.metrics_text()
    with open(os.path.join(td, "exposition.txt"), "w") as f:
        f.write(text)
    vals = {
        ln.rsplit(" ", 1)[0]: float(ln.rsplit(" ", 1)[1])
        for ln in text.splitlines()
        if ln and not ln.startswith("#")
    }
    assert vals.get("ctt_serve_warm_compile_jobs_total", 0) >= 1, vals
    assert vals.get("ctt_serve_jobs_done_total", 0) >= 2, vals

    # SIGTERM -> drain: clean exit, heartbeat flags the drain
    daemon.send_signal(signal.SIGTERM)
    rc = daemon.wait(timeout=120)
    assert rc == 0, (rc, daemon.stderr.read()[-2000:])
    ep = json.load(open(os.path.join(state_dir, "serve.json")))
    run_dir = os.path.join(state_dir, "trace", ep["run_id"])
    hbs = [n for n in os.listdir(run_dir) if n.startswith("hb.p")]
    assert hbs, os.listdir(run_dir)
    hb = json.load(open(os.path.join(run_dir, hbs[0])))
    assert hb["draining"] is True and hb["exiting"] is True, hb
    # nothing queued was lost (both jobs completed pre-drain)
    q = JobQueue(os.path.join(state_dir, "jobs"), lease_s=0.5)
    assert all(j["state"] == "done" for j in q.list()), q.list()
    print("serve smoke ok: cold->warm accounting, drain clean")
finally:
    if daemon.poll() is None:
        daemon.kill()
        daemon.wait(timeout=30)
PY
serve_rc=$?
if [ "$serve_rc" -ne 0 ]; then
    rm -rf "$serve_tmp"
    echo "serve smoke failed (rc=$serve_rc): daemon warm-compile" \
         "accounting, /metrics, or SIGTERM drain regressed" >&2
    exit "$serve_rc"
fi
# the daemon's exposition must be valid OpenMetrics (same validator as
# the watch smoke)
python - "$serve_tmp/exposition.txt" <<'PY'
import re, sys
with open(sys.argv[1]) as f:
    text = f.read()
lines = text.splitlines()
assert lines and lines[-1] == "# EOF", "exposition must end with # EOF"
try:
    from prometheus_client.openmetrics.parser import (
        text_string_to_metric_families,
    )
    families = list(text_string_to_metric_families(text))
    assert families, "no metric families in exposition"
except ImportError:
    sample = re.compile(
        r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? -?[0-9.+eEinfa]+$")
    meta = re.compile(r"^# (TYPE [a-zA-Z_:][a-zA-Z0-9_:]* \w+|HELP .+|EOF)$")
    for line in lines:
        assert sample.match(line) or meta.match(line), f"bad line: {line}"
print("serve prom exposition ok")
PY
serve_prom_rc=$?
rm -rf "$serve_tmp"
if [ "$serve_prom_rc" -ne 0 ]; then
    echo "serve /metrics output is not valid OpenMetrics" \
         "(rc=$serve_prom_rc)" >&2
    exit "$serve_prom_rc"
fi

echo "== ctt-cloud smoke (serve daemon against the stub object store, 5% request chaos) =="
# the deployability gate: the ctt-serve daemon executes a watershed whose
# input AND output live in an object store (the tests/objstub.py stub,
# injecting 5% request failures), and the result is byte-identical —
# chunk digests included — to an in-process POSIX run, with the daemon's
# /metrics showing nonzero remote IO and absorbed retries.
cloud_tmp="$(mktemp -d)"
JAX_PLATFORMS=cpu PYTHONPATH="$repo_root${PYTHONPATH:+:$PYTHONPATH}" \
    python - "$cloud_tmp" <<'PY'
import hashlib, json, os, signal, subprocess, sys, time

td = sys.argv[1]
repo_root = os.environ.get("PYTHONPATH", "").split(os.pathsep)[0] or "."
env = {**os.environ, "JAX_PLATFORMS": "cpu",
       "CTT_HEARTBEAT_S": "0.2"}
for k in ("CTT_TRACE_DIR", "CTT_RUN_ID"):
    env.pop(k, None)

import numpy as np
from scipy import ndimage

from cluster_tools_tpu.runtime import build, config as cfg
from cluster_tools_tpu.serve import ServeClient
from cluster_tools_tpu.utils import file_reader
from cluster_tools_tpu.workflows import WatershedWorkflow

rng = np.random.default_rng(0)
base = ndimage.gaussian_filter(rng.random((16, 64, 64)), (1.0, 2.0, 2.0))
vol = ((base - base.min()) / (base.max() - base.min())).astype("float32")
ws_conf = {"threshold": 0.5, "sigma_seeds": 1.6, "size_filter": 10,
           "halo": [2, 4, 4]}
gconf = {"block_shape": [8, 32, 32], "target": "tpu", "pipeline_depth": 3}


def digest(root):
    h = hashlib.sha256()
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames.sort()
        for name in sorted(filenames):
            p = os.path.join(dirpath, name)
            h.update(os.path.relpath(p, root).encode())
            with open(p, "rb") as f:
                h.update(f.read())
    return h.hexdigest()


# POSIX reference, in-process
local = os.path.join(td, "local.n5")
file_reader(local).create_dataset(
    "bnd", data=vol, chunks=(8, 32, 32), compression="gzip"
)
config_dir = os.path.join(td, "configs_local")
cfg.write_global_config(config_dir, gconf)
cfg.write_config(config_dir, "watershed", ws_conf)
assert build([WatershedWorkflow(
    os.path.join(td, "tmp_local"), config_dir,
    input_path=local, input_key="bnd",
    output_path=local, output_key="ws",
)]), "posix reference run failed"

# stub object store with 5% injected request failures
objroot = os.path.join(td, "objroot")
os.makedirs(objroot)
served = os.path.join(objroot, "data.n5")
file_reader(served).create_dataset(
    "bnd", data=vol, chunks=(8, 32, 32), compression="gzip"
)
port_file = os.path.join(td, "stub.port")
stub = subprocess.Popen([
    sys.executable, os.path.join(repo_root, "tests", "objstub.py"),
    "--root", objroot, "--port-file", port_file,
    "--fail-rate", "0.05", "--seed", "7",
], env=env)
daemon = None
try:
    deadline = time.monotonic() + 30
    while not os.path.exists(port_file):
        assert stub.poll() is None, "objstub died on startup"
        assert time.monotonic() < deadline, "objstub never came up"
        time.sleep(0.05)
    url = f"http://127.0.0.1:{open(port_file).read().strip()}"

    state_dir = os.path.join(td, "state")
    daemon = subprocess.Popen(
        [sys.executable, "-m", "cluster_tools_tpu.serve",
         "--state-dir", state_dir],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    client = None
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        assert daemon.poll() is None, daemon.stderr.read()
        try:
            client = ServeClient(state_dir=state_dir)
            client.healthz()
            break
        except Exception:
            time.sleep(0.1)
    assert client is not None, "daemon never became healthy"

    state = client.submit_and_wait(
        "WatershedWorkflow",
        {"tmp_folder": os.path.join(td, "tmp_remote"),
         "config_dir": os.path.join(td, "configs_remote"),
         "input_path": f"{url}/data.n5", "input_key": "bnd",
         "output_path": f"{url}/data.n5", "output_key": "ws"},
        configs={"global": dict(gconf), "watershed": dict(ws_conf)},
        timeout_s=600,
    )
    assert state["result"]["ok"], state

    # byte-identity: the store the stub served now holds the SAME chunk
    # files as the POSIX run
    assert digest(os.path.join(local, "ws")) == digest(
        os.path.join(served, "ws")
    ), "remote watershed output is not byte-identical to the POSIX run"

    # remote counters visible through the daemon's own exposition
    vals = {
        ln.rsplit(" ", 1)[0]: float(ln.rsplit(" ", 1)[1])
        for ln in client.metrics_text().splitlines()
        if ln and not ln.startswith("#")
    }
    assert vals.get("ctt_store_remote_reads_total", 0) > 0, vals
    assert vals.get("ctt_store_remote_writes_total", 0) > 0, vals
    assert vals.get("ctt_store_remote_retries_total", 0) > 0, (
        "5% request chaos never forced a retry", vals,
    )
    print("cloud smoke ok:", json.dumps({
        "remote_reads": vals.get("ctt_store_remote_reads_total"),
        "remote_writes": vals.get("ctt_store_remote_writes_total"),
        "remote_retries": vals.get("ctt_store_remote_retries_total"),
    }))
finally:
    if daemon is not None:
        daemon.send_signal(signal.SIGTERM)
        try:
            daemon.wait(timeout=60)
        except subprocess.TimeoutExpired:
            daemon.kill()
            daemon.wait(timeout=30)
    stub.terminate()
    stub.wait(timeout=30)
PY
cloud_rc=$?
rm -rf "$cloud_tmp"
if [ "$cloud_rc" -ne 0 ]; then
    echo "cloud smoke failed (rc=$cloud_rc): the serve daemon could not" \
         "produce a byte-identical watershed against the stub object" \
         "store under 5% request chaos" >&2
    exit "$cloud_rc"
fi

echo "== ctt-hbm smoke (serve daemon: second job zero upload bytes, fused dispatches < blocks, byte-identical) =="
hbm_tmp="$(mktemp -d)"
JAX_PLATFORMS=cpu PYTHONPATH="$repo_root${PYTHONPATH:+:$PYTHONPATH}" \
    python - "$hbm_tmp" <<'PY'
import hashlib, json, os, signal, subprocess, sys, time

td = sys.argv[1]
state_dir = os.path.join(td, "state")
env = {**os.environ, "JAX_PLATFORMS": "cpu"}
for k in ("CTT_TRACE_DIR", "CTT_RUN_ID"):
    env.pop(k, None)

import numpy as np
from scipy import ndimage
from cluster_tools_tpu.serve import ServeClient
from cluster_tools_tpu.utils import file_reader

path = os.path.join(td, "d.n5")
rng = np.random.default_rng(0)
raw = ndimage.gaussian_filter(rng.random((8, 32, 32)), (1.0, 2.0, 2.0))
raw = ((raw - raw.min()) / (raw.max() - raw.min())).astype("float32")
file_reader(path).create_dataset("bnd", data=raw, chunks=(4, 8, 8))
n_blocks = 2 * 4 * 4

daemon = subprocess.Popen(
    [sys.executable, "-m", "cluster_tools_tpu.serve",
     "--state-dir", state_dir],
    env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
)
try:
    client = None
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        assert daemon.poll() is None, daemon.stderr.read()
        try:
            client = ServeClient(state_dir=state_dir)
            client.healthz()
            break
        except Exception:
            time.sleep(0.1)
    assert client is not None, "daemon never became healthy"

    def scrape():
        return {
            ln.rsplit(" ", 1)[0]: float(ln.rsplit(" ", 1)[1])
            for ln in client.metrics_text().splitlines()
            if ln and not ln.startswith("#")
        }

    # the same small watershed twice (fresh tmp/output per job): the
    # second job must be served entirely from the warm HBM buffer cache
    def submit(tag):
        return client.submit_and_wait(
            "WatershedWorkflow",
            {"tmp_folder": os.path.join(td, f"tmp_{tag}"),
             "config_dir": os.path.join(td, f"configs_{tag}"),
             "input_path": path, "input_key": "bnd",
             "output_path": path, "output_key": f"ws_{tag}"},
            configs={
                "global": {"block_shape": [4, 8, 8], "target": "tpu",
                           "device_batch_size": 1, "pipeline_depth": 3,
                           "hbm_stack": 4},
                "watershed": {"threshold": 0.5, "sigma_seeds": 1.6,
                              "size_filter": 10, "halo": [2, 4, 4]},
            },
            timeout_s=300,
        )

    m0 = scrape()
    s1 = submit("j1")
    m1 = scrape()
    s2 = submit("j2")
    m2 = scrape()
    assert s1["result"]["ok"] and s2["result"]["ok"]

    def delta(a, b, name):
        return b.get(name, 0.0) - a.get(name, 0.0)

    up = "ctt_device_upload_bytes_total"
    assert delta(m0, m1, up) > 0, (m0, m1)
    # second job: ZERO new upload bytes (warm HBM), >= 1 skip
    assert delta(m1, m2, up) == 0, (m1, m2)
    assert delta(m1, m2, "ctt_device_uploads_skipped_total") >= 1
    # aggregated dispatch: fused dispatch count < block count
    disp = delta(m1, m2, "ctt_device_dispatches_total")
    assert 0 < disp < n_blocks, (disp, n_blocks)
    assert delta(m0, m1, "ctt_device_fused_blocks_total") > 0

    # byte-identity incl. chunk digests between the two jobs' outputs
    f = file_reader(path, "r")
    assert np.array_equal(f["ws_j1"][:], f["ws_j2"][:])

    def digest(root):
        h = hashlib.sha256()
        for dp, dns, fns in os.walk(root):
            dns.sort()
            for n in sorted(fns):
                p = os.path.join(dp, n)
                h.update(os.path.relpath(p, root).encode())
                h.update(open(p, "rb").read())
        return h.hexdigest()

    assert digest(os.path.join(path, "ws_j1")) == digest(
        os.path.join(path, "ws_j2")
    )
    print("hbm smoke ok: warm job zero upload bytes,",
          int(disp), "fused dispatches for", n_blocks,
          "blocks, chunk digests identical")
finally:
    daemon.send_signal(signal.SIGTERM)
    try:
        daemon.wait(timeout=60)
    except subprocess.TimeoutExpired:
        daemon.kill()
        daemon.wait(timeout=30)
PY
hbm_rc=$?
rm -rf "$hbm_tmp"
if [ "$hbm_rc" -ne 0 ]; then
    echo "hbm smoke failed (rc=$hbm_rc): warm-HBM upload accounting," \
         "dispatch aggregation, or byte-identity regressed" >&2
    exit "$hbm_rc"
fi

echo "== ctt-hier smoke (daemon hierarchy build, 3-threshold warm sweep, parity vs fresh re-runs, zero warm upload bytes) =="
hier_tmp="$(mktemp -d)"
JAX_PLATFORMS=cpu PYTHONPATH="$repo_root${PYTHONPATH:+:$PYTHONPATH}" \
    python - "$hier_tmp" <<'PY'
import os, signal, subprocess, sys, time

td = sys.argv[1]
state_dir = os.path.join(td, "state")
env = {**os.environ, "JAX_PLATFORMS": "cpu"}
for k in ("CTT_TRACE_DIR", "CTT_RUN_ID"):
    env.pop(k, None)

import numpy as np
from scipy import ndimage
from cluster_tools_tpu.ops import hier as hier_ops
from cluster_tools_tpu.serve import ServeClient
from cluster_tools_tpu.utils import file_reader

path = os.path.join(td, "d.n5")
rng = np.random.default_rng(0)
raw = ndimage.gaussian_filter(rng.random((8, 32, 32)), (1.0, 2.0, 2.0))
raw = ((raw - raw.min()) / (raw.max() - raw.min())).astype("float32")
file_reader(path).create_dataset("bnd", data=raw, chunks=(4, 16, 16))
gconf = {"block_shape": [4, 16, 16], "target": "tpu",
         "device_batch_size": 1, "pipeline_depth": 2}
bconf = {"threshold": 0.5, "sigma_seeds": 1.6, "size_filter": 10}

daemon = subprocess.Popen(
    [sys.executable, "-m", "cluster_tools_tpu.serve",
     "--state-dir", state_dir],
    env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
)
try:
    client = None
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        assert daemon.poll() is None, daemon.stderr.read()
        try:
            client = ServeClient(state_dir=state_dir)
            client.healthz()
            break
        except Exception:
            time.sleep(0.1)
    assert client is not None, "daemon never became healthy"

    def scrape():
        return {
            ln.rsplit(" ", 1)[0]: float(ln.rsplit(" ", 1)[1])
            for ln in client.metrics_text().splitlines()
            if ln and not ln.startswith("#")
        }

    def build_job(tag, out_key):
        return client.submit_and_wait(
            "HierarchyWorkflow",
            {"tmp_folder": os.path.join(td, f"tmp_{tag}"),
             "config_dir": os.path.join(td, f"configs_{tag}"),
             "input_path": path, "input_key": "bnd",
             "output_path": path, "output_key": out_key},
            configs={"global": dict(gconf), "hierarchy_blocks": dict(bconf)},
            timeout_s=600,
        )

    def reseg(tag, labels_key, out_key, t, write_volume):
        job = client.resegment(
            hierarchy=os.path.join(path, f"{labels_key}_hierarchy.npz"),
            labels_path=path, labels_key=labels_key,
            output_path=path, output_key=out_key,
            threshold=t, write_volume=write_volume,
            tmp_folder=os.path.join(td, f"tmp_{tag}"),
            config_dir=os.path.join(td, f"configs_{tag}"),
            configs={"global": dict(gconf)},
        )
        st = client.wait(job, timeout_s=600)
        assert st["result"]["ok"], st
        return st

    s = build_job("build", "seg")
    assert s["result"]["ok"], s
    art = hier_ops.load_hierarchy(os.path.join(path, "seg_hierarchy.npz"))
    ts = [float(t) for t in np.quantile(art["saddle"], (0.25, 0.5, 0.75))]
    # warm the HBM cache + compiles, then the measured sweep window
    reseg("warm", "seg", "seg_warm", ts[0], True)
    m1 = scrape()
    for i, t in enumerate(ts):
        reseg(f"sweep{i}", "seg", f"cut{i}", t, False)
    reseg("commit", "seg", "seg_commit", ts[1], True)
    m2 = scrape()
    up = "ctt_device_upload_bytes_total"
    delta = m2.get(up, 0.0) - m1.get(up, 0.0)
    assert delta == 0, f"warm sweep uploaded {delta} bytes"
    assert m2.get("ctt_hier_resegment_jobs_total", 0) >= 5

    # parity vs fresh full re-runs at every swept threshold
    from cluster_tools_tpu.ops.evaluation import rand_scores
    from cluster_tools_tpu.ops.segment import contingency_table

    f = file_reader(path, "r")
    seg = f["seg"][:]
    for i, t in enumerate(ts):
        assert build_job(f"full{i}", f"seg_f{i}")["result"]["ok"]
        reseg(f"fullcut{i}", f"seg_f{i}", f"seg_f{i}_t", t, True)
        cut = hier_ops.load_cut_table(
            os.path.join(path, f"cut{i}_cut.npz"))
        swept = hier_ops.apply_cut_np(seg, cut["vals"], cut["roots"])
        ia, ib, counts = contingency_table(
            swept.astype(np.uint64), f[f"seg_f{i}_t"][:])
        ri = rand_scores(ia, ib, counts)["rand_index"]
        assert ri == 1.0, (t, ri)
    print("hier smoke ok: 3-threshold warm sweep, zero upload bytes,",
          "RI == 1.0 vs fresh full re-runs at every threshold")
finally:
    daemon.send_signal(signal.SIGTERM)
    try:
        daemon.wait(timeout=60)
    except subprocess.TimeoutExpired:
        daemon.kill()
        daemon.wait(timeout=30)
PY
hier_rc=$?
rm -rf "$hier_tmp"
if [ "$hier_rc" -ne 0 ]; then
    echo "hier smoke failed (rc=$hier_rc): hierarchy build, warm sweep" \
         "upload accounting, or re-cut parity regressed" >&2
    exit "$hier_rc"
fi

echo "== ctt-fleet chaos smoke (2 daemons over the stub object store, SIGKILL one mid-job -> zero loss, fast reclaim) =="
# the fleet gate: two serve daemons share one state dir, executing a
# 6-job burst whose volumes live in the stub object store; one daemon is
# SIGKILLed mid-job.  Every job must still publish an ok result, the
# recovered job's output must be byte-identical to a single-daemon
# reference run, recovery must ride the fleet-heartbeat fast path (not
# the 3 x lease_s staleness window), and the survivor's /metrics must
# parse as OpenMetrics with ctt_serve_jobs_reclaimed_total >= 1.
fleet_tmp="$(mktemp -d)"
JAX_PLATFORMS=cpu PYTHONPATH="$repo_root${PYTHONPATH:+:$PYTHONPATH}" \
    python - "$fleet_tmp" <<'PY'
import hashlib, json, os, re, subprocess, sys, time

td = sys.argv[1]
repo_root = os.environ.get("PYTHONPATH", "").split(os.pathsep)[0] or "."
env = {**os.environ, "JAX_PLATFORMS": "cpu",
       "CTT_HEARTBEAT_S": "0.2"}
for k in ("CTT_TRACE_DIR", "CTT_RUN_ID"):
    env.pop(k, None)

import numpy as np

from cluster_tools_tpu.serve import ServeClient
from cluster_tools_tpu.utils import file_reader


def digest(root):
    h = hashlib.sha256()
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames.sort()
        for name in sorted(filenames):
            p = os.path.join(dirpath, name)
            h.update(os.path.relpath(p, root).encode())
            with open(p, "rb") as f:
                h.update(f.read())
    return h.hexdigest()


def sleep_job(root_td, data_root, tag, sleep_s, phase):
    # the ctt-steal calibrated-cost fixture task: one block,
    # deterministic output (input * 2 + 1), every block costs sleep_s —
    # so the reference run can be fast while staying byte-identical.
    # tmp/config dirs are per phase: a shared checkpoint folder would let
    # the fleet run skip blocks the reference run already marked done
    return {
        "workflow": "bench_e2e_lib:SkewedCostTask",
        "kwargs": {
            "tmp_folder": os.path.join(root_td, f"tmp_{phase}_{tag}"),
            "config_dir": os.path.join(root_td, f"configs_{phase}_{tag}"),
            "input_path": f"{data_root}/{tag}.n5", "input_key": "x",
            "output_path": f"{data_root}/{tag}.n5", "output_key": "y",
        },
        "configs": {
            "global": {"block_shape": [2, 8, 8]},
            "skewed_cost": {
                "hot_z_end": 0, "base_s": float(sleep_s), "hot_s": 99.0,
            },
        },
        "tenant": tag,
    }


def spawn(state_dir, daemon_id):
    proc = subprocess.Popen(
        [sys.executable, "-m", "cluster_tools_tpu.serve",
         "--state-dir", state_dir, "--lease-s", "5",
         "--daemon-id", daemon_id],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    proc.stdout.readline()  # listening banner
    ep_line = proc.stdout.readline()  # per-daemon endpoint JSON
    assert ep_line, f"{daemon_id} died at startup:\n{proc.stderr.read()}"
    ep = json.loads(ep_line)
    client = ServeClient(endpoint=f"http://{ep['host']}:{ep['port']}",
                         token=ep["token"])
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        try:
            client.healthz()
            return proc, client
        except Exception:
            assert proc.poll() is None, (
                f"{daemon_id} died:\n{proc.stderr.read()}")
            time.sleep(0.1)
    raise AssertionError(f"{daemon_id} never became healthy")


tags = [f"k{i}" for i in range(6)]

# single-daemon reference run (POSIX volumes, zero job sleep)
ref_root = os.path.join(td, "ref")
os.makedirs(ref_root)
for tag in tags:
    file_reader(os.path.join(ref_root, f"{tag}.n5")).create_dataset(
        "x", data=np.ones((2, 8, 8), dtype="float32"), chunks=(2, 8, 8))
ref, ref_client = spawn(os.path.join(td, "state_ref"), "ref")
try:
    jobs = [ref_client.submit(**sleep_job(td, ref_root, t, 0.01, "ref"))
            for t in tags]
    for jid in jobs:
        assert ref_client.wait(jid, timeout_s=300)["result"]["ok"]
finally:
    ref.kill()
    ref.wait(timeout=30)

# the fleet run: volumes on the stub object store, two daemons, SIGKILL
objroot = os.path.join(td, "objroot")
os.makedirs(objroot)
for tag in tags:
    file_reader(os.path.join(objroot, f"{tag}.n5")).create_dataset(
        "x", data=np.ones((2, 8, 8), dtype="float32"), chunks=(2, 8, 8))
port_file = os.path.join(td, "stub.port")
stub = subprocess.Popen([
    sys.executable, os.path.join(repo_root, "tests", "objstub.py"),
    "--root", objroot, "--port-file", port_file,
], env=env)
proc_a = proc_b = None
try:
    deadline = time.monotonic() + 30
    while not os.path.exists(port_file):
        assert stub.poll() is None, "objstub died on startup"
        assert time.monotonic() < deadline, "objstub never came up"
        time.sleep(0.05)
    url = f"http://127.0.0.1:{open(port_file).read().strip()}"

    state_dir = os.path.join(td, "state_fleet")
    proc_a, client_a = spawn(state_dir, "dA")
    proc_b, client_b = spawn(state_dir, "dB")
    jobs = []
    for i, tag in enumerate(tags):
        cl = client_a if i % 2 == 0 else client_b
        jobs.append(cl.submit(**sleep_job(td, url, tag, 2.0, "fleet")))

    # SIGKILL dA once its own fleet beat reports a job in flight
    beat = os.path.join(state_dir, "daemon.dA.json")
    deadline = time.monotonic() + 60
    running = 0
    while time.monotonic() < deadline and running < 1:
        try:
            running = json.load(open(beat)).get("running_jobs", 0)
        except Exception:
            pass
        time.sleep(0.05)
    assert running >= 1, "dA never started executing"
    proc_a.kill()
    proc_a.wait(timeout=30)
    t_kill = time.time()

    # zero loss: every job publishes an ok result via the survivor
    for jid in jobs:
        assert client_b.wait(jid, timeout_s=300)["result"]["ok"], jid
    from cluster_tools_tpu.serve import JobQueue
    q = JobQueue(os.path.join(state_dir, "jobs"), lease_s=5.0)
    results = [q.get(j)["result"] for j in jobs]
    requeued = [r for r in results if r["gen"] > 0]
    assert requeued, "the killed daemon's job never requeued"
    for r in requeued:
        # heartbeat-bounded recovery (3 x 0.2s detection + one 2s
        # re-execution), far inside the 15s lease-staleness window
        assert r["finished_wall"] - t_kill < 12.0, r

    # byte-identity vs the single-daemon reference, recovered job included
    for tag in tags:
        assert digest(os.path.join(objroot, f"{tag}.n5", "y")) == digest(
            os.path.join(ref_root, f"{tag}.n5", "y")
        ), f"{tag} output differs from the single-daemon run"

    # the survivor's ledger: fast-path reclaim counted, /metrics parses
    text = client_b.metrics_text()
    lines = text.splitlines()
    assert lines and lines[-1] == "# EOF", "exposition must end with # EOF"
    try:
        from prometheus_client.openmetrics.parser import (
            text_string_to_metric_families,
        )
        assert list(text_string_to_metric_families(text))
    except ImportError:
        sample = re.compile(
            r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? -?[0-9.+eEinfa]+$")
        meta = re.compile(
            r"^# (TYPE [a-zA-Z_:][a-zA-Z0-9_:]* \w+|HELP .+|EOF)$")
        for line in lines:
            assert sample.match(line) or meta.match(line), line
    vals = {
        ln.rsplit(" ", 1)[0]: float(ln.rsplit(" ", 1)[1])
        for ln in lines if ln and not ln.startswith("#")
    }
    assert vals.get("ctt_serve_jobs_reclaimed_total", 0) >= 1, vals
    assert vals.get("ctt_serve_jobs_quarantined_total", 0) == 0, vals
    print("fleet smoke ok:", json.dumps({
        "requeued": len(requeued),
        "reclaim_latency_s": round(
            min(r["finished_wall"] for r in requeued) - t_kill, 2),
        "jobs_reclaimed": vals.get("ctt_serve_jobs_reclaimed_total"),
    }))
finally:
    for proc in (proc_a, proc_b):
        if proc is not None and proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
    stub.terminate()
    stub.wait(timeout=30)
PY
fleet_rc=$?
if [ "$fleet_rc" -eq 0 ]; then
    # ctt-proto: the SIGKILL-survivor state dir is exactly what the
    # artifact registry describes — every surviving file must match a
    # registered schema (protocol conformance IS the recovery contract)
    echo "== ctt-proto conformance (fleet-chaos state dir vs the artifact registry) =="
    JAX_PLATFORMS=cpu python -m cluster_tools_tpu.analysis conformance \
        "$fleet_tmp/state_fleet"
    fleet_rc=$?
    if [ "$fleet_rc" -ne 0 ]; then
        echo "conformance failed (rc=$fleet_rc): the fleet smoke left" \
             "behind files the registry does not describe — update" \
             "analysis/protocols.py or fix the writer" >&2
    fi
fi
rm -rf "$fleet_tmp"
if [ "$fleet_rc" -ne 0 ]; then
    echo "fleet smoke failed (rc=$fleet_rc): the two-daemon fleet lost a" \
         "job, recovered slower than the heartbeat bound, or broke" \
         "byte-identity after a SIGKILL" >&2
    exit "$fleet_rc"
fi

echo "== ctt-events smoke (daemon event_batch, scipy parity, quota 429 under burst, OpenMetrics events counters) =="
# the events gate: one serve daemon at a tiny admission envelope builds
# events for a frame stack (must match scipy.ndimage.label + numpy
# property reduction exactly), a submission burst past the envelope must
# draw CLEAN 429s, and /metrics must still parse as OpenMetrics with a
# nonzero ctt_events_frames_total afterwards.
events_tmp="$(mktemp -d)"
JAX_PLATFORMS=cpu PYTHONPATH="$repo_root${PYTHONPATH:+:$PYTHONPATH}" \
    python - "$events_tmp" <<'PY'
import os, signal, subprocess, sys, time

td = sys.argv[1]
state_dir = os.path.join(td, "state")
env = {**os.environ, "JAX_PLATFORMS": "cpu"}
for k in ("CTT_TRACE_DIR", "CTT_RUN_ID"):
    env.pop(k, None)

import numpy as np
from cluster_tools_tpu.ops import events as events_ops
from cluster_tools_tpu.serve import QuotaRejected, ServeClient
from cluster_tools_tpu.utils import file_reader

path = os.path.join(td, "d.n5")
rng = np.random.default_rng(0)
frames = np.where(rng.random((6, 32, 32)) > 0.97,
                  rng.random((6, 32, 32)) + 1.0, 0.0).astype("float32")
file_reader(path).create_dataset("frames", data=frames,
                                 chunks=(2, 32, 32))
gconf = {"block_shape": [2, 32, 32], "target": "tpu",
         "device_batch_size": 2, "pipeline_depth": 2}

daemon = subprocess.Popen(
    [sys.executable, "-m", "cluster_tools_tpu.serve",
     "--state-dir", state_dir, "--concurrency", "1",
     "--tenant-quota", "2", "--max-queue-depth", "4"],
    env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
)
try:
    client = None
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        assert daemon.poll() is None, daemon.stderr.read()
        try:
            client = ServeClient(state_dir=state_dir)
            client.healthz()
            break
        except Exception:
            time.sleep(0.1)
    assert client is not None, "daemon never became healthy"

    def submit(tag):
        return client.event_batch(
            input_path=path, input_key="frames",
            output_path=path, output_key=f"ev_{tag}",
            tmp_folder=os.path.join(td, f"tmp_{tag}"),
            config_dir=os.path.join(td, f"configs_{tag}"),
            threshold=0.5, configs={"global": dict(gconf)},
        )

    job = submit("main")
    st = client.wait(job, timeout_s=300)
    assert st["result"]["ok"], st

    # scipy parity: daemon labels volume == per-frame host oracle
    ref_l, ref_c, _ = events_ops.build_events_np(frames, threshold=0.5)
    srv = file_reader(path, "r")["ev_main"][:]
    assert np.array_equal(srv, ref_l), "daemon labels != scipy oracle"
    from cluster_tools_tpu.tasks.events import read_event_tables
    rows = read_event_tables(path, "ev_main", 3)
    assert len(rows) == int(ref_c.sum()), (len(rows), int(ref_c.sum()))

    # burst past the admission envelope: CLEAN 429s, no socket errors
    accepted, rejected = [], 0
    for i in range(32):
        try:
            accepted.append(submit(f"burst{i}"))
        except QuotaRejected:
            rejected += 1
    assert rejected > 0, "no 429 observed under a 32-submission burst"
    for j in accepted:
        assert client.wait(j, timeout_s=300)["result"]["ok"]

    text = client.metrics_text()
    lines = {
        parts[0]: float(parts[1])
        for parts in (ln.split() for ln in text.splitlines())
        if len(parts) == 2 and not parts[0].startswith("#")
    }
    assert lines.get("ctt_events_frames_total", 0) >= len(frames)
    assert lines.get("ctt_events_clusters_total", 0) > 0
    assert lines.get("ctt_serve_quota_rejections_total", 0) >= rejected
    try:
        from prometheus_client.openmetrics.parser import (
            text_string_to_metric_families,
        )
        fams = {f.name for f in text_string_to_metric_families(text)}
        assert any(n.startswith("ctt_events_frames") for n in fams), fams
    except ImportError:
        assert text.rstrip().endswith("# EOF"), "metrics lost # EOF"
    print("events smoke ok: scipy parity exact,",
          f"{rejected} clean 429s in burst, events counters on /metrics")
finally:
    daemon.send_signal(signal.SIGTERM)
    try:
        daemon.wait(timeout=60)
    except subprocess.TimeoutExpired:
        daemon.kill()
        daemon.wait(timeout=30)
PY
events_rc=$?
rm -rf "$events_tmp"
if [ "$events_rc" -ne 0 ]; then
    echo "events smoke failed (rc=$events_rc): daemon event_batch parity," \
         "quota 429 behaviour, or the events /metrics counters regressed" >&2
    exit "$events_rc"
fi

echo "== ctt-microbatch smoke (12-job mixed-tenant burst -> stacked dispatch, byte-identity vs window-0, kill-poison fails alone) =="
# the microbatch gate: a short-window daemon must coalesce a 12-job
# mixed-tenant event_batch burst into stacked dispatches (>= 2x
# aggregation on ctt_serve_microbatch_batches_total), the outputs must
# be byte-identical to a window-0 daemon, and an executor.block:kill
# poisoned member must burn its own retry budget alone — its
# batchmates from the same window publish ok.
microbatch_tmp="$(mktemp -d)"
JAX_PLATFORMS=cpu PYTHONPATH="$repo_root${PYTHONPATH:+:$PYTHONPATH}" \
    python - "$microbatch_tmp" <<'PY'
import hashlib, os, signal, subprocess, sys, time

td = sys.argv[1]
env = {**os.environ, "JAX_PLATFORMS": "cpu",
       "CTT_HEARTBEAT_S": "0.2"}
for k in ("CTT_TRACE_DIR", "CTT_RUN_ID"):
    env.pop(k, None)

import numpy as np
from scipy import ndimage
from cluster_tools_tpu.serve import ServeClient
from cluster_tools_tpu.utils import file_reader

gconf = {"block_shape": [2, 16, 16], "target": "local"}


def frames(seed, n=4):
    rng = np.random.default_rng(seed)
    raw = ndimage.gaussian_filter(
        rng.random((n, 16, 16)), (0.0, 1.0, 1.0)
    ).astype("float32")
    return np.where(raw > np.quantile(raw, 0.9), raw, 0.0).astype("float32")


def write_frames(tag, seed, n=4):
    path = os.path.join(td, f"{tag}.n5")
    file_reader(path).create_dataset("frames", data=frames(seed, n=n),
                                     chunks=(2, 16, 16))
    return path


def digest(root):
    h = hashlib.sha256()
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames.sort()
        for name in sorted(filenames):
            p = os.path.join(dirpath, name)
            h.update(os.path.relpath(p, root).encode())
            with open(p, "rb") as f:
                h.update(f.read())
    return h.hexdigest()


def spawn(state_dir, *extra_args, extra_env=None):
    daemon = subprocess.Popen(
        [sys.executable, "-m", "cluster_tools_tpu.serve",
         "--state-dir", state_dir, "--concurrency", "1", *extra_args],
        env={**env, **(extra_env or {})},
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        assert daemon.poll() is None, daemon.stderr.read()
        try:
            client = ServeClient(state_dir=state_dir)
            client.healthz()
            return daemon, client
        except Exception:
            time.sleep(0.1)
    daemon.kill()
    raise AssertionError("daemon never became healthy")


def stop(daemon):
    if daemon.poll() is None:
        daemon.send_signal(signal.SIGTERM)
        try:
            daemon.wait(timeout=60)
        except subprocess.TimeoutExpired:
            daemon.kill()
            daemon.wait(timeout=30)


def submit(client, path, tag, tenant, priority=0):
    return client.event_batch(
        input_path=path, input_key="frames",
        output_path=path, output_key=f"ev_{tag}",
        tmp_folder=os.path.join(td, f"tmp_{tag}"),
        config_dir=os.path.join(td, f"configs_{tag}"),
        threshold=0.1, configs={"global": dict(gconf)},
        tenant=tenant, priority=priority,
    )


# -- leg 1: short window, 12-job mixed-tenant burst -> stacked dispatch
burst_path = write_frames("burst", seed=7)
daemon, client = spawn(os.path.join(td, "state_mb"),
                       "--microbatch-window-s", "2.0",
                       "--microbatch-max-jobs", "12")
try:
    jobs = [submit(client, burst_path, f"mb{i}", tenant=f"t{i % 4}")
            for i in range(12)]
    for j in jobs:
        st = client.wait(j, timeout_s=300)
        assert st["result"]["ok"], st
        assert st["result"].get("microbatch"), (
            "burst member missing the microbatch annotation", st)
    text = client.metrics_text()
    vals = {
        parts[0]: float(parts[1])
        for parts in (ln.split() for ln in text.splitlines())
        if len(parts) == 2 and not parts[0].startswith("#")
    }
    batches = vals.get("ctt_serve_microbatch_batches_total", 0)
    batched = vals.get("ctt_serve_microbatch_jobs_batched_total", 0)
    assert batches >= 1, "no stacked dispatch under a 12-job burst"
    assert batched / batches >= 2, (
        f"aggregation below 2x: {batched} jobs over {batches} batches")
finally:
    stop(daemon)

# -- leg 2: window 0 = exact per-job dispatch; outputs byte-identical
daemon, client = spawn(os.path.join(td, "state_solo"),
                       "--microbatch-window-s", "0")
try:
    solo = [submit(client, burst_path, f"solo{i}", tenant=f"t{i % 4}")
            for i in range(12)]
    for j in solo:
        st = client.wait(j, timeout_s=300)
        assert st["result"]["ok"], st
        assert "microbatch" not in st["result"], (
            "window-0 daemon must not aggregate", st)
finally:
    stop(daemon)
for i in range(12):
    a = digest(os.path.join(burst_path, f"ev_mb{i}"))
    b = digest(os.path.join(burst_path, f"ev_solo{i}"))
    assert a == b, f"stacked output not byte-identical for job {i}"

# -- leg 3: executor.block:kill poison — the culprit (6 frames = blocks
# 0..2, fault targets id 2) kills the daemon mid-batch; across respawns
# the batchmates (2 frames = block 0 only) publish ok while only the
# culprit burns its retry budget and quarantines
culprit_path = write_frames("culprit", seed=11, n=6)
mate_path = write_frames("mates", seed=13, n=2)
kill_state = os.path.join(td, "state_kill")
kill_args = ("--lease-s", "5", "--max-job-gens", "2",
             "--microbatch-window-s", "2.0", "--microbatch-max-jobs", "3")
poison = {"CTT_FAULTS": "executor.block:kill:ids=2"}
daemon, client = spawn(kill_state, *kill_args, extra_env=poison)
culprit = submit(client, culprit_path, "culprit", tenant="bad")
mates = [submit(client, mate_path, f"mate{i}", tenant=f"t{i}", priority=5)
         for i in range(2)]
assert daemon.wait(timeout=120) == 17, "poisoned batch never killed m0"
daemon, client = spawn(kill_state, *kill_args, extra_env=poison)
assert daemon.wait(timeout=120) == 17, "gen-1 solo culprit never killed m1"
daemon, client = spawn(kill_state, *kill_args)
try:
    deadline = time.monotonic() + 120
    res = None
    while time.monotonic() < deadline:
        st = client.status(culprit)
        if st["state"] == "failed":
            res = st["result"]
            break
        time.sleep(0.2)
    assert res is not None, "poison member never quarantined"
    assert res.get("quarantined") is True, res
    for j in mates:
        st = client.wait(j, timeout_s=180)
        assert st["result"]["ok"], f"batchmate lost to the kill: {st}"
finally:
    stop(daemon)
print("microbatch smoke ok:",
      f"{batched:.0f} jobs over {batches:.0f} stacked dispatches,",
      "byte-identical to window-0, kill-poisoned culprit failed alone")
PY
microbatch_rc=$?
rm -rf "$microbatch_tmp"
if [ "$microbatch_rc" -ne 0 ]; then
    echo "microbatch smoke failed (rc=$microbatch_rc): the aggregation" \
         "window under-batched a mixed-tenant burst, broke byte-identity" \
         "vs per-job dispatch, or let a kill-poisoned member hurt its" \
         "batchmates" >&2
    exit "$microbatch_rc"
fi

echo "== ctt-ingest chaos smoke (stream a growing volume through the daemon, SIGKILL mid-stream -> successor resumes from carry, byte-identical) =="
# the ingest gate: the control plane (manifest, slab markers, carry
# records, frontier) lives on the flaky stub object store while the
# volume grows on POSIX; a serve daemon runs the long-lived ingest job,
# is SIGKILLed after the first slab commits, and a successor daemon must
# reclaim the burned generation, restore the persisted carry, finish the
# stream byte-identical (chunk digests) to a batch run over the finished
# volume, and report ctt_ingest_resumes_total >= 1 on /metrics.
ingest_tmp="$(mktemp -d)"
JAX_PLATFORMS=cpu PYTHONPATH="$repo_root${PYTHONPATH:+:$PYTHONPATH}" \
    python - "$ingest_tmp" <<'PY'
import hashlib, json, os, subprocess, sys, time

td = sys.argv[1]
repo_root = os.environ.get("PYTHONPATH", "").split(os.pathsep)[0] or "."
env = {**os.environ, "JAX_PLATFORMS": "cpu",
       "CTT_HEARTBEAT_S": "0.2"}
for k in ("CTT_TRACE_DIR", "CTT_RUN_ID"):
    env.pop(k, None)

import numpy as np
from scipy import ndimage

from cluster_tools_tpu.ingest import publish_manifest, publish_slab
from cluster_tools_tpu.ingest.runner import FRONTIER_NAME, carry_record_name
from cluster_tools_tpu.runtime import build, config as cfg
from cluster_tools_tpu.serve import ServeClient
from cluster_tools_tpu.utils import file_reader
from cluster_tools_tpu.workflows import StreamingSegmentationWorkflow

SHAPE, SLAB_DEPTH, THRESHOLD = (24, 32, 32), 8, 0.55
GCONF = {"block_shape": [8, 16, 16], "target": "tpu",
         "device_batch_size": 4, "devices": [0], "max_num_retries": 0}


def digest(root):
    h = hashlib.sha256()
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames.sort()
        for name in sorted(filenames):
            p = os.path.join(dirpath, name)
            h.update(os.path.relpath(p, root).encode())
            with open(p, "rb") as f:
                h.update(f.read())
    return h.hexdigest()


rng = np.random.default_rng(7)
raw = ndimage.gaussian_filter(rng.random(SHAPE), 1.0)
vol = ((raw - raw.min()) / (raw.max() - raw.min())).astype("float32")

path = os.path.join(td, "data.n5")
f = file_reader(path)
f.create_dataset("raw", data=vol, chunks=(8, 16, 16))
f.create_dataset("raw_live", shape=vol.shape, dtype=vol.dtype,
                 chunks=(8, 16, 16))

# batch reference over the finished volume (in-process, same configs)
config_dir = os.path.join(td, "configs_batch")
cfg.write_global_config(config_dir, dict(GCONF))
cfg.write_config(config_dir, "threshold", {"threshold": THRESHOLD})
wf = StreamingSegmentationWorkflow(
    os.path.join(td, "tmp_batch"), config_dir,
    input_path=path, input_key="raw",
    output_path=path, output_key="cc_batch", watershed=False,
)
assert build([wf]), "batch reference failed"

objroot = os.path.join(td, "objroot")
os.makedirs(objroot)
port_file = os.path.join(td, "stub.port")
stub = subprocess.Popen([
    sys.executable, os.path.join(repo_root, "tests", "objstub.py"),
    "--root", objroot, "--port-file", port_file,
    "--fail-rate", "0.05", "--seed", "7",
], env=env)
daemons = []
state_dir = os.path.join(td, "state")


def spawn():
    proc = subprocess.Popen(
        [sys.executable, "-m", "cluster_tools_tpu.serve",
         "--state-dir", state_dir, "--lease-s", "0.5"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    daemons.append(proc)
    proc.stdout.readline()  # listening banner
    ep_line = proc.stdout.readline()  # endpoint JSON
    assert ep_line, f"daemon died at startup:\n{proc.stderr.read()}"
    ep = json.loads(ep_line)
    client = ServeClient(endpoint=f"http://{ep['host']}:{ep['port']}",
                         token=ep["token"])
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        try:
            client.healthz()
            return proc, client
        except Exception:
            assert proc.poll() is None, (
                f"daemon died:\n{proc.stderr.read()}")
            time.sleep(0.1)
    raise AssertionError("daemon never became healthy")


try:
    deadline = time.monotonic() + 30
    while not os.path.exists(port_file):
        assert stub.poll() is None, "objstub died on startup"
        assert time.monotonic() < deadline, "objstub never came up"
        time.sleep(0.05)
    url = f"http://127.0.0.1:{open(port_file).read().strip()}"
    control = url + "/ingest_ctl"
    assert publish_manifest(control, SHAPE, SLAB_DEPTH)

    d1, client1 = spawn()
    job = client1.ingest(
        control_dir=control,
        input_path=path, input_key="raw_live",
        output_path=path, output_key="cc_live",
        tmp_folder=os.path.join(td, "tmp_live"),
        config_dir=os.path.join(td, "configs_live"),
        watershed=False, poll_s=0.05, timeout_s=300.0,
        configs={"global": dict(GCONF),
                 "threshold": {"threshold": THRESHOLD}},
    )

    # the acquisition: slab data to POSIX, THEN its marker to the stub
    # store (the protocol's commit order); slab 2 withheld until after
    # the kill so the takeover provably happens mid-stream
    ds = file_reader(path)["raw_live"]
    for s in (0, 1):
        z0, z1 = s * SLAB_DEPTH, (s + 1) * SLAB_DEPTH
        ds[z0:z1] = vol[z0:z1]
        assert publish_slab(control, s)

    # SIGKILL once the first carry record commits (the stub serves from
    # objroot, so the remote control dir is observable on local disk)
    carry0 = os.path.join(objroot, "ingest_ctl", carry_record_name(0))
    deadline = time.monotonic() + 180
    while not os.path.exists(carry0):
        assert d1.poll() is None, f"daemon died:\n{d1.stderr.read()}"
        assert time.monotonic() < deadline, "first carry never landed"
        time.sleep(0.05)
    d1.kill()
    d1.wait(timeout=30)

    # land the final slab; the successor reclaims the burned generation
    # (lease staleness, 3 x 0.5s) and resumes from the persisted carry
    ds[2 * SLAB_DEPTH:] = vol[2 * SLAB_DEPTH:]
    assert publish_slab(control, 2)
    d2, client2 = spawn()
    st = client2.wait(job, timeout_s=300)
    assert st["result"]["ok"], st
    assert st["result"]["gen"] >= 1, st  # the takeover generation

    f = file_reader(path, "r")
    assert np.array_equal(f["cc_live"][:], f["cc_batch"][:]), (
        "ingest labels differ from the batch run")
    assert digest(os.path.join(path, "cc_live")) == digest(
        os.path.join(path, "cc_batch")
    ), "ingest chunk bytes differ from the batch run"

    frontier = json.load(open(
        os.path.join(objroot, "ingest_ctl", FRONTIER_NAME)))
    assert frontier["slabs_done"] == frontier["slabs_total"] == 3, frontier
    assert frontier["resumes"] >= 1, frontier

    text = client2.metrics_text()
    vals = {
        ln.rsplit(" ", 1)[0]: float(ln.rsplit(" ", 1)[1])
        for ln in text.splitlines() if ln and not ln.startswith("#")
    }
    assert vals.get("ctt_ingest_resumes_total", 0) >= 1, vals
    assert vals.get("ctt_ingest_slabs_ingested_total", 0) >= 1, vals
    print("ingest smoke ok:", json.dumps({
        "gen": st["result"]["gen"],
        "resumes": vals.get("ctt_ingest_resumes_total"),
        "successor_slabs": vals.get("ctt_ingest_slabs_ingested_total"),
    }))
finally:
    for proc in daemons:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
    stub.terminate()
    stub.wait(timeout=30)
PY
ingest_rc=$?
if [ "$ingest_rc" -eq 0 ]; then
    # ctt-proto: the stream's whole control plane (manifest, slab
    # markers, carry records, frontier) plus the SIGKILL-survivor state
    # dir must match the artifact registry — resumability IS the schema
    echo "== ctt-proto conformance (ingest control + state dirs vs the artifact registry) =="
    JAX_PLATFORMS=cpu python -m cluster_tools_tpu.analysis conformance \
        "$ingest_tmp/objroot/ingest_ctl" \
    && JAX_PLATFORMS=cpu python -m cluster_tools_tpu.analysis conformance \
        "$ingest_tmp/state"
    ingest_rc=$?
    if [ "$ingest_rc" -ne 0 ]; then
        echo "conformance failed (rc=$ingest_rc): the ingest smoke left" \
             "behind files the registry does not describe — update" \
             "analysis/protocols.py or fix the writer" >&2
    fi
fi
rm -rf "$ingest_tmp"
if [ "$ingest_rc" -ne 0 ]; then
    echo "ingest smoke failed (rc=$ingest_rc): the streaming ingest lost" \
         "byte-identity vs the batch run, the successor never resumed" \
         "from the carry, or the control-plane artifacts drifted from" \
         "the registry" >&2
    exit "$ingest_rc"
fi

echo "== ctt-diskless chaos smoke (supervisor-autoscaled 1->3->1 fleet on a SigV4 stub store, SIGKILL daemon + supervisor mid-burst -> zero loss) =="
# the diskless gate: a serve fleet whose ONLY shared state is an object
# store prefix (SigV4-verified requests, 5% seeded request chaos).  A
# supervisor autoscales 1->3 under a 12-job burst; one daemon AND the
# supervisor are SIGKILLed mid-burst; a restarted supervisor re-adopts
# the fleet from beats alone.  Every job must publish an ok result,
# outputs must be byte-identical to a single-daemon POSIX-state
# reference run, the fleet must drain back to 1, /metrics must show a
# fast-path reclaim and supervisor activity, and the surviving remote
# state dir must pass protocol conformance.
diskless_tmp="$(mktemp -d)"
JAX_PLATFORMS=cpu PYTHONPATH="$repo_root${PYTHONPATH:+:$PYTHONPATH}" \
AWS_ACCESS_KEY_ID=ctt-ci-access AWS_SECRET_ACCESS_KEY=ctt-ci-secret \
CTT_S3_SIGN=1 CTT_HEARTBEAT_S=1.0 \
CTT_TRACE_DIR="$diskless_tmp/trace" CTT_RUN_ID=ci_diskless \
    python - "$diskless_tmp" <<'PY'
import hashlib, json, os, signal, subprocess, sys, time

td = sys.argv[1]
repo_root = os.environ.get("PYTHONPATH", "").split(os.pathsep)[0] or "."
env = {**os.environ}

import numpy as np

from cluster_tools_tpu.serve import ServeClient
from cluster_tools_tpu.serve.client import read_endpoint
from cluster_tools_tpu.serve.fleet import FleetView, read_peers
from cluster_tools_tpu.utils import file_reader


def digest(root):
    h = hashlib.sha256()
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames.sort()
        for name in sorted(filenames):
            p = os.path.join(dirpath, name)
            h.update(os.path.relpath(p, root).encode())
            with open(p, "rb") as f:
                h.update(f.read())
    return h.hexdigest()


def sleep_job(root_td, data_root, tag, sleep_s, phase):
    # the calibrated-cost fixture task (deterministic input * 2 + 1):
    # the reference run is fast while staying byte-identical
    return {
        "workflow": "bench_e2e_lib:SkewedCostTask",
        "kwargs": {
            "tmp_folder": os.path.join(root_td, f"tmp_{phase}_{tag}"),
            "config_dir": os.path.join(root_td, f"configs_{phase}_{tag}"),
            "input_path": f"{data_root}/{tag}.n5", "input_key": "x",
            "output_path": f"{data_root}/{tag}.n5", "output_key": "y",
        },
        "configs": {
            "global": {"block_shape": [2, 8, 8]},
            "skewed_cost": {
                "hot_z_end": 0, "base_s": float(sleep_s), "hot_s": 99.0,
            },
        },
        "tenant": tag,
    }


tags = [f"k{i}" for i in range(12)]

# -- single-daemon POSIX reference run (the digest oracle) ----------------
ref_root = os.path.join(td, "ref")
os.makedirs(ref_root)
for tag in tags:
    file_reader(os.path.join(ref_root, f"{tag}.n5")).create_dataset(
        "x", data=np.ones((2, 8, 8), dtype="float32"), chunks=(2, 8, 8))
ref = subprocess.Popen(
    [sys.executable, "-m", "cluster_tools_tpu.serve",
     "--state-dir", os.path.join(td, "state_ref"), "--daemon-id", "ref"],
    env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
)
ref.stdout.readline()
ep = json.loads(ref.stdout.readline())
try:
    ref_client = ServeClient(endpoint=f"http://{ep['host']}:{ep['port']}",
                             token=ep["token"])
    jobs = [ref_client.submit(**sleep_job(td, ref_root, t, 0.01, "ref"))
            for t in tags]
    for jid in jobs:
        assert ref_client.wait(jid, timeout_s=300)["result"]["ok"]
finally:
    ref.kill()
    ref.wait(timeout=30)

# -- the diskless fleet: SigV4 stub store, 5% chaos, supervisor ------------
objroot = os.path.join(td, "objroot")
os.makedirs(objroot)
for tag in tags:
    file_reader(os.path.join(objroot, f"{tag}.n5")).create_dataset(
        "x", data=np.ones((2, 8, 8), dtype="float32"), chunks=(2, 8, 8))
port_file = os.path.join(td, "stub.port")
stub = subprocess.Popen([
    sys.executable, os.path.join(repo_root, "tests", "objstub.py"),
    "--root", objroot, "--port-file", port_file,
    "--fail-rate", "0.05", "--seed", "23",
    "--sigv4-access-key", env["AWS_ACCESS_KEY_ID"],
    "--sigv4-secret-key", env["AWS_SECRET_ACCESS_KEY"],
], env=env)
sup = sup2 = None
sup_log = open(os.path.join(td, "supervisor.log"), "w")
try:
    deadline = time.monotonic() + 30
    while not os.path.exists(port_file):
        assert stub.poll() is None, "objstub died on startup"
        assert time.monotonic() < deadline, "objstub never came up"
        time.sleep(0.05)
    url = f"http://127.0.0.1:{open(port_file).read().strip()}"
    state_url = f"{url}/state"

    # acceptance: an UNSIGNED request against the SigV4 store is a
    # retryable auth error (EACCES), never a silent miss
    probe_env = {k: v for k, v in env.items()
                 if k not in ("AWS_ACCESS_KEY_ID", "AWS_SECRET_ACCESS_KEY",
                              "CTT_S3_SIGN")}
    probe_env["CTT_IO_RETRIES"] = "1"
    probe_env["CTT_IO_BACKOFF_BASE_S"] = "0.001"
    probe = subprocess.run(
        [sys.executable, "-c", (
            "import errno, sys\n"
            "from cluster_tools_tpu.utils.store_backend import backend_for\n"
            f"b = backend_for({url!r})\n"
            "try:\n"
            f"    b.read_bytes({url!r} + '/state/serve.json')\n"
            "except FileNotFoundError:\n"
            "    sys.exit(3)  # silent auth downgrade\n"
            "except OSError as e:\n"
            "    sys.exit(0 if e.errno == errno.EACCES else 4)\n"
            "sys.exit(5)\n"
        )], env=probe_env,
    )
    assert probe.returncode == 0, (
        f"unsigned request not a retryable auth error (rc={probe.returncode})")

    def spawn_supervisor():
        return subprocess.Popen(
            [sys.executable, "-m", "cluster_tools_tpu.serve.supervisor",
             "--state-dir", state_url, "--min", "1", "--max", "3",
             "--poll-s", "0.5",
             "--daemon-arg=--lease-s", "--daemon-arg=5",
             "--daemon-arg=--concurrency", "--daemon-arg=2"],
            env=env, stdout=sup_log, stderr=sup_log,
        )

    def live_ids():
        try:
            return sorted(FleetView(state_url).live())
        except OSError:
            return []

    def endpoint_client():
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            try:
                ep = read_endpoint(state_url)
                client = ServeClient(
                    endpoint=f"http://{ep['host']}:{ep['port']}",
                    token=ep["token"])
                client.healthz()
                return client, int(ep["pid"])
            except Exception:
                time.sleep(0.2)
        raise AssertionError("no healthy endpoint over the remote state dir")

    sup = spawn_supervisor()
    client, ep_pid = endpoint_client()  # min-floor daemon came up

    jobs = [client.submit(**sleep_job(td, url, t, 4.0, "fleet"))
            for t in tags]

    # burst pressure scales the fleet to the ceiling (capture the
    # observation: on a loaded host a re-read can transiently flicker)
    n_live = 0
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline and n_live != 3:
        assert sup.poll() is None, "supervisor died during scale-up"
        n_live = len(live_ids())
        time.sleep(0.2)
    assert n_live == 3, f"never scaled to 3: {live_ids()}"

    # SIGKILL a non-endpoint daemon once its beat proves a job in
    # flight, and SIGKILL the supervisor in the same breath
    client, ep_pid = endpoint_client()
    victim_pid = None
    deadline = time.monotonic() + 90
    while time.monotonic() < deadline and victim_pid is None:
        for did, rec in read_peers(state_url).items():
            if rec.get("torn") or rec.get("exiting"):
                continue
            pid = int(rec.get("pid") or 0)
            if pid and pid != ep_pid and rec.get("running_jobs", 0) >= 1:
                victim_pid = pid
                break
        time.sleep(0.1)
    assert victim_pid is not None, "no non-endpoint daemon went busy"
    os.kill(victim_pid, signal.SIGKILL)
    sup.kill()
    sup.wait(timeout=30)
    t_kill = time.time()

    # a RESTARTED supervisor re-adopts the fleet from beats alone
    sup2 = spawn_supervisor()

    # zero loss: every job publishes an ok result
    for jid in jobs:
        done = None
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            try:
                client, ep_pid = endpoint_client()
                done = client.wait(jid, timeout_s=60)
                break
            except Exception:
                time.sleep(0.5)
        assert done is not None and done["result"]["ok"], jid

    from cluster_tools_tpu.serve import JobQueue
    q = JobQueue(f"{state_url}/jobs", lease_s=5.0)
    results = [q.get(j)["result"] for j in jobs]
    requeued = [r for r in results if r["gen"] > 0]
    assert requeued, "the killed daemon's job never requeued"

    # byte-identity vs the single-daemon POSIX reference, reclaim incl.
    for tag in tags:
        assert digest(os.path.join(objroot, f"{tag}.n5", "y")) == digest(
            os.path.join(ref_root, f"{tag}.n5", "y")
        ), f"{tag} output differs from the single-daemon run"

    # shared-run /metrics: the fleet reclaimed the killed daemon's job
    # and the supervisors' action ledger moved (spawns + re-adoptions)
    vals = {}
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        try:
            client, ep_pid = endpoint_client()
            text = client.metrics_text()
        except Exception:
            time.sleep(0.5)
            continue
        vals = {
            ln.rsplit(" ", 1)[0]: float(ln.rsplit(" ", 1)[1])
            for ln in text.splitlines()
            if ln and not ln.startswith("#")
        }
        if (vals.get("ctt_serve_jobs_reclaimed_total", 0) >= 1
                and vals.get("ctt_serve_supervisor_spawns_total", 0) >= 1
                and vals.get("ctt_serve_supervisor_adoptions_total", 0) >= 1):
            break
        time.sleep(0.5)
    assert vals.get("ctt_serve_jobs_reclaimed_total", 0) >= 1, vals
    assert vals.get("ctt_serve_supervisor_spawns_total", 0) >= 1, vals
    assert vals.get("ctt_serve_supervisor_adoptions_total", 0) >= 1, vals

    # idle fleet drains back to the floor
    n_live = 99
    deadline = time.monotonic() + 150
    while time.monotonic() < deadline and n_live != 1:
        assert sup2.poll() is None, "restarted supervisor died"
        n_live = len(live_ids())
        time.sleep(0.3)
    assert n_live == 1, f"never drained to 1: {live_ids()}"

    # protocol conformance over the SURVIVING REMOTE state dir
    conf = subprocess.run(
        [sys.executable, "-m", "cluster_tools_tpu.analysis",
         "conformance", state_url], env=env,
    )
    assert conf.returncode == 0, (
        f"remote-state conformance failed (rc={conf.returncode})")

    print("diskless smoke ok:", json.dumps({
        "requeued": len(requeued),
        "reclaim_latency_s": round(
            min(r["finished_wall"] for r in requeued) - t_kill, 2),
        "supervisor_spawns": vals.get("ctt_serve_supervisor_spawns_total"),
        "supervisor_adoptions": vals.get(
            "ctt_serve_supervisor_adoptions_total"),
    }))
finally:
    for proc in (sup, sup2):
        if proc is not None and proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
    # orphaned daemons (their supervisors were SIGKILLed): sweep by beat
    try:
        for did, rec in read_peers(state_url).items():
            pid = int(rec.get("pid") or 0)
            if pid:
                try:
                    os.kill(pid, signal.SIGKILL)
                except OSError:
                    pass
    except Exception:
        pass
    stub.terminate()
    stub.wait(timeout=30)
    sup_log.close()
PY
diskless_rc=$?
if [ "$diskless_rc" -ne 0 ]; then
    echo "--- supervisor log tail ---" >&2
    tail -40 "$diskless_tmp/supervisor.log" >&2 || true
fi
rm -rf "$diskless_tmp"
if [ "$diskless_rc" -ne 0 ]; then
    echo "diskless smoke failed (rc=$diskless_rc): the supervisor-scaled" \
         "fleet over the SigV4 object store lost a job, broke" \
         "byte-identity, failed to re-adopt after the supervisor kill," \
         "never autoscaled 1->3->1, or left a non-conformant remote" \
         "state dir" >&2
    exit "$diskless_rc"
fi

echo "== ctt-slo smoke (mixed-priority burst -> journey phases, fleet rollup parses, slo gate 0/4) =="
# the request-grain observability gate: a 12-job mixed-priority burst
# through one short-window daemon, then the three post-hoc verbs against
# the surviving state dir alone — `obs journey` must render every phase
# (admission/queue_wait/window_wait/execution/publish/e2e), `obs fleet`
# must emit OpenMetrics the prometheus_client parser accepts, and
# `obs slo` must exit 0 on a generous objective and 4 on an impossible
# one under --fail-on-violation.
slo_tmp="$(mktemp -d)"
JAX_PLATFORMS=cpu PYTHONPATH="$repo_root${PYTHONPATH:+:$PYTHONPATH}" \
    python - "$slo_tmp" <<'PY'
import os, signal, subprocess, sys, time

td = sys.argv[1]
env = {**os.environ, "JAX_PLATFORMS": "cpu",
       "CTT_HEARTBEAT_S": "0.2"}
for k in ("CTT_TRACE_DIR", "CTT_RUN_ID"):
    env.pop(k, None)

import numpy as np
from scipy import ndimage
from cluster_tools_tpu.serve import ServeClient
from cluster_tools_tpu.utils import file_reader

gconf = {"block_shape": [2, 16, 16], "target": "local"}
rng = np.random.default_rng(3)
raw = ndimage.gaussian_filter(
    rng.random((4, 16, 16)), (0.0, 1.0, 1.0)
).astype("float32")
data = np.where(raw > np.quantile(raw, 0.9), raw, 0.0).astype("float32")
path = os.path.join(td, "burst.n5")
file_reader(path).create_dataset("frames", data=data, chunks=(2, 16, 16))

state = os.path.join(td, "state")
daemon = subprocess.Popen(
    [sys.executable, "-m", "cluster_tools_tpu.serve",
     "--state-dir", state, "--concurrency", "1",
     "--microbatch-window-s", "1.0", "--microbatch-max-jobs", "4"],
    env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
)
deadline = time.monotonic() + 120
client = None
while time.monotonic() < deadline:
    assert daemon.poll() is None, daemon.stderr.read()
    try:
        client = ServeClient(state_dir=state)
        client.healthz()
        break
    except Exception:
        time.sleep(0.1)
assert client is not None, "daemon never became healthy"

try:
    jobs = [
        client.event_batch(
            input_path=path, input_key="frames",
            output_path=path, output_key=f"ev_{i}",
            tmp_folder=os.path.join(td, f"tmp_{i}"),
            config_dir=os.path.join(td, f"configs_{i}"),
            threshold=0.1, configs={"global": dict(gconf)},
            tenant=f"t{i % 3}", priority=(i % 3) * 5,
        )
        for i in range(12)
    ]
    for j in jobs:
        st = client.wait(j, timeout_s=300)
        assert st["result"]["ok"], st
finally:
    # SIGTERM drain: run() teardown publishes the final snap.<id>.json
    if daemon.poll() is None:
        daemon.send_signal(signal.SIGTERM)
        try:
            daemon.wait(timeout=60)
        except subprocess.TimeoutExpired:
            daemon.kill()
            daemon.wait(timeout=30)

obs = [sys.executable, "-m", "cluster_tools_tpu.obs"]

# 1) journey: a job that rode the window renders every phase, purely
#    from the state-dir records (the daemon is gone)
out = subprocess.run(obs + ["journey", state, jobs[0]], env=env,
                     capture_output=True, text=True)
assert out.returncode == 0, (out.returncode, out.stderr)
for phase in ("admission", "queue_wait", "window_wait",
              "execution", "publish", "e2e"):
    assert phase in out.stdout, (f"journey missing phase {phase}",
                                 out.stdout)

# 2) fleet: the merged rollup is parser-grade OpenMetrics
fleet = subprocess.run(obs + ["fleet", state], env=env,
                       capture_output=True, text=True)
assert fleet.returncode == 0, (fleet.returncode, fleet.stderr)
from prometheus_client.openmetrics.parser import (
    text_string_to_metric_families,
)
families = {f.name for f in text_string_to_metric_families(fleet.stdout)}
assert any("serve_latency_e2e" in name for name in families), families

# 3) slo gate: generous objective met (0), impossible one violated (4)
met = subprocess.run(
    obs + ["slo", state, "--objective", "e2e_p99_s=300",
           "--fail-on-violation"],
    env=env, capture_output=True, text=True)
assert met.returncode == 0, (met.returncode, met.stdout, met.stderr)
assert "MET" in met.stdout, met.stdout
violated = subprocess.run(
    obs + ["slo", state, "--objective", "e2e_p99_s=0.000001",
           "--fail-on-violation"],
    env=env, capture_output=True, text=True)
assert violated.returncode == 4, (violated.returncode, violated.stdout,
                                  violated.stderr)
assert "VIOLATED" in violated.stdout, violated.stdout

print("slo smoke ok: journey rendered all 6 phases,",
      f"fleet rollup parsed ({len(families)} families),",
      "slo gate 0 on generous / 4 on impossible")
PY
slo_rc=$?
rm -rf "$slo_tmp"
if [ "$slo_rc" -ne 0 ]; then
    echo "slo smoke failed (rc=$slo_rc): the journey timeline lost a" \
         "phase, the fleet rollup was not parser-grade OpenMetrics, or" \
         "the slo gate exit codes broke their 0/4 contract" >&2
    exit "$slo_rc"
fi

echo "== tier-1 tests (ROADMAP.md) =="
rm -f /tmp/_t1.log
timeout -k 10 870 env JAX_PLATFORMS=cpu \
    python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider \
    -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)"
exit "$rc"
