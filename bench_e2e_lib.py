"""End-to-end multicut pipeline for bench.py (config 5 of BASELINE.md).

Shared by the device run (in-process) and the host-CPU baseline (subprocess
with JAX_PLATFORMS=cpu): the full MulticutSegmentationWorkflow —
watershed → graph → features → costs → multicut → write (reference
workflows.py:203-233) — on a synthetic CREMI-like boundary volume.
Returns the workflow wall-clock in seconds (data staging excluded).
"""

import json
import os
import sys
import tempfile
import time

import numpy as np


# the benchmarked watershed task config — ONE definition so the ws-only
# benchmark measures exactly the workload the full pipeline's first stage
# runs (run_pipeline and run_ws_pipeline must not drift apart)
WS_TASK_CONFIG = {
    "threshold": 0.5, "sigma_seeds": 2.0, "size_filter": 25,
    "halo": [2, 4, 4],
}
# the collective (whole-volume) watershed variants take the same kernel
# knobs minus the block-only halo, PLUS the per-slice mode flags matching
# the block pipeline's default (apply_dt_2d/apply_ws_2d default True
# there) — the collective 2d kernel is embarrassingly parallel over the
# z-shards and measures the same algorithm the baseline runs
SHARDED_WS_CONFIG = {
    **{k: v for k, v in WS_TASK_CONFIG.items() if k != "halo"},
    "apply_dt_2d": True,
    "apply_ws_2d": True,
}


def flood_rounds_probe(x, tile=(8, 64, 64)):
    """Flood fixpoint round counts — flat vs ctt-cc tile-warm-started — on
    the bench fixture's own DT-WS fields (threshold/sigma from
    WS_TASK_CONFIG, per-slice production mode).  Rounds, not walls: the
    crop is small and the point is the hierarchical-flood structural
    contract (ops.watershed._flood_scan_impl), recorded alongside the ws
    e2e walls in bench.py's extras."""
    import jax.numpy as jnp

    from cluster_tools_tpu.ops import watershed as ws_ops
    from cluster_tools_tpu.ops.cc import resolve_coarse_tile
    from cluster_tools_tpu.ops.dt import distance_transform_2d_stack

    xv = jnp.asarray(np.asarray(x)[:8], jnp.float32)
    fg = xv < WS_TASK_CONFIG["threshold"]
    dt = distance_transform_2d_stack(fg, pixel_pitch=None)
    seeds, _ = ws_ops.dt_seeds(
        dt, WS_TASK_CONFIG["sigma_seeds"], per_slice=True
    )
    hmap = ws_ops.make_hmap(
        xv, dt, 0.8, WS_TASK_CONFIG["sigma_seeds"], per_slice=True
    )
    out = {}
    for tag, t in (
        ("flat", None), ("tiled", resolve_coarse_tile(xv.shape, tile))
    ):
        _, _, stats = ws_ops.flood_with_stats(
            hmap, seeds, fg, per_slice=True, tile=t
        )
        out[f"ws_flood_alt_iters_{tag}"] = int(stats["flood_alt_iters"])
        out[f"ws_flood_assign_iters_{tag}"] = int(
            stats["flood_assign_iters"]
        )
        if t is not None:
            out["ws_flood_tile_iters"] = int(stats["flood_tile_iters"])
    return out


def stage_breakdown(tmp_folder):
    """Per-stage pipeline seconds summed over a run's status files — the
    three-stage executor's ``stage_{read,compute,write}_total`` records
    (one aggregate per dispatch round).  Empty dict when no staged dispatch
    ran (local target, sharded single-shot tasks, pipeline_depth 1)."""
    import json

    totals = {"read": 0.0, "compute": 0.0, "write": 0.0}
    found = False
    sdir = os.path.join(tmp_folder, "status")
    if not os.path.isdir(sdir):
        return {}
    for name in sorted(os.listdir(sdir)):
        if not name.endswith(".status.json"):
            continue
        try:
            with open(os.path.join(sdir, name)) as fh:
                st = json.load(fh)
        except (OSError, ValueError):
            continue
        for rec in st.get("timings", []):
            label = str(rec.get("label", ""))
            if label.startswith("stage_") and label.endswith("_total"):
                key = label[len("stage_"):-len("_total")]
                if key in totals:
                    totals[key] += float(rec.get("seconds", 0.0))
                    found = True
    if not found:
        return {}
    return {f"stage_{k}_s": round(v, 3) for k, v in totals.items()}


def task_breakdown(tmp_folder):
    """Per-task busy seconds from the status files — the data behind
    'where did the e2e wall go' (printed to stderr for the cold AND
    warm runs; cold-minus-warm per task isolates compile cost).

    Counts one aggregate per dispatch round: the local executor's
    "blocks_total" records (its companion "block_max" is a max, not
    an addend) and the tpu executor's per-batch "batch_*" walls.
    Batch walls can overlap under ``pipeline_depth`` > 1, so a
    task's busy seconds may legitimately exceed its wall share."""
    out = {}
    sdir = os.path.join(tmp_folder, "status")
    if not os.path.isdir(sdir):
        return out
    for name in sorted(os.listdir(sdir)):
        if not name.endswith(".status.json"):
            continue
        try:
            with open(os.path.join(sdir, name)) as fh:
                st = json.load(fh)
        except (OSError, ValueError):
            continue
        disp = sum(
            t.get("seconds", 0.0) for t in st.get("timings", [])
            if t.get("label") == "blocks_total"
            or str(t.get("label", "")).startswith("batch_")
        )
        blk = sum(float(r) for r in st.get("block_runtimes", []))
        # sum, don't assign: multi-host topologies write one status
        # file PER PROCESS (<task>.p<pid>.status.json) under the
        # same task identifier
        key = st.get("task", name)
        out[key] = round(out.get(key, 0.0) + max(disp, blk), 3)
    return out


def _stage_volume(td, vol_path, shape, block_shape, warm):
    """Load the benchmark volume into a fresh n5 container; with ``warm``
    also stage a DISTINCT (z-rolled) copy for the jit-cache-warm rerun."""
    from cluster_tools_tpu.utils import file_reader

    vol = np.load(vol_path).astype(np.float32)
    assert vol.shape == tuple(shape)
    data_path = os.path.join(td, "data.n5")
    f = file_reader(data_path)
    f.create_dataset("bnd", data=vol, chunks=tuple(block_shape))
    if warm:
        f.create_dataset(
            "bnd_warm", data=np.roll(vol, 7, axis=1),
            chunks=tuple(block_shape),
        )
    return data_path


def run_pipeline(vol_path, shape, block_shape, target, sharded_problem=False,
                 sharded_ws=False, warm=False, seg_export=None):
    """Wall-clock of the full pipeline; ``sharded_problem=True`` swaps the
    block-wise graph+features extraction for the one-program collective
    path (ShardedProblemTask + global solve); ``sharded_ws=True``
    additionally fuses the watershed into that collective session
    (ShardedWsProblemTask: the boundary volume crosses host→device ONCE
    and stays resident through watershed and RAG — since round 5 the
    bench's sharded configuration measures THIS path).

    ``warm=True`` runs the pipeline a second time in fresh scratch folders
    on a DISTINCT (z-rolled) copy of the volume and returns
    ``(cold_wall, warm_wall)``: same shapes → every jit cache is reused,
    different data → no dispatch can be served from an execution-result
    cache (the warm number must be steady-state compute, the rate a
    production sweep over many ROIs pays)."""
    from cluster_tools_tpu.runtime import build, config as cfg
    from cluster_tools_tpu.workflows import MulticutSegmentationWorkflow

    with tempfile.TemporaryDirectory() as td:
        data_path = _stage_volume(td, vol_path, shape, block_shape, warm)

        def one_run(tag, input_key):
            config_dir = os.path.join(td, f"configs{tag}")
            tmp_folder = os.path.join(td, f"tmp{tag}")
            cfg.write_global_config(
                config_dir,
                {"block_shape": list(block_shape), "target": target},
            )
            cfg.write_config(config_dir, "watershed", dict(WS_TASK_CONFIG))
            cfg.write_config(
                config_dir, "sharded_problem", {"max_edges": 1 << 17}
            )
            cfg.write_config(
                config_dir, "sharded_ws_problem",
                {"max_edges": 1 << 17, **SHARDED_WS_CONFIG},
            )
            wf = MulticutSegmentationWorkflow(
                tmp_folder, config_dir,
                input_path=data_path, input_key=input_key,
                ws_path=data_path, ws_key=f"ws{tag}",
                output_path=data_path, output_key=f"seg{tag}",
                n_scales=1,
                sharded_problem=sharded_problem,
                sharded_ws=sharded_ws,
            )
            t0 = time.perf_counter()
            ok = build([wf])
            wall = time.perf_counter() - t0
            if not ok:
                raise RuntimeError(f"e2e multicut workflow failed ({tag})")
            return wall, task_breakdown(tmp_folder)

        def show(tag, wall_s, breakdown):
            accounted = round(sum(breakdown.values()), 2)
            print(f"[e2e breakdown {tag}, wall {wall_s:.2f} s, task-busy "
                  f"{accounted} s] "
                  + " ".join(f"{k}={v}" for k, v in sorted(
                      breakdown.items(), key=lambda kv: -kv[1])),
                  file=sys.stderr, flush=True)

        wall, cold_breakdown = one_run("", "bnd")
        if seg_export is not None:
            # the cold run's final segmentation, for cross-target Rand/VoI
            # parity (BASELINE.md: "Rand-Index / VoI parity vs 'local'")
            from cluster_tools_tpu.utils import file_reader

            with file_reader(data_path, "r") as f:
                np.save(seg_export, f["seg"][:])
        if not warm:
            return wall
        # cold-vs-warm per task separates compile cost (cold only) from
        # steady-state compute — the data behind cold-wall attribution
        show("cold", wall, cold_breakdown)
        warm_wall, breakdown = one_run("_warm", "bnd_warm")
        show("warm", warm_wall, breakdown)
    return wall, warm_wall


def run_stream_pipeline(vol_path, shape, block_shape, target):
    """ctt-stream contract: the StreamingSegmentationWorkflow (threshold →
    block CC → watershed over one raw volume) run fused (one streaming
    pass, mask elided, offsets/faces from carried state) AND task-at-a-time,
    with ``store.bytes_read`` / ``store.bytes_written`` recorded from the
    obs store counters for both — the round-trip reduction lands in the
    bench JSON rather than only in wall clock.

    Byte counts are taken with the decoded-chunk LRU disabled: at bench
    scale the 64 MB cache holds the whole fixture and would hide exactly
    the cross-task re-reads the fusion removes (production volumes dwarf
    the cache, so codec-boundary traffic is the honest scale model).  Warm
    walls follow the run_ws_pipeline discipline: cold on ``bnd``, warm on
    the distinct z-rolled copy, same shapes → jit caches reused.
    """
    from cluster_tools_tpu.obs import metrics as obs_metrics, trace as obs_trace
    from cluster_tools_tpu.runtime import build, config as cfg
    from cluster_tools_tpu.utils import file_reader, store as store_mod
    from cluster_tools_tpu.workflows import StreamingSegmentationWorkflow

    with tempfile.TemporaryDirectory() as td:
        data_path = _stage_volume(td, vol_path, shape, block_shape, True)
        trace_was_on = obs_trace.enabled()
        if not trace_was_on:
            obs_trace.enable(
                os.path.join(td, "trace"), "stream_bench", export_env=False
            )
        prev_budget = store_mod.set_chunk_cache_budget(0)
        try:
            def one(tag, fused, input_key):
                config_dir = os.path.join(td, f"configs_{tag}")
                cfg.write_global_config(
                    config_dir,
                    {"block_shape": list(block_shape), "target": target,
                     "stream_fusion": fused},
                )
                cfg.write_config(config_dir, "threshold", {"threshold": 0.5})
                cfg.write_config(
                    config_dir, "watershed", dict(WS_TASK_CONFIG)
                )
                wf = StreamingSegmentationWorkflow(
                    os.path.join(td, f"tmp_{tag}"), config_dir,
                    input_path=data_path, input_key=input_key,
                    output_path=data_path, output_key=f"cc_{tag}",
                )
                before = obs_metrics.snapshot()["counters"]
                t0 = time.perf_counter()
                ok = build([wf])
                wall = time.perf_counter() - t0
                after = obs_metrics.snapshot()["counters"]
                if not ok:
                    raise RuntimeError(f"stream pipeline failed ({tag})")

                def delta(name):
                    return after.get(name, 0.0) - before.get(name, 0.0)

                return (wall, delta("store.bytes_read"),
                        delta("store.bytes_written"))

            one("un_cold", False, "bnd")
            un_warm, un_read, un_written = one("un_warm", False, "bnd_warm")
            one("f_cold", True, "bnd")
            f_warm, f_read, f_written = one("f_warm", True, "bnd_warm")

            with file_reader(data_path, "r") as f:
                parity = bool(
                    np.array_equal(f["cc_un_warm"][:], f["cc_f_warm"][:])
                    and np.array_equal(
                        f["cc_un_warm_ws"][:], f["cc_f_warm_ws"][:]
                    )
                )
        finally:
            store_mod.set_chunk_cache_budget(prev_budget)
            if not trace_was_on:
                obs_trace.disable()
    return {
        "ws_e2e_store_bytes_read": int(un_read),
        "ws_e2e_store_bytes_written": int(un_written),
        "ws_e2e_stream_store_bytes_read": int(f_read),
        "ws_e2e_stream_store_bytes_written": int(f_written),
        "ws_e2e_stream_read_reduction": round(un_read / max(f_read, 1.0), 2),
        "ws_e2e_stream_warm_wall_s": round(f_warm, 2),
        "ws_e2e_stream_unfused_warm_wall_s": round(un_warm, 2),
        "ws_e2e_stream_parity": parity,
    }


_SKEWED_TASK_CLS = None


def _skewed_cost_task_cls():
    """Build (once) the skewed-cost fixture task for the scheduler A/B
    bench.  Defined lazily so importing bench_e2e_lib stays free of
    cluster_tools_tpu imports (the cpu-baseline subprocess imports this
    module before pinning its jax platform), but published as module
    attribute ``SkewedCostTask`` (via the PEP 562 ``__getattr__`` below)
    so the driver can pickle it to ``task.pkl`` and scheduler workers can
    unpickle it by reference."""
    global _SKEWED_TASK_CLS
    if _SKEWED_TASK_CLS is not None:
        return _SKEWED_TASK_CLS
    from cluster_tools_tpu.tasks.base import VolumeTask

    class SkewedCostTask(VolumeTask):
        """Every block writes a deterministic transform of its input
        (byte-comparable across scheduling modes); per-block cost is a
        calibrated stall — blocks whose z-origin falls in the hot z-slab
        cost ``hot_s`` seconds, the rest ``base_s`` (the ~8x hot-slab
        skew).  A sleep, not a compute loop, so the measured walls
        isolate SCHEDULING (assignment + queue mechanics) from kernel
        throughput and CPU contention between the worker processes."""

        task_name = "skewed_cost"
        output_dtype = "float32"

        def process_block(self, block_id, blocking, config):
            bb = blocking.block(block_id)
            x = self.input_ds()[bb.slicing]
            hot = bb.begin[0] < int(config.get("hot_z_end", 0))
            time.sleep(
                float(config["hot_s"]) if hot else float(config["base_s"])
            )
            self.output_ds()[bb.slicing] = (
                np.asarray(x, dtype="float32") * 2.0 + 1.0
            )

    SkewedCostTask.__module__ = __name__
    SkewedCostTask.__qualname__ = "SkewedCostTask"
    _SKEWED_TASK_CLS = SkewedCostTask
    return SkewedCostTask


def __getattr__(name):
    # PEP 562: lets pickle resolve bench_e2e_lib.SkewedCostTask in worker
    # processes without paying the cluster_tools_tpu import at module load
    if name == "SkewedCostTask":
        return _skewed_cost_task_cls()
    raise AttributeError(name)


def _write_async_stub_scheduler(folder, piddir):
    """sbatch/squeue stand-in that runs jobs in the BACKGROUND (unlike the
    test suite's synchronous stub): submission returns immediately and the
    queue command reports one line per still-running job pid — so n_jobs
    workers really execute concurrently, which is the whole point of a
    scheduler bench."""
    os.makedirs(folder, exist_ok=True)
    os.makedirs(piddir, exist_ok=True)
    submit = os.path.join(folder, "stub_submit")
    with open(submit, "w") as f:
        f.write(
            "#!/bin/bash\n"
            'script="${@: -1}"\n'
            'bash "$script" >/dev/null 2>&1 &\n'
            f'echo "$!" >> {piddir}/pids\n'
            'echo "Submitted batch job $!"\n'
        )
    queue = os.path.join(folder, "stub_queue")
    with open(queue, "w") as f:
        f.write(
            "#!/bin/bash\n"
            f'[ -f {piddir}/pids ] || exit 0\n'
            "while read -r p; do\n"
            '  kill -0 "$p" 2>/dev/null && echo RUNNING\n'
            f"done < {piddir}/pids\n"
            "exit 0\n"
        )
    import stat as _stat

    for p in (submit, queue):
        os.chmod(p, os.stat(p).st_mode | _stat.S_IEXEC)
    return submit, queue


def run_steal_pipeline(n_jobs=4, n_z_blocks=25, base_s=1.5, hot_s=12.0):
    """ctt-steal contract: static round-robin vs work-stealing wall clock
    on the async stub scheduler, over a skewed-cost fixture — a hot
    z-slab whose block costs ``hot_s / base_s`` (~8x) as much as the
    rest.  Geometry makes the skew bite the frozen split the way a hot
    volume region bites a real run: slab-blocks (one block per z-slab),
    so ``ids[0::n_jobs]`` pins the hot slab AND an equal share of cold
    slabs on job 0 while its siblings go idle — the stealing queue
    redistributes the cold tail and the wall collapses toward the hot
    block's own cost.  Both paths must be byte-identical
    (``ws_e2e_steal_parity``)."""
    from cluster_tools_tpu.runtime import build, config as cfg
    from cluster_tools_tpu.utils import file_reader

    task_cls = _skewed_cost_task_cls()
    rng = np.random.default_rng(0)
    bz, ny, nx = 2, 16, 16
    vol = rng.random((n_z_blocks * bz, ny, nx)).astype("float32")

    with tempfile.TemporaryDirectory() as td:
        walls = {}
        outputs = {}
        for tag, sched in (("static", "static"), ("steal", "steal")):
            submit, queue = _write_async_stub_scheduler(
                os.path.join(td, f"sched_{tag}"),
                os.path.join(td, f"pids_{tag}"),
            )
            path = os.path.join(td, f"{tag}.n5")
            file_reader(path).create_dataset(
                "x", data=vol, chunks=(bz, ny, nx)
            )
            config_dir = os.path.join(td, f"configs_{tag}")
            cfg.write_global_config(config_dir, {
                "block_shape": [bz, ny, nx],
                "target": "slurm",
                "max_jobs": n_jobs,
                "sched": sched,
                # one block per lease: the finest redistribution grain,
                # matching the one-block-per-slab fixture
                "steal_batch_size": 1,
                "steal_lease_s": 0.5,
                # A/B purity: the hot block is legitimately 8x, not a dead
                # straggler — duplication would re-run it on an idle
                # worker whose (harmless, losing) copy keeps its job alive
                # past the owner's finish and pads the measured wall
                "steal_duplicate": False,
                "poll_interval_s": 0.2,
                "sbatch_cmd": submit,
                "squeue_cmd": queue,
                "worker_env": {
                    "JAX_PLATFORMS": "cpu",
                },
            })
            cfg.write_config(config_dir, "skewed_cost", {
                "hot_z_end": bz,  # the first z-slab is the hot one
                "base_s": float(base_s),
                "hot_s": float(hot_s),
            })
            task = task_cls(
                os.path.join(td, f"tmp_{tag}"), config_dir,
                max_jobs=n_jobs,
                input_path=path, input_key="x",
                output_path=path, output_key="y",
            )
            t0 = time.perf_counter()
            ok = build([task])
            walls[tag] = time.perf_counter() - t0
            if not ok:
                raise RuntimeError(f"steal bench run failed ({tag})")
            outputs[tag] = path

        with file_reader(outputs["static"], "r") as fs, \
                file_reader(outputs["steal"], "r") as fw:
            parity = bool(np.array_equal(fs["y"][:], fw["y"][:]))

    return {
        "ws_e2e_steal_static_wall_s": round(walls["static"], 2),
        "ws_e2e_steal_wall_s": round(walls["steal"], 2),
        "ws_e2e_steal_speedup": round(
            walls["static"] / max(walls["steal"], 1e-9), 2
        ),
        "ws_e2e_steal_parity": parity,
    }


def run_serve_pipeline(n_jobs=6, shape=(8, 32, 32), block_shape=(8, 16, 16)):
    """ctt-serve contract: N back-to-back small watershed workflows,
    cold-process vs daemon-submitted — the amortization headline.

    The cold path is the pre-serve deployment: each workflow runs in a
    FRESH python process (interpreter + jax import + cache loads + build),
    sequentially — what a sweep of small user submissions used to cost.
    The serve path starts ONE ``python -m cluster_tools_tpu.serve`` daemon
    and submits the same N workflows back-to-back over its HTTP API; the
    daemon's warm ExecutionContext (in-process jit caches, devices, chunk
    LRU) makes every job after the first marginal-cost.

    Discipline: both paths share the persistent on-disk compile cache
    and each runs one UNTIMED warmup workflow first (the warm-vs-warm
    convention of this suite — the disk cache is equally hot for both, so
    the measured gap is process amortization, not disk-cache luck).  Each
    of the N jobs gets its OWN volume (z-rolled copies), identical
    between the paths, and every output must be byte-identical
    (``ws_e2e_serve_parity``: arrays + chunk-file digests).  Runs pinned
    to JAX_PLATFORMS=cpu like the steal bench: the quantity under test is
    scheduling/setup amortization, not kernel throughput."""
    import hashlib
    import signal
    import subprocess

    from cluster_tools_tpu.serve import ServeClient

    here = os.path.dirname(os.path.abspath(__file__))
    rng = np.random.default_rng(0)
    from scipy import ndimage

    base = ndimage.gaussian_filter(rng.random(shape), (1.0, 2.0, 2.0))
    base = ((base - base.min()) / (base.max() - base.min())).astype(
        "float32"
    )
    ws_conf = {"threshold": 0.5, "sigma_seeds": 1.6, "size_filter": 10,
               "halo": [2, 4, 4]}
    gconf = {"block_shape": list(block_shape), "target": "tpu"}
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    for k in ("CTT_TRACE_DIR", "CTT_RUN_ID"):
        env.pop(k, None)

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

    with tempfile.TemporaryDirectory() as td:
        from cluster_tools_tpu.utils import file_reader

        vols = {}
        for i in range(-1, n_jobs):  # -1 = the untimed warmup volume
            vols[i] = np.roll(base, 3 * (i + 1), axis=1)
            for side in ("cold", "serve"):
                file_reader(
                    os.path.join(td, f"{side}_{i}.n5")
                ).create_dataset(
                    "bnd", data=vols[i], chunks=tuple(block_shape)
                )

        driver = os.path.join(td, "cold_driver.py")
        with open(driver, "w") as f:
            f.write(
                "import os, sys\n"
                "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
                f"sys.path.insert(0, {here!r})\n"
                "import jax\n"
                "jax.config.update('jax_platforms', 'cpu')\n"
                "from cluster_tools_tpu.runtime import build, config as cfg\n"
                "from cluster_tools_tpu.workflows import WatershedWorkflow\n"
                "data_path, tag, td = sys.argv[1:4]\n"
                "config_dir = os.path.join(td, 'configs_' + tag)\n"
                f"cfg.write_global_config(config_dir, {gconf!r})\n"
                f"cfg.write_config(config_dir, 'watershed', {ws_conf!r})\n"
                "wf = WatershedWorkflow(\n"
                "    os.path.join(td, 'tmp_' + tag), config_dir,\n"
                "    input_path=data_path, input_key='bnd',\n"
                "    output_path=data_path, output_key='ws')\n"
                "assert build([wf])\n"
            )

        def one_cold(i, tag):
            proc = subprocess.run(
                [sys.executable, driver,
                 os.path.join(td, f"cold_{i}.n5"), tag, td],
                capture_output=True, text=True, env=env, timeout=600,
            )
            if proc.returncode != 0:
                raise RuntimeError(
                    f"cold run {tag} failed:\n{proc.stderr[-2000:]}"
                )

        one_cold(-1, "warmup")  # disk compile cache hot for BOTH paths
        t0 = time.perf_counter()
        for i in range(n_jobs):
            one_cold(i, f"c{i}")
        cold_wall = time.perf_counter() - t0

        state_dir = os.path.join(td, "serve_state")
        daemon = subprocess.Popen(
            [sys.executable, "-m", "cluster_tools_tpu.serve",
             "--state-dir", state_dir],
            env=env, cwd=here,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        try:
            deadline = time.perf_counter() + 120
            client = None
            while time.perf_counter() < deadline:
                if daemon.poll() is not None:
                    raise RuntimeError(
                        f"serve daemon died:\n{daemon.stderr.read()[-2000:]}"
                    )
                try:
                    client = ServeClient(state_dir=state_dir)
                    client.healthz()
                    break
                except Exception:
                    time.sleep(0.1)
            if client is None:
                raise RuntimeError("serve daemon never became healthy")

            def submit(i, tag):
                data_path = os.path.join(td, f"serve_{i}.n5")
                return client.submit(
                    "WatershedWorkflow",
                    {
                        "tmp_folder": os.path.join(td, f"tmp_s_{tag}"),
                        "config_dir": os.path.join(td, f"configs_s_{tag}"),
                        "input_path": data_path, "input_key": "bnd",
                        "output_path": data_path, "output_key": "ws",
                    },
                    configs={"global": dict(gconf),
                             "watershed": dict(ws_conf)},
                )

            client.wait(submit(-1, "warmup"), timeout_s=600)
            t0 = time.perf_counter()
            job_ids = [submit(i, f"s{i}") for i in range(n_jobs)]
            for jid in job_ids:
                client.wait(jid, timeout_s=600)
            serve_wall = time.perf_counter() - t0
        finally:
            daemon.send_signal(signal.SIGTERM)
            try:
                daemon.wait(timeout=60)
            except subprocess.TimeoutExpired:
                daemon.kill()
                daemon.wait(timeout=30)

        parity = True
        for i in range(n_jobs):
            cold_path = os.path.join(td, f"cold_{i}.n5")
            serve_path = os.path.join(td, f"serve_{i}.n5")
            with file_reader(cold_path, "r") as fc, \
                    file_reader(serve_path, "r") as fs:
                if not np.array_equal(fc["ws"][:], fs["ws"][:]):
                    parity = False
            if digest(os.path.join(cold_path, "ws")) != digest(
                os.path.join(serve_path, "ws")
            ):
                parity = False

    return {
        "ws_e2e_serve_jobs": int(n_jobs),
        "ws_e2e_serve_cold_wall_s": round(cold_wall, 2),
        "ws_e2e_serve_wall_s": round(serve_wall, 2),
        "ws_e2e_serve_speedup": round(cold_wall / max(serve_wall, 1e-9), 2),
        "ws_e2e_serve_parity": parity,
    }


def run_hbm_pipeline(shape=(48, 384, 384), block_shape=(8, 32, 32),
                     warm_reps=3):
    """ctt-hbm contract: back-to-back serve jobs on the SAME volume —
    warm HBM (device-buffer cache + aggregated dispatch + double-buffered
    upload stage) vs the PR 9/10 serve warm path, through one daemon each.

    Two daemons over the same input volume, each warm-vs-warm:

      * **hbm** — ``hbm_cache_mb`` default (512), ``hbm_stack: 8``,
        transfer stage on.  Job 1 is the cold-HBM measurement (uploads
        cross), job 2 the warm one: every batch is signature-validated
        HBM-resident, so uploads AND host input reads are skipped.
      * **base** — ``hbm_cache_mb: 0``, ``hbm_stack: 1``,
        ``hbm_prefetch: false``: the exact pre-hbm execution (the
        ctt-cloud LRU prefetch stays on — the honest PR 10 baseline).

    The fixture is a threshold sweep (compute-light, transfer/dispatch-
    bound — the workload shape the HBM levers target; a flood-heavy
    kernel measures the device kernel instead, see ws_e2e_warm_wall_s).
    Both daemons share the disk compile cache and run one untimed warmup
    job; the gated records are the per-job `/metrics` deltas of
    ``ctt_device_upload_bytes_total`` (warm ≈ 0 vs nonzero cold), the
    warm job's dispatch count (aggregation: << block count), the upload
    seconds hidden behind compute on the cold job, and the warm-vs-warm
    wall ratio.  Outputs of all four jobs must be byte-identical
    including chunk digests.  Pinned to JAX_PLATFORMS=cpu like the other
    scheduling benches: the quantity under test is transfer/dispatch
    economics, not kernel throughput."""
    import hashlib
    import signal
    import subprocess

    from cluster_tools_tpu.serve import ServeClient

    here = os.path.dirname(os.path.abspath(__file__))
    rng = np.random.default_rng(0)
    vol = rng.random(shape).astype("float32")
    n_blocks = 1
    for s, b in zip(shape, block_shape):
        n_blocks *= -(-s // b)
    thr_conf = {"threshold": 0.5}
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    for k in ("CTT_TRACE_DIR", "CTT_RUN_ID"):
        env.pop(k, None)

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

    def scrape(client):
        text = client.metrics_text()
        out = {}
        for line in text.splitlines():
            if line and not line.startswith("#") and " " in line:
                name, val = line.split(" ", 1)
                try:
                    out[name] = float(val)
                except ValueError:
                    pass
        return out

    with tempfile.TemporaryDirectory() as td:
        from cluster_tools_tpu.runtime import config as cfg_mod
        from cluster_tools_tpu.utils import file_reader

        data_path = os.path.join(td, "vol.n5")
        file_reader(data_path).create_dataset(
            "bnd", data=vol, chunks=tuple(block_shape)
        )
        # the warmup job gets its OWN volume: it exists to heat the disk
        # compile cache for both daemons — running it on the measured
        # volume would leave job 1 HBM-warm and erase the cold
        # upload-bytes record
        warm_path = os.path.join(td, "vol_warmup.n5")
        file_reader(warm_path).create_dataset(
            "bnd", data=np.roll(vol, 7, axis=1), chunks=tuple(block_shape)
        )
        stats = {}
        for side, gextra, sconf in (
            ("hbm", {"hbm_stack": 8}, {}),
            ("base", {"hbm_stack": 1, "hbm_prefetch": False},
             {"hbm_cache_mb": 0}),
        ):
            state_dir = os.path.join(td, f"state_{side}")
            if sconf:
                cfg_mod.write_config(state_dir, "serve", sconf)
            daemon = subprocess.Popen(
                [sys.executable, "-m", "cluster_tools_tpu.serve",
                 "--state-dir", state_dir],
                env=env, cwd=here,
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            )
            try:
                deadline = time.perf_counter() + 120
                client = None
                while time.perf_counter() < deadline:
                    if daemon.poll() is not None:
                        raise RuntimeError(
                            "hbm bench daemon died:\n"
                            f"{daemon.stderr.read()[-2000:]}"
                        )
                    try:
                        client = ServeClient(state_dir=state_dir)
                        client.healthz()
                        break
                    except Exception:
                        time.sleep(0.1)
                if client is None:
                    raise RuntimeError("hbm bench daemon never came up")

                def submit(tag):
                    out_path = os.path.join(td, f"out_{side}.n5")
                    src = warm_path if tag == "warmup" else data_path
                    return client.submit_and_wait(
                        "cluster_tools_tpu.tasks.threshold:ThresholdTask",
                        {
                            "tmp_folder": os.path.join(
                                td, f"tmp_{side}_{tag}"),
                            "config_dir": os.path.join(
                                td, f"configs_{side}_{tag}"),
                            "input_path": src, "input_key": "bnd",
                            "output_path": out_path,
                            "output_key": f"thr_{tag}",
                        },
                        configs={
                            "global": {
                                "block_shape": list(block_shape),
                                "target": "tpu", "pipeline_depth": 3,
                                **gextra,
                            },
                            "threshold": dict(thr_conf),
                        },
                        timeout_s=600,
                    )

                submit("warmup")  # untimed: disk compile cache hot
                m0 = scrape(client)
                s1 = submit("j1")
                m1 = scrape(client)
                # several warm reps, median wall: the jobs are seconds-
                # scale, so one burst of host load must not decide the A/B
                warm_walls = []
                for rep in range(max(int(warm_reps), 1)):
                    s2 = submit(f"j2r{rep}")
                    warm_walls.append(float(s2["result"]["seconds"]))
                m2 = scrape(client)
                stats[side] = {
                    "cold_s": float(s1["result"]["seconds"]),
                    "warm_s": float(np.median(warm_walls)),
                    "m0": m0, "m1": m1, "m2": m2,
                }
            finally:
                daemon.send_signal(signal.SIGTERM)
                try:
                    daemon.wait(timeout=60)
                except subprocess.TimeoutExpired:
                    daemon.kill()
                    daemon.wait(timeout=30)

        parity = True
        fa = file_reader(os.path.join(td, "out_hbm.n5"), "r")
        fb = file_reader(os.path.join(td, "out_base.n5"), "r")
        tags = ["j1"] + [f"j2r{r}" for r in range(max(int(warm_reps), 1))]
        for tag in tags:
            if not np.array_equal(fa[f"thr_{tag}"][:], fb[f"thr_{tag}"][:]):
                parity = False
            if digest(os.path.join(td, "out_hbm.n5", f"thr_{tag}")) != \
                    digest(os.path.join(td, "out_base.n5", f"thr_{tag}")):
                parity = False

        def delta(side, a, b, name):
            return stats[side][b].get(name, 0.0) - stats[side][a].get(
                name, 0.0
            )

        up = "ctt_device_upload_bytes_total"
        cold_upload = delta("hbm", "m0", "m1", up)
        # warm window spans warm_reps jobs: bytes stay 0 in total, the
        # dispatch record normalizes to one job
        warm_upload = delta("hbm", "m1", "m2", up)
        warm_dispatches = delta(
            "hbm", "m1", "m2", "ctt_device_dispatches_total"
        ) / max(int(warm_reps), 1)
        # seconds of host→HBM transfer the double-buffered stage ran on
        # the transfer thread — i.e. moved OFF the in-order dispatch
        # thread's critical path — during the cold (upload-heavy) job
        overlap = delta("hbm", "m0", "m1",
                        "ctt_executor_stage_upload_s_total")

    return {
        "ws_e2e_hbm_blocks": int(n_blocks),
        "ws_e2e_hbm_upload_bytes_cold": int(cold_upload),
        "ws_e2e_hbm_upload_bytes_warm": int(warm_upload),
        "ws_e2e_hbm_dispatches": int(warm_dispatches),
        "ws_e2e_hbm_overlap_s": round(overlap, 3),
        "ws_e2e_hbm_warm_wall_s": round(stats["hbm"]["warm_s"], 3),
        "ws_e2e_hbm_base_warm_wall_s": round(stats["base"]["warm_s"], 3),
        "ws_e2e_hbm_warm_speedup": round(
            stats["base"]["warm_s"] / max(stats["hbm"]["warm_s"], 1e-9), 2
        ),
        "ws_e2e_hbm_parity": parity,
    }


def run_hier_pipeline(shape=(48, 384, 384), block_shape=(8, 64, 64),
                      n_thresholds=3):
    """ctt-hier contract: build the merge hierarchy ONCE through a serve
    daemon, then sweep merge thresholds as warm ``resegment`` jobs against
    the same daemon — vs a FULL pipeline re-run per threshold.

    The sweep step is the interactive mode (``write_volume: false``): the
    job loads the (daemon-warm) artifact, thresholds the sorted saddle
    column, runs ONE value-space union-find pass and persists the relabel
    table — what a proofreading slider applies to its current view.  The
    comparator is what the reference stack does for every slider move: a
    complete re-run (hierarchy build + volume re-cut) at the same
    threshold, itself WARM (same daemon, hot jit caches — charitable to
    the baseline).  One volume-mode warm re-cut is also measured (the
    "commit this threshold" job; its reads ride the warm ctt-hbm
    DeviceBufferCache — the gated record asserts zero upload bytes across
    the whole warm window).

    Parity: at every swept threshold the persisted table applied to the
    labels volume must equal the full re-run's re-cut volume as a label
    PARTITION (RI == 1.0).  Pinned to JAX_PLATFORMS=cpu like the other
    scheduling benches — the quantity under test is amortization
    structure, not kernel throughput."""
    import signal
    import subprocess

    from cluster_tools_tpu.serve import ServeClient

    here = os.path.dirname(os.path.abspath(__file__))
    rng = np.random.default_rng(0)
    from scipy import ndimage

    raw = ndimage.gaussian_filter(rng.random(shape), (1.0, 2.0, 2.0))
    raw = ((raw - raw.min()) / (raw.max() - raw.min())).astype("float32")
    gconf = {"block_shape": list(block_shape), "target": "tpu",
             "pipeline_depth": 3}
    blocks_conf = {"threshold": 0.5, "sigma_seeds": 1.6, "size_filter": 10}
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    for k in ("CTT_TRACE_DIR", "CTT_RUN_ID"):
        env.pop(k, None)

    def scrape(client):
        out = {}
        for line in client.metrics_text().splitlines():
            if line and not line.startswith("#") and " " in line:
                name, val = line.rsplit(" ", 1)
                try:
                    out[name] = float(val)
                except ValueError:
                    pass
        return out

    def partition_ri(a, b):
        from cluster_tools_tpu.ops.evaluation import rand_scores
        from cluster_tools_tpu.ops.segment import contingency_table

        ia, ib, counts = contingency_table(
            np.asarray(a, np.uint64), np.asarray(b, np.uint64)
        )
        return rand_scores(ia, ib, counts)["rand_index"]

    with tempfile.TemporaryDirectory() as td:
        from cluster_tools_tpu.ops import hier as hier_ops
        from cluster_tools_tpu.utils import file_reader

        data_path = os.path.join(td, "vol.n5")
        file_reader(data_path).create_dataset(
            "bnd", data=raw, chunks=tuple(block_shape)
        )
        state_dir = os.path.join(td, "state")
        daemon = subprocess.Popen(
            [sys.executable, "-m", "cluster_tools_tpu.serve",
             "--state-dir", state_dir],
            env=env, cwd=here,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        try:
            deadline = time.perf_counter() + 120
            client = None
            while time.perf_counter() < deadline:
                if daemon.poll() is not None:
                    raise RuntimeError(
                        "hier bench daemon died:\n"
                        f"{daemon.stderr.read()[-2000:]}"
                    )
                try:
                    client = ServeClient(state_dir=state_dir)
                    client.healthz()
                    break
                except Exception:
                    time.sleep(0.1)
            if client is None:
                raise RuntimeError("hier bench daemon never came up")

            def build_job(tag, out_key):
                return client.submit_and_wait(
                    "HierarchyWorkflow",
                    {
                        "tmp_folder": os.path.join(td, f"tmp_{tag}"),
                        "config_dir": os.path.join(td, f"configs_{tag}"),
                        "input_path": data_path, "input_key": "bnd",
                        "output_path": data_path, "output_key": out_key,
                    },
                    configs={"global": dict(gconf),
                             "hierarchy_blocks": dict(blocks_conf)},
                    timeout_s=1200,
                )

            def reseg_job(tag, labels_key, out_key, t, write_volume):
                job = client.resegment(
                    hierarchy=os.path.join(
                        data_path, f"{labels_key}_hierarchy.npz"
                    ),
                    labels_path=data_path, labels_key=labels_key,
                    output_path=data_path, output_key=out_key,
                    threshold=t, write_volume=write_volume,
                    tmp_folder=os.path.join(td, f"tmp_{tag}"),
                    config_dir=os.path.join(td, f"configs_{tag}"),
                    configs={"global": dict(gconf)},
                )
                return client.wait(job, timeout_s=1200)

            # the one-time hierarchy build (cold: first flood + compiles)
            s_build = build_job("build", "seg")
            build_wall = float(s_build["result"]["seconds"])
            art = hier_ops.load_hierarchy(
                os.path.join(data_path, "seg_hierarchy.npz")
            )
            qs = np.linspace(0.25, 0.75, max(int(n_thresholds), 1))
            ts = [float(t) for t in np.quantile(art["saddle"], qs)]

            # untimed warmups: one volume re-cut (warms the HBM cache +
            # gather compiles) and one table cut (warms the union-find
            # shape buckets) — the sweep measures steady state
            reseg_job("warm_vol", "seg", "seg_wv", ts[0], True)
            reseg_job("warm_tab", "seg", "seg_wt", ts[len(ts) // 2],
                      False)

            m1 = scrape(client)
            sweep_walls = []
            for i, t in enumerate(ts):
                st = reseg_job(f"sweep{i}", "seg", f"cut{i}", t, False)
                sweep_walls.append(float(st["result"]["seconds"]))
            s_vol = reseg_job(
                "commit", "seg", "seg_commit", ts[len(ts) // 2], True
            )
            m2 = scrape(client)
            warm_upload = m2.get(
                "ctt_device_upload_bytes_total", 0.0
            ) - m1.get("ctt_device_upload_bytes_total", 0.0)

            # the baseline: a FULL pipeline re-run per threshold (fresh
            # tmp folders, same daemon = warm compiles for it too)
            full_walls = []
            for i, t in enumerate(ts):
                sb = build_job(f"full{i}", f"seg_f{i}")
                sr = reseg_job(
                    f"fullcut{i}", f"seg_f{i}", f"seg_f{i}_t", t, True
                )
                full_walls.append(
                    float(sb["result"]["seconds"])
                    + float(sr["result"]["seconds"])
                )

            # parity: the sweep's relabel table applied to the labels
            # volume == the full re-run's re-cut volume, as a partition
            f = file_reader(data_path, "r")
            seg = f["seg"][:]
            parity = True
            for i, t in enumerate(ts):
                cut = hier_ops.load_cut_table(
                    os.path.join(data_path, f"cut{i}_cut.npz")
                )
                swept = hier_ops.apply_cut_np(
                    seg, cut["vals"], cut["roots"]
                )
                full = f[f"seg_f{i}_t"][:]
                if partition_ri(swept, full) != 1.0:
                    parity = False
        finally:
            daemon.send_signal(signal.SIGTERM)
            try:
                daemon.wait(timeout=60)
            except subprocess.TimeoutExpired:
                daemon.kill()
                daemon.wait(timeout=30)

    return {
        "ws_e2e_hier_blocks": int(np.prod([
            -(-s // b) for s, b in zip(shape, block_shape)
        ])),
        "ws_e2e_hier_edges": int(art["a"].size),
        "ws_e2e_hier_build_wall_s": round(build_wall, 2),
        "ws_e2e_hier_sweep_ms_warm": round(
            float(np.median(sweep_walls)) * 1e3, 1
        ),
        "ws_e2e_hier_recut_volume_s": round(
            float(s_vol["result"]["seconds"]), 3
        ),
        "ws_e2e_hier_full_rerun_s": round(float(np.mean(full_walls)), 2),
        "ws_e2e_hier_sweep_speedup": round(
            float(np.mean(full_walls))
            / max(float(np.median(sweep_walls)), 1e-9), 1
        ),
        "ws_e2e_hier_upload_bytes_warm": int(warm_upload),
        "ws_e2e_hier_parity": parity,
    }


def run_events_pipeline(n_frames=64, frame_shape=(512, 512),
                        soak_submissions=1000):
    """ctt-events contract, both legs of the acceptance gate.

    Throughput: ONE batched ``build_events`` dispatch over an
    ``(n_frames, h, w)`` detector stack vs the per-frame host baseline
    (``scipy.ndimage.label`` + numpy property reduction — exactly what a
    pre-batching event builder runs per frame).  Gate: >= 10x frames/s
    with EXACT label/count parity and close props.

    Soak: an in-process serve daemon at a deliberately tiny admission
    envelope (tenant_quota 2, queue depth 4) takes a burst of
    ``soak_submissions`` ``event_batch`` submissions — the "millions of
    users" request shape scaled to CI.  Past-capacity submissions must
    be CLEAN 429s, every accepted job must finish ok, /metrics must stay
    parseable mid-burst, and the process must return to its pre-burst
    thread/fd baseline with zero lease-renewer threads left — the
    serve-path per-request allocation audit, benched."""
    import threading

    from scipy import ndimage

    from cluster_tools_tpu.ops import events as events_ops

    rng = np.random.default_rng(0)
    raw = ndimage.gaussian_filter(
        rng.random((n_frames,) + tuple(frame_shape)), (0.0, 1.0, 1.0)
    ).astype("float32")
    # ~1% occupancy of compact blobs — the Timepix-like regime the
    # throughput gate is specified against
    frames = np.where(raw > np.quantile(raw, 0.99), raw, 0.0).astype(
        "float32"
    )
    hits = rng.random(frames.shape) > 0.999
    frames[hits] = (rng.random(int(hits.sum())) + 1.0).astype("float32")

    # -- throughput leg ----------------------------------------------------
    compiles0 = events_ops.kernel_cache_size()
    labels, counts, props = events_ops.build_events(frames)  # warm/compile
    compiles = events_ops.kernel_cache_size() - compiles0
    dev_walls = []
    for _ in range(3):
        t0 = time.perf_counter()
        labels, counts, props = events_ops.build_events(frames)
        dev_walls.append(time.perf_counter() - t0)
    dev_wall = float(np.median(dev_walls))

    t0 = time.perf_counter()
    ref_l, ref_c, ref_p = events_ops.build_events_np(frames)
    scipy_wall = time.perf_counter() - t0

    parity = bool(
        np.array_equal(counts, ref_c) and np.array_equal(labels, ref_l)
    )
    if parity:
        for f in range(n_frames):
            k = int(counts[f])
            if not np.allclose(props[f, :k], ref_p[f, :k],
                               rtol=1e-4, atol=1e-4):
                parity = False
                break

    res = {
        "ws_e2e_events_frames": int(n_frames),
        "ws_e2e_events_frame_shape": list(frame_shape),
        "ws_e2e_events_clusters": int(counts.sum()),
        "ws_e2e_events_compiles": int(compiles),
        "ws_e2e_events_frames_per_s": round(n_frames / dev_wall, 1),
        "ws_e2e_events_scipy_frames_per_s": round(
            n_frames / scipy_wall, 1
        ),
        "ws_e2e_events_speedup": round(scipy_wall / dev_wall, 1),
        "ws_e2e_events_parity": parity,
    }

    # -- serve soak leg ----------------------------------------------------
    from cluster_tools_tpu.serve import (
        QuotaRejected, ServeClient, ServeDaemon,
    )
    from cluster_tools_tpu.utils import file_reader

    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "soak.n5")
        file_reader(path).create_dataset(
            "frames", data=frames[:4, :16, :16].copy(),
            chunks=(2, 16, 16),
        )
        gconf = {"block_shape": [2, 16, 16], "target": "tpu",
                 "device_batch_size": 2, "devices": [0],
                 "pipeline_depth": 2}
        daemon = ServeDaemon(
            os.path.join(td, "state"),
            config={"tenant_quota": 2, "max_queue_depth": 4},
        )
        daemon.start()
        try:
            client = ServeClient(state_dir=os.path.join(td, "state"))

            def submit(i):
                return client.event_batch(
                    input_path=path, input_key="frames",
                    output_path=path, output_key=f"ev_{i}",
                    tmp_folder=os.path.join(td, f"tmp_{i}"),
                    config_dir=os.path.join(td, f"configs_{i}"),
                    configs={"global": dict(gconf)},
                )

            # warm-up job: compiles + pool threads + store handles, so
            # the baseline below is steady state, not cold start
            client.wait(submit(0), timeout_s=600)

            def renewers():
                return [t for t in threading.enumerate()
                        if t.name == "ctt-serve-lease" and t.is_alive()]

            deadline = time.monotonic() + 10
            while renewers() and time.monotonic() < deadline:
                time.sleep(0.05)
            threads_before = threading.active_count()
            fds_before = len(os.listdir("/proc/self/fd"))

            accepted, rejected, metrics_ok = [], 0, True
            t0 = time.perf_counter()
            for i in range(1, soak_submissions + 1):
                try:
                    accepted.append(submit(i))
                except QuotaRejected:
                    rejected += 1
                if i % 200 == 0:  # /metrics must answer mid-burst
                    try:
                        if "# EOF" not in client.metrics_text():
                            metrics_ok = False
                    except Exception:
                        metrics_ok = False
            for jid in accepted:
                state = client.wait(jid, timeout_s=600)
                if not state["result"]["ok"]:
                    metrics_ok = False
            soak_wall = time.perf_counter() - t0

            leases_clean = True
            deadline = time.monotonic() + 15
            while renewers() and time.monotonic() < deadline:
                time.sleep(0.05)
            if renewers():
                leases_clean = False
            thread_parity = fd_parity = False
            deadline = time.monotonic() + 15
            while time.monotonic() < deadline:
                thread_parity = (
                    threading.active_count() <= threads_before
                )
                fd_parity = (
                    len(os.listdir("/proc/self/fd")) <= fds_before
                )
                if thread_parity and fd_parity:
                    break
                time.sleep(0.1)
            if "# EOF" not in client.metrics_text():
                metrics_ok = False
        finally:
            daemon.request_drain()
            if daemon._httpd is not None:
                daemon._httpd.shutdown()
                daemon._httpd.server_close()
            for t in daemon._threads:
                if t.name.startswith("ctt-serve-exec"):
                    t.join(timeout=60)

    res.update({
        "ws_e2e_events_soak_submissions": int(soak_submissions),
        "ws_e2e_events_soak_accepted": len(accepted) + 1,  # + warm-up
        "ws_e2e_events_soak_rejections": int(rejected),
        "ws_e2e_events_soak_wall_s": round(soak_wall, 2),
        "ws_e2e_events_soak_thread_parity": bool(thread_parity),
        "ws_e2e_events_soak_fd_parity": bool(fd_parity),
        "ws_e2e_events_soak_leases_clean": bool(leases_clean),
        "ws_e2e_events_soak_metrics_ok": bool(metrics_ok),
    })
    return res


def run_microbatch_pipeline(n_jobs=1000, n_tenants=4, window_s=0.25,
                            max_jobs=32, frame_n=2, frame_hw=16):
    """ctt-microbatch contract: a mixed-tenant burst of ``n_jobs`` small
    ``event_batch`` jobs through ONE daemon, aggregation window on vs
    window 0 (exact per-job dispatch).

    Both legs pre-fill the durable queue, then start the daemon and
    measure wall-to-last-result — so the comparison is pure executor
    economics (per-job claim scans + builds + dispatches vs amortized
    multi-claims and stacked dispatches), not HTTP submission overhead.
    Gates: ``ws_e2e_microbatch_speedup`` >= 3; outputs byte-identical
    per job (labels + event-table chunk digests); per-tenant ok counts
    sum exactly to the window-0 control; p99 admission-to-result of the
    aggregated leg bounded by the control's p99 + the window (the window
    may delay a job, never by more than itself); zero splits (no member
    failed out of a batch).  ctt-slo (BENCH_r14): the aggregated leg
    also reports ``ws_e2e_mb_e2e_p50_s``/``ws_e2e_mb_e2e_p99_s`` from
    the daemon's own ``serve.latency.e2e`` histograms, cross-checked
    against the client stopwatch within the log2 bucket resolution."""
    import hashlib

    from cluster_tools_tpu.obs import hist as obs_hist
    from cluster_tools_tpu.obs import metrics as obs_metrics
    from cluster_tools_tpu.serve import JobQueue, ServeDaemon
    from cluster_tools_tpu.serve import protocol as serve_protocol
    from cluster_tools_tpu.utils import file_reader

    gconf = {"block_shape": [2, frame_hw, frame_hw], "target": "tpu",
             "device_batch_size": 2, "devices": [0], "pipeline_depth": 2}
    rng = np.random.default_rng(0)
    frames = rng.random((frame_n, frame_hw, frame_hw)).astype("float32")
    frames[frames < 0.9] = 0.0

    def _drain(daemon):
        daemon.request_drain()
        if daemon._httpd is not None:
            daemon._httpd.shutdown()
            daemon._httpd.server_close()
        for t in daemon._threads:
            if t.name.startswith("ctt-serve-exec"):
                t.join(timeout=120)

    def _digest(root):
        h = hashlib.sha256()
        for dirpath, dirnames, filenames in os.walk(root):
            dirnames.sort()
            for name in sorted(filenames):
                p = os.path.join(dirpath, name)
                h.update(os.path.relpath(p, root).encode())
                with open(p, "rb") as f:
                    h.update(f.read())
        return h.hexdigest()

    def _e2e_buckets(snap):
        # ctt-slo: sum the serve.latency.e2e buckets across tenant/
        # priority labels — fixed edges make the aggregation exact
        acc = [0] * (len(obs_hist.EDGES) + 1)
        for s in snap.get("hists") or []:
            if s.get("name") == "serve.latency.e2e":
                for i, c in enumerate(s["buckets"]):
                    acc[i] += int(c)
        return acc

    def _leg(td, path, tag, window):
        state = os.path.join(td, f"state_{tag}")
        q = JobQueue(os.path.join(state, "jobs"))
        job_ids = []
        for i in range(n_jobs):
            rec = serve_protocol.validate_submission({
                "type": "event_batch",
                "input_path": path, "input_key": "frames",
                "output_path": path, "output_key": f"ev_{tag}_{i}",
                "tmp_folder": os.path.join(td, f"tmp_{tag}_{i}"),
                "config_dir": os.path.join(td, f"configs_{tag}_{i}"),
                "threshold": 0.5,
                "configs": {"global": dict(gconf)},
                "tenant": f"t{i % n_tenants}",
            })
            job_ids.append(q.submit(rec))
        before = dict(obs_metrics.snapshot()["counters"])
        # ctt-slo: the daemon runs in-process, so its latency histograms
        # accumulate in THIS process — a before/after bucket delta
        # isolates the leg (reset() would clobber the run's flush file)
        hist_before = _e2e_buckets(obs_hist.snapshot())
        t0 = time.perf_counter()
        daemon = ServeDaemon(state, config={
            "microbatch_window_s": float(window),
            "microbatch_max_jobs": int(max_jobs),
            "max_queue_depth": None, "tenant_quota": None,
        })
        daemon.start()
        try:
            results_dir = os.path.join(state, "jobs")
            deadline = time.monotonic() + 1800
            while time.monotonic() < deadline:
                n_done = sum(
                    1 for n in os.listdir(results_dir)
                    if n.startswith("result.")
                )
                if n_done >= n_jobs:
                    break
                time.sleep(0.05)
            wall = time.perf_counter() - t0
        finally:
            _drain(daemon)
        obs_metrics.flush()
        after = dict(obs_metrics.snapshot()["counters"])
        hist_after = _e2e_buckets(obs_hist.snapshot())
        e2e_buckets = [b - a for a, b in zip(hist_before, hist_after)]
        per_tenant, latencies, all_ok = {}, [], True
        for jid in job_ids:
            st = q.get(jid)
            res = st["result"] or {}
            if not res.get("ok"):
                all_ok = False
                continue
            tenant = res.get("tenant") or "?"
            per_tenant[tenant] = per_tenant.get(tenant, 0) + 1
            latencies.append(
                res["finished_wall"] - st["record"]["submit_wall"]
            )

        def delta(name):
            return after.get(name, 0.0) - before.get(name, 0.0)

        return {
            "wall": wall, "ok": all_ok, "per_tenant": per_tenant,
            "p50": float(np.percentile(latencies, 50)),
            "p99": float(np.percentile(latencies, 99)),
            "e2e_buckets": e2e_buckets,
            "jobs_done": delta("serve.jobs_done"),
            "batches": delta("serve.microbatch_batches"),
            "jobs_batched": delta("serve.microbatch_jobs_batched"),
            "splits": delta("serve.microbatch_splits"),
        }

    import shutil

    # manual mkdtemp: an in-process daemon's heartbeat thread may still
    # stamp beat files while a TemporaryDirectory teardown walks the tree
    td = tempfile.mkdtemp()
    try:
        path = os.path.join(td, "burst.n5")
        file_reader(path).create_dataset(
            "frames", data=frames, chunks=(2, frame_hw, frame_hw)
        )
        # warm-up: pay the event-kernel compiles before EITHER timed leg
        # (leg order must not hand one side the warm cache for free).
        # Each leg dispatches its own frame-stack shapes — the solo leg
        # one job at a time, the aggregated leg full and tail job stacks
        # — and the pow2-padded kernels compile once per shape, an
        # O(log stream) one-time cost by design (ctt-events); warming
        # every shape with the leg's real frame content keeps the A/B a
        # throughput measurement, not a compile-count one.
        from cluster_tools_tpu.ops import events as events_ops

        tail = n_jobs % max_jobs
        for stack in {1, max_jobs, tail} - {0}:
            events_ops.build_events(
                np.tile(frames, (stack, 1, 1)), threshold=0.5
            )
        solo = _leg(td, path, "solo", 0.0)
        mb = _leg(td, path, "mb", window_s)

        # byte-identity per job vs the window-0 control: labels AND the
        # ragged event tables, chunk-for-chunk
        parity = solo["ok"] and mb["ok"]
        if parity:
            for i in range(n_jobs):
                if _digest(
                    os.path.join(path, f"ev_mb_{i}")
                ) != _digest(
                    os.path.join(path, f"ev_solo_{i}")
                ) or _digest(
                    os.path.join(path, f"ev_mb_{i}_events")
                ) != _digest(
                    os.path.join(path, f"ev_solo_{i}_events")
                ):
                    parity = False
                    break
    finally:
        shutil.rmtree(td, ignore_errors=True)

    jobs_per_dispatch = (
        mb["jobs_batched"] / mb["batches"] if mb["batches"] else 0.0
    )

    # ctt-slo (BENCH_r14): the aggregated leg's e2e percentiles as the
    # DAEMON's serve.latency.e2e histograms saw them, cross-checked
    # against the client stopwatch — both span submit->publish, so they
    # must agree within the log2 bucket resolution (adjacent-edge
    # ratio == 2)
    mb_hist_p50 = obs_hist.quantile(mb["e2e_buckets"], 0.50)
    mb_hist_p99 = obs_hist.quantile(mb["e2e_buckets"], 0.99)

    def _hist_close(h, c):
        return (h is not None and h > 0.0 and c > 0.0
                and max(h, c) / min(h, c) <= 2.0000001)

    return {
        "ws_e2e_microbatch_jobs": int(n_jobs),
        "ws_e2e_microbatch_tenants": int(n_tenants),
        "ws_e2e_microbatch_window_s": float(window_s),
        "ws_e2e_microbatch_max_jobs": int(max_jobs),
        "ws_e2e_microbatch_solo_wall_s": round(solo["wall"], 2),
        "ws_e2e_microbatch_wall_s": round(mb["wall"], 2),
        "ws_e2e_microbatch_speedup": round(solo["wall"] / mb["wall"], 2),
        "ws_e2e_microbatch_batches": int(mb["batches"]),
        "ws_e2e_microbatch_jobs_batched": int(mb["jobs_batched"]),
        "ws_e2e_microbatch_jobs_per_dispatch": round(jobs_per_dispatch, 1),
        "ws_e2e_microbatch_splits": int(mb["splits"]),
        "ws_e2e_microbatch_solo_p99_s": round(solo["p99"], 3),
        "ws_e2e_microbatch_p99_s": round(mb["p99"], 3),
        "ws_e2e_microbatch_p99_bounded": bool(
            mb["p99"] <= solo["p99"] + window_s
        ),
        "ws_e2e_mb_e2e_p50_s": round(mb_hist_p50 or 0.0, 4),
        "ws_e2e_mb_e2e_p99_s": round(mb_hist_p99 or 0.0, 4),
        "ws_e2e_mb_e2e_samples": int(sum(mb["e2e_buckets"])),
        "ws_e2e_mb_e2e_hist_consistent": bool(
            _hist_close(mb_hist_p50, mb["p50"])
            and _hist_close(mb_hist_p99, mb["p99"])
        ),
        "ws_e2e_microbatch_tenant_sums_match": bool(
            solo["per_tenant"] == mb["per_tenant"]
            and sum(solo["per_tenant"].values()) == n_jobs
        ),
        "ws_e2e_microbatch_parity": bool(parity),
    }


def run_remote_pipeline(vol_path, shape, block_shape, target):
    """ctt-cloud contract: the WatershedWorkflow run against the local
    stub object server (tests/objstub.py, spawned as a SUBPROCESS so its
    request handling never shares the GIL with compute) vs the POSIX
    store — cold + warm remote walls, the host-IO seconds the pipeline
    hid on the warm remote run, and byte parity (arrays AND chunk-file
    digests; gzip chunks are deterministic, so a remote run must produce
    the exact same files).

    Discipline matches run_ws_pipeline: cold on ``bnd``, warm on the
    DISTINCT z-rolled ``bnd_warm`` copy in fresh scratch — jit caches
    reused, no result-cache replay.  The fault-free timing run is the
    honest latency model (chaos byte-identity rides the test suite and
    the ci_check cloud smoke); the gate is the warm remote wall within
    1.5x of the warm POSIX wall with parity true."""
    import subprocess

    from cluster_tools_tpu.obs import metrics as obs_metrics
    from cluster_tools_tpu.obs import trace as obs_trace
    from cluster_tools_tpu.runtime import build, config as cfg
    from cluster_tools_tpu.utils import file_reader
    from cluster_tools_tpu.workflows import WatershedWorkflow

    here = os.path.dirname(os.path.abspath(__file__))

    def digest(root):
        import hashlib

        h = hashlib.sha256()
        for dirpath, dirnames, filenames in os.walk(root):
            dirnames.sort()
            for name in sorted(filenames):
                p = os.path.join(dirpath, name)
                h.update(os.path.relpath(p, root).encode())
                with open(p, "rb") as f:
                    h.update(f.read())
        return h.hexdigest()

    with tempfile.TemporaryDirectory() as td:
        data_path = _stage_volume(td, vol_path, shape, block_shape, True)
        objroot = os.path.join(td, "objroot")
        served = os.path.join(objroot, "data.n5")
        vol = np.load(vol_path).astype(np.float32)
        f = file_reader(served)
        f.create_dataset("bnd", data=vol, chunks=tuple(block_shape))
        f.create_dataset(
            "bnd_warm", data=np.roll(vol, 7, axis=1),
            chunks=tuple(block_shape),
        )

        port_file = os.path.join(td, "stub.port")
        stub = subprocess.Popen([
            sys.executable, os.path.join(here, "tests", "objstub.py"),
            "--root", objroot, "--port-file", port_file,
        ])
        trace_was_on = obs_trace.enabled()
        if not trace_was_on:
            obs_trace.enable(
                os.path.join(td, "trace"), "remote_bench", export_env=False
            )
        try:
            deadline = time.perf_counter() + 30
            while not os.path.exists(port_file):
                if stub.poll() is not None:
                    raise RuntimeError("objstub died on startup")
                if time.perf_counter() > deadline:
                    raise RuntimeError("objstub never came up")
                time.sleep(0.05)
            url = f"http://127.0.0.1:{open(port_file).read().strip()}"

            def one_run(tag, path, input_key, out_key):
                config_dir = os.path.join(td, f"configs_{tag}")
                cfg.write_global_config(
                    config_dir,
                    {"block_shape": list(block_shape), "target": target,
                     "pipeline_depth": 3},
                )
                cfg.write_config(
                    config_dir, "watershed", dict(WS_TASK_CONFIG)
                )
                wf = WatershedWorkflow(
                    os.path.join(td, f"tmp_{tag}"), config_dir,
                    input_path=path, input_key=input_key,
                    output_path=path, output_key=out_key,
                )
                before = obs_metrics.snapshot()["counters"]
                t0 = time.perf_counter()
                ok = build([wf])
                wall = time.perf_counter() - t0
                after = obs_metrics.snapshot()["counters"]
                if not ok:
                    raise RuntimeError(f"remote bench run failed ({tag})")
                hidden = after.get("executor.stage_hidden_io_s", 0.0) \
                    - before.get("executor.stage_hidden_io_s", 0.0)
                return wall, hidden

            local_cold, _ = one_run("l_cold", data_path, "bnd", "ws_cold")
            local_warm, _ = one_run("l_warm", data_path, "bnd_warm", "ws")
            remote_cold, _ = one_run(
                "r_cold", f"{url}/data.n5", "bnd", "ws_cold"
            )
            remote_warm, hidden = one_run(
                "r_warm", f"{url}/data.n5", "bnd_warm", "ws"
            )

            with file_reader(data_path, "r") as fl, \
                    file_reader(served, "r") as fr:
                parity = bool(np.array_equal(fl["ws"][:], fr["ws"][:]))
            if digest(os.path.join(data_path, "ws")) != digest(
                os.path.join(served, "ws")
            ):
                parity = False
        finally:
            if not trace_was_on:
                obs_trace.disable()
            stub.terminate()
            stub.wait(timeout=30)

    return {
        "ws_e2e_remote_cold_wall_s": round(remote_cold, 2),
        "ws_e2e_remote_warm_wall_s": round(remote_warm, 2),
        "ws_e2e_remote_posix_warm_wall_s": round(local_warm, 2),
        "ws_e2e_remote_vs_posix_warm": round(
            remote_warm / max(local_warm, 1e-9), 2
        ),
        "ws_e2e_remote_read_hidden_s": round(hidden, 3),
        "ws_e2e_remote_parity": parity,
    }


def run_ws_pipeline(vol_path, shape, block_shape, target, warm=False,
                    sharded=False):
    """Wall-clock of the WatershedWorkflow alone — the BASELINE.md north
    star is "≥10x wall-clock vs target='local' on CREMI sample-A
    DT-watershed", i.e. THIS workload (block reads → fused DT-WS program →
    label writes), not the full multicut pipeline whose host-bound merge
    and solve stages dilute the device speedup.  Same cold/warm and
    distinct-volume discipline as ``run_pipeline``.

    ``sharded=True`` runs the collective whole-volume watershed
    (WatershedWorkflow(sharded=True): one upload, one program over the
    mesh, one label write) instead of the block pipeline.  Since round 5
    SHARDED_WS_CONFIG selects the per-slice (2d) collective kernel — the
    SAME algorithm the block pipeline and the cpu-local baseline run
    (apples-to-apples), zero cross-shard collectives; rounds before that
    measured the 3d collective.

    With ``warm=True`` returns ``(cold_wall, warm_wall, stages)`` where
    ``stages`` carries the warm run's three-stage pipeline breakdown
    (``stage_breakdown``; empty when no staged dispatch ran)."""
    from cluster_tools_tpu.runtime import build, config as cfg
    from cluster_tools_tpu.workflows import WatershedWorkflow

    with tempfile.TemporaryDirectory() as td:
        data_path = _stage_volume(td, vol_path, shape, block_shape, warm)

        def one_run(tag, input_key):
            config_dir = os.path.join(td, f"configs{tag}")
            cfg.write_global_config(
                config_dir,
                {"block_shape": list(block_shape), "target": target},
            )
            cfg.write_config(config_dir, "watershed", dict(WS_TASK_CONFIG))
            cfg.write_config(
                config_dir, "sharded_watershed", dict(SHARDED_WS_CONFIG)
            )
            wf = WatershedWorkflow(
                os.path.join(td, f"tmp{tag}"), config_dir,
                input_path=data_path, input_key=input_key,
                output_path=data_path, output_key=f"ws{tag}",
                sharded=sharded,
            )
            t0 = time.perf_counter()
            ok = build([wf])
            wall = time.perf_counter() - t0
            if not ok:
                raise RuntimeError(f"watershed workflow failed ({tag})")
            return wall

        wall = one_run("", "bnd")
        if not warm:
            return wall
        warm_wall = one_run("_warm", "bnd_warm")
        stages = stage_breakdown(os.path.join(td, "tmp_warm"))
    return wall, warm_wall, stages
