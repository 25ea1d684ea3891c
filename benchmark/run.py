#!/usr/bin/env python3
"""Benchmark of cluster_tools_tpu: one cell, one run.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

A cell (``BENCHMARK.json`` ``workloads``) is a deployment configuration
(``benchmark/configs``) under a traffic mix (``benchmark/traffic``).  The
run checks the device, builds the native solvers, synthesizes the volume
from the seed and writes it as n5, runs one warm-up job, then measures a
closed loop of one client: jobs back to back, each one ``build()`` of the
mix's workflow over the next ROI of a fixed z-major sequence, until the
first job that ends at or after ``--seconds``.  Afterwards it compares a
sample of the jobs' outputs with the plain references of
``benchmark/checks``.  ``--trace 1`` records the program's spans and a
device trace of the window and reports the per-layer metrics instead of
the end-to-end ones.  The last line of stdout is the result as JSON.
Without a TPU, or with another chip count than the cell's, it exits 1 and
prints no result.
"""

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.harness import cell as cell_mod  # noqa: E402
from benchmark.harness import peaks, spans, window, xtrace  # noqa: E402

JOB = "ctt_bench_job"
# BENCHMARK.json to read; None is the one at the checkout's root
BENCH_FILE = None


class SetupError(RuntimeError):
    """The run cannot measure: no chip, no program, a broken set-up."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def configure_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    unless the environment names one; every program is cached."""
    path = os.environ.setdefault(
        "JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    return path


def persistent_cache_off() -> None:
    """Stop reading and writing the persistent compilation cache.  After
    the warm-up, a program that the window compiles at shapes only its data
    sets compiles in every run alike, and not only in a run whose seed an
    earlier run of this checkout had."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()


def apply_jax_config(cell) -> None:
    """The JAX settings the configuration states (its precision)."""
    import jax

    for key, value in cell.config.get("jax_config", {}).items():
        jax.config.update(key, value)


def check_device(chips: int) -> dict:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SetupError(f"no TPU: JAX found {len(devs)} "
                         f"{devs[0].platform} device(s)")
    if len(devs) != chips:
        raise SetupError(f"the cell needs {chips} chip(s), JAX found "
                         f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax

    peaks = [d.memory_stats().get("peak_bytes_in_use", 0)
             for d in jax.local_devices() if d.memory_stats()]
    return int(max(peaks)) if peaks else 0


class Runner:
    """Set-up, jobs and checks of one run of one cell."""

    def __init__(self, cell, seed: int, workdir: str):
        self.cell = cell
        self.seed = seed
        self.workdir = workdir
        self.input_path = os.path.join(workdir, "input.n5")
        self.output_path = os.path.join(workdir, "output.n5")
        self.ctx = cell_mod.Context(cell, output_path=self.output_path)
        self.rois = window.roi_grid(cell.volume_shape, cell.block_shape,
                                    cell.traffic["roi_blocks"])
        # the grid's last ROI warms up; edge blocks are padded to the block
        # shape, so it compiles every program shape the window uses
        self.warmup = self.rois[-1]
        self.sequence = self.rois[:-1]
        self.jobs = {}

    def synthesize(self) -> None:
        from benchmark.harness import n5, volume

        t0 = time.monotonic()
        conf = self.cell.config
        gen = conf["generator"]
        raw = volume.synthesize(conf["volume_shape"], self.seed,
                                sigma=gen["sigma"],
                                boundary_frac=gen["boundary_frac"])
        t1 = time.monotonic()
        n5.write(self.input_path, "raw", raw, conf["block_shape"])
        self.ctx.raw = raw
        log(f"volume {raw.shape} seed {self.seed}: "
            f"{float((raw > 0.5).mean()):.4f} above 0.5; synthesis "
            f"{t1 - t0:.1f} s, n5 write {time.monotonic() - t1:.1f} s")

    def _workflow(self, tag: str, roi):
        import importlib

        from cluster_tools_tpu.runtime import config as cfg

        traffic = self.cell.traffic
        root = os.path.join(self.workdir, "jobs", tag)
        config_dir = os.path.join(root, "configs")
        gconf = {
            "block_shape": list(self.cell.block_shape), "target": "tpu",
            "roi_begin": list(roi[0]), "roi_end": list(roi[1]),
            **self.cell.config.get("global_config", {}),
        }
        cfg.write_global_config(config_dir, gconf)
        for task, conf in traffic.get("task_configs", {}).items():
            cfg.write_config(config_dir, task, conf)
        module, cls = traffic["workflow"].split(":")
        workflow = getattr(importlib.import_module(module), cls)
        fields = {"input_path": self.input_path, "input_key": "raw",
                  "output_path": self.output_path, "job": tag}
        kwargs = {k: v.format(**fields) if isinstance(v, str) else v
                  for k, v in traffic["kwargs"].items()}
        tmp = os.path.join(root, "tmp")
        return workflow(tmp, config_dir, **kwargs), tmp

    def run_job(self, tag, roi) -> bool:
        from cluster_tools_tpu.runtime import build

        wf, tmp = self._workflow(str(tag), roi)
        self.jobs[tag] = {"index": tag, "begin": roi[0], "end": roi[1],
                          "tmp_folder": tmp}
        try:
            return bool(build([wf]))
        except Exception:  # a failed job counts as failed, the run goes on
            log(f"job {tag} failed:\n{traceback.format_exc()}")
            return False

    def check(self, records, produce=None, every=False, checks=None) -> tuple:
        """``(correct, numbers)``: the jobs drawn from the seed (``every``:
        all of them) compared by each check of the traffic mix (``checks``:
        those named) with its reference, each number beside its limit.
        ``produce(mod, ctx, job, entry)`` puts something in the program's
        place (the control, a planted fault); by default what the job wrote
        is read back."""
        import numpy as np

        numbers = {}
        correct = all(r["ok"] for r in records)
        rng = np.random.default_rng(self.seed)
        for entry in self.cell.traffic["checks"]:
            if checks is not None and entry["check"] not in checks:
                continue
            mod = cell_mod.load_module("checks", entry["check"])
            n = min(int(entry.get("sample", len(records))), len(records))
            picked = sorted(rng.choice(len(records), n, replace=False))
            worst = {}
            for i in (range(len(records)) if every else picked):
                job = self.jobs[records[i]["index"]]
                t0 = time.monotonic()
                out = (produce(mod, self.ctx, job, entry) if produce
                       else mod.program(self.ctx, job, entry))
                got = mod.numbers(out, mod.reference(self.ctx, job, entry,
                                                     out))
                log(f"check {entry['check']} job {job['index']}: {got} "
                    f"({time.monotonic() - t0:.1f} s)")
                for k, v in got.items():
                    worst[k] = max(worst.get(k, v), v)
            for k, v in worst.items():
                limit = float(entry["limits"][k])
                numbers[k] = {"value": v, "limit": limit}
                correct = correct and v <= limit
        return correct, numbers


def per_layer(cell, ctx) -> dict:
    """Each per-layer metric of the cell from its reader.  A reader that
    finds nothing to read returns None and the metric is left out; one
    that raises fails the run."""
    out = {}
    for m in cell.per_layer:
        value = cell_mod.load_module("metrics", m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run(args) -> dict:
    cell = cell_mod.load(args.workload, BENCH_FILE)
    configure_compile_cache()
    try:
        import cluster_tools_tpu  # noqa: F401
    except ImportError as e:
        raise SetupError(f"the program is missing: {e}") from e
    device = check_device(cell.chips)
    apply_jax_config(cell)
    from cluster_tools_tpu import native

    if not native.available():
        raise SetupError("the native solvers did not build")
    from benchmark.harness import native as ref_native

    ref_native.load()
    from cluster_tools_tpu.utils.compile_cache import enable_compile_cache

    log(f"device {device}; compile cache {enable_compile_cache()}")
    workdir = tempfile.mkdtemp(prefix="ctt_bench_")
    try:
        return measure(cell, args, device, workdir)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def measure(cell, args, device, workdir) -> dict:
    import jax

    runner = Runner(cell, args.seed, workdir)
    ctx = runner.ctx
    runner.synthesize()
    ctt_dir = os.path.join(workdir, "ctt_trace")
    profile_dir = os.path.join(workdir, "profile")
    if args.trace:
        from cluster_tools_tpu.obs import trace as ctt_trace

        ctt_trace.enable(ctt_dir)
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, duration, **kw: compiles.append(event)
        if "backend_compile" in event else None)
    t0 = time.monotonic()
    if not runner.run_job("warmup", runner.warmup):
        raise SetupError("the warm-up job failed")
    log(f"warm-up job {runner.warmup}: {time.monotonic() - t0:.1f} s")
    persistent_cache_off()

    from cluster_tools_tpu.obs import metrics as ctt_metrics

    counters0 = ctt_metrics.snapshot()["counters"]
    sync_ns = None
    if args.trace:
        jax.profiler.start_trace(profile_dir)
        sync_ns = time.monotonic_ns()
        with jax.profiler.TraceAnnotation(xtrace.SYNC):
            pass
    n_compiles = len(compiles)

    def job(index, roi):
        with jax.profiler.TraceAnnotation(JOB):
            return runner.run_job(index, roi)

    setup_s = time.monotonic() - T_PROCESS
    records = window.run(runner.sequence, args.seconds, job)
    peak = memory_peak_bytes()
    ctx.jobs = [dict(r, **runner.jobs[r["index"]]) for r in records]
    ctx.counters = spans.counter_delta(
        counters0, ctt_metrics.snapshot()["counters"])
    log(f"window: {len(records)} jobs in {window.window_seconds(records):.3f}"
        f" s, {len(compiles) - n_compiles} compiles inside it, peak {peak} B")
    for job_ in ctx.jobs:
        tmp = job_["tmp_folder"]
        walls = " ".join(f"{k}={v:.3f}" for k, v in sorted(
            spans.task_walls(tmp).items()))
        stages = " ".join(f"{k}={v:.3f}" for k, v in sorted(
            spans.stage_walls(tmp).items()))
        log(f"job {job_['index']} roi {job_['roi']}: "
            f"{job_['t1'] - job_['t0']:.3f} s ok={job_['ok']}; "
            f"task walls: {walls}; stage walls: {stages or 'none'}")

    result = {"correct": False, "attempted": len(records),
              "failed": sum(not r["ok"] for r in records)}
    device = dict(device, memory_peak_bytes=peak)
    if args.trace:
        jax.profiler.stop_trace()
        ctt_trace.flush()
        ctx.spans = spans.read_spans(ctt_dir)
        ctx.peaks = peaks.peaks(device["kind"])
        ctx.trace = read_trace(profile_dir, sync_ns, ctx)
        device.update(busy_s=xtrace.busy_ns(ctx.trace) / 1e9,
                      window_s=xtrace.window_ns(ctx.trace) / 1e9)
        result["metrics"] = per_layer(cell, ctx)
        result["device"] = device
        result["breakdown"] = {
            "device_ops": [list(x) for x in xtrace.top_ops(ctx.trace)],
            "idle_gaps": [list(x) for x in xtrace.attribute_gaps(ctx.trace)],
        }
    else:
        result["metrics"] = {
            "mvox_s": {"value": window.mvox_per_s(records), "unit": "Mvox/s"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
        result["device"] = device
    t0 = time.monotonic()
    result["correct"], result["checks"] = runner.check(records)
    log(f"checks: {time.monotonic() - t0:.1f} s")
    for k, v in result["checks"].items():
        print(f"{k} = {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr, flush=True)
    return result


def read_trace(profile_dir, sync_ns, ctx):
    """The window's device trace, with the program's spans inside the
    window put on its clock and the window cut to the jobs' annotations."""
    lo, hi = ctx.jobs[0]["t0"], ctx.jobs[-1]["t1"]
    host = [(s["name"], s["t0"] * 1e9, (s["t1"] - s["t0"]) * 1e9)
            for s in spans.inside(ctx.spans, lo, hi)]
    tr = xtrace.load(profile_dir, host_offset_ns=sync_ns, extra_host=host)
    tr.window = xtrace.host_window(tr, JOB) or tr.window
    names = sorted({n.split("(")[0] for evs in tr.modules.values()
                    for n, _, _ in evs})
    log(f"trace: devices {tr.devices}, "
        f"{sum(map(len, tr.modules.values()))} module and "
        f"{sum(map(len, tr.ops.values()))} op events; programs {names}")
    return tr


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    try:
        result = run(args)
    except (SetupError, KeyError, FileNotFoundError) as e:
        print(f"[bench] cannot run: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
