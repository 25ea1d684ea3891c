#!/usr/bin/env python3
"""Readings that set a cell's limits, many seeds in one process.

    python3 benchmark/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--jobs 2] [--program] [--control] [--faults]

For each seed: synthesize the cell's volume, then take the first
``--jobs`` ROIs of the window's sequence and judge them with the run's own
decision (``Runner.check``, every job compared):

* ``--program``: the jobs run through ``build()`` as the window runs them
  (the lower readings);
* ``--control``: the reference in the program's place, in bfloat16 (the
  upper readings; it has to come out not correct);
* ``--faults``: each fault of a check's ``FAULTS`` planted in the
  reference (float64) put in the program's place, judged by that check
  alone (the upper readings of the numbers the control cannot reach).

One JSON line per seed.  The benchmark's own runs never run the control.
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402
from benchmark.harness import cell as cell_mod  # noqa: E402


def reading(verdict):
    correct, numbers = verdict
    return {"correct": correct,
            **{k: v["value"] for k, v in numbers.items()}}


# (check, job, precision) -> the reference in the program's place, for
# the seed at hand
MADE = {}


def in_place(precision, fault=None):
    """A producer for ``Runner.check``: the reference at ``precision`` in
    the program's place, with ``fault`` planted where a check has it.
    Each job's reference is computed once per precision."""

    def produce(mod, ctx, job, entry):
        key = (entry["check"], job["index"], precision)
        if key not in MADE:
            MADE[key] = mod.control(ctx, job, entry, precision)
        plant = getattr(mod, "FAULTS", {}).get(fault)
        return plant(MADE[key]) if plant else MADE[key]

    return produce


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--jobs", type=int, default=2)
    parser.add_argument("--program", action="store_true")
    parser.add_argument("--control", action="store_true")
    parser.add_argument("--faults", action="store_true")
    args = parser.parse_args(argv)
    cell = cell_mod.load(args.workload, run.BENCH_FILE)
    run.configure_compile_cache()
    device = run.check_device(cell.chips)
    run.apply_jax_config(cell)
    from cluster_tools_tpu import native
    from cluster_tools_tpu.utils.compile_cache import enable_compile_cache

    if not native.available():
        raise SystemExit("the native solvers did not build")
    enable_compile_cache()
    planted = {e["check"]: sorted(getattr(cell_mod.load_module(
        "checks", e["check"]), "FAULTS", {})) for e in cell.traffic["checks"]}
    warm = False
    for seed in (int(s) for s in args.seeds.split(",")):
        workdir = tempfile.mkdtemp(prefix="ctt_calib_")
        MADE.clear()
        try:
            runner = run.Runner(cell, seed, workdir)
            runner.synthesize()
            if args.program and not warm:
                warm = runner.run_job("warmup", runner.warmup)
            records = []
            for index, roi in enumerate(runner.sequence[:args.jobs]):
                t0 = time.monotonic()
                if args.program:
                    ok = runner.run_job(index, roi)
                else:
                    runner.jobs[index] = {"index": index, "begin": roi[0],
                                          "end": roi[1]}
                    ok = True
                records.append({"index": index, "roi": roi, "ok": ok,
                                "t0": t0, "t1": time.monotonic()})
            out = {"workload": cell.name, "seed": seed, "jobs": len(records),
                   "device": device}
            if args.program:
                out["job_s"] = [r["t1"] - r["t0"] for r in records]
                out["program"] = reading(runner.check(records, every=True))
            if args.control:
                out["control"] = reading(runner.check(
                    records, produce=in_place("bfloat16"), every=True))
            if args.faults:
                out["faults"] = {f: reading(runner.check(
                    records, produce=in_place("float64", f), every=True,
                    checks=[check])) for check, fs in planted.items()
                    for f in fs}
            print(json.dumps(out), flush=True)
        finally:
            shutil.rmtree(workdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
