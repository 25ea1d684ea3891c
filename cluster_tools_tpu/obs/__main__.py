"""CLI: ``python -m cluster_tools_tpu.obs`` — post-mortem and live verbs.

Post-mortem (strict: malformed traces fail loudly):

    python -m cluster_tools_tpu.obs summarize <run_dir> [--json]
    python -m cluster_tools_tpu.obs trace <run_dir> [-o trace.json]
    python -m cluster_tools_tpu.obs diff <base_run> <cand_run> \
        [--threshold 0.2] [--min-s 0.01] [--json]

Live (ctt-watch: incremental, tolerant of in-flight writes):

    python -m cluster_tools_tpu.obs watch <run_dir> [--once]
        [--interval S] [--fail-on-stall] [--straggler-k K] [--json]
    python -m cluster_tools_tpu.obs heatmap <run_dir> [--task NAME]
    python -m cluster_tools_tpu.obs prom <run_dir>

Request-grain (ctt-slo: serve state dirs, POSIX or object-store):

    python -m cluster_tools_tpu.obs journey <state_dir> <job_id> [--json]
    python -m cluster_tools_tpu.obs fleet <state_dir>
    python -m cluster_tools_tpu.obs slo <dir> --objective SPEC [...]
        [--fail-on-violation] [--json]

``<run_dir>`` is either ``<CTT_TRACE_DIR>/<run_id>`` or a trace dir
containing exactly one run.  Exit codes:

  0  success (summarize: at least one task span; diff: no regression;
     watch: block/task progress observed and no stall flagged;
     journey: timeline rendered; fleet: rollup emitted; slo: every
     objective judged against data and none violated)
  1  nothing recorded (summarize: no task spans; watch --once: no
     progress; heatmap: no finished blocks; prom: no run directory;
     journey: no such job; fleet: no daemon snapshots; slo: an
     objective matched no data)
  2  malformed trace (truncated/corrupt shard, mixed runs, bad metrics,
     a bad --objective spec, or foreign histogram bucket edges)
  3  diff found at least one task regressed beyond the threshold
  4  watch --fail-on-stall flagged a stale worker (heartbeat older than
     3x its cadence: suspected dead before the deadline watchdog
     fires); slo --fail-on-violation found an objective violated
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from .export import (
    TraceFormatError,
    diff,
    format_diff,
    format_summary,
    load_run,
    summarize,
    to_chrome_trace,
)

EXIT_OK = 0
EXIT_NO_TASKS = 1
EXIT_MALFORMED = 2
EXIT_REGRESSED = 3
EXIT_STALLED = 4


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m cluster_tools_tpu.obs",
        description="ctt-obs: merge, summarize, export, and diff "
        "structured run traces; ctt-watch: live watch/heatmap/prom",
    )
    sub = parser.add_subparsers(dest="cmd", required=True)

    p_sum = sub.add_parser(
        "summarize", help="per-task host-IO/host-compute/collective breakdown"
    )
    p_sum.add_argument("run")
    p_sum.add_argument("--json", action="store_true",
                       help="machine-readable output")

    p_trace = sub.add_parser(
        "trace", help="export Chrome trace_event JSON (Perfetto-loadable)"
    )
    p_trace.add_argument("run")
    p_trace.add_argument("-o", "--output", default=None,
                         help="output path (default: stdout)")

    p_diff = sub.add_parser(
        "diff", help="compare two runs; nonzero exit on regression"
    )
    p_diff.add_argument("base")
    p_diff.add_argument("candidate")
    p_diff.add_argument("--threshold", type=float, default=0.2,
                        help="fractional wall-clock growth that counts as "
                        "a regression (default 0.2 = 20%%)")
    p_diff.add_argument("--min-s", type=float, default=0.01,
                        help="absolute floor in seconds below which growth "
                        "is jitter, not regression (default 0.01)")
    p_diff.add_argument("--json", action="store_true")

    p_watch = sub.add_parser(
        "watch", help="live progress/ETA/straggler report (ctt-watch)"
    )
    p_watch.add_argument("run")
    p_watch.add_argument("--once", action="store_true",
                         help="one poll + report, then exit (CI mode)")
    p_watch.add_argument("--interval", type=float, default=5.0,
                         help="poll cadence in seconds (default 5)")
    p_watch.add_argument("--fail-on-stall", action="store_true",
                         help="exit 4 as soon as a stale worker is flagged")
    p_watch.add_argument("--straggler-k", type=float, default=4.0,
                         help="flag in-flight blocks older than K x the "
                         "median completed block duration (default 4)")
    p_watch.add_argument("--json", action="store_true",
                         help="one JSON snapshot object per poll")

    p_heat = sub.add_parser(
        "heatmap", help="z-slab text heatmap of per-block durations"
    )
    p_heat.add_argument("run")
    p_heat.add_argument("--task", default=None,
                        help="task identifier (default: most blocks done)")

    p_prom = sub.add_parser(
        "prom", help="OpenMetrics/Prometheus text exposition of the run"
    )
    p_prom.add_argument("run")

    p_journey = sub.add_parser(
        "journey", help="per-job phase timeline from serve state-dir "
        "records (failover-aware, purely post-hoc)"
    )
    p_journey.add_argument("state_dir")
    p_journey.add_argument("job_id")
    p_journey.add_argument("--json", action="store_true")

    p_fleet = sub.add_parser(
        "fleet", help="fleet-wide OpenMetrics rollup of every daemon's "
        "snap.<id>.json (counters summed, histograms exactly merged)"
    )
    p_fleet.add_argument("state_dir")

    p_slo = sub.add_parser(
        "slo", help="gate latency objectives against merged histograms "
        "(exit 0 met / 1 no data / 4 violated with --fail-on-violation)"
    )
    p_slo.add_argument("dir")
    p_slo.add_argument("--objective", action="append", required=True,
                       metavar="PHASE_pNN_s=SECONDS[@label=value,...]",
                       help="e.g. e2e_p99_s=2.0@priority=5 (repeatable)")
    p_slo.add_argument("--fail-on-violation", action="store_true",
                       help="exit 4 when any objective is violated")
    p_slo.add_argument("--json", action="store_true")

    args = parser.parse_args(argv)
    if args.cmd in ("watch", "heatmap", "prom"):
        return _live_main(args)
    if args.cmd in ("journey", "fleet", "slo"):
        return _slo_main(args)
    try:
        if args.cmd == "summarize":
            summary = summarize(load_run(args.run))
            if args.json:
                print(json.dumps(summary, indent=2, sort_keys=True))
            else:
                print(format_summary(summary))
            if summary["n_task_spans"] < 1:
                print("obs: no task spans recorded", file=sys.stderr)
                return EXIT_NO_TASKS
            return EXIT_OK
        if args.cmd == "trace":
            chrome = to_chrome_trace(load_run(args.run))
            payload = json.dumps(chrome)
            if args.output:
                with open(args.output, "w") as f:
                    f.write(payload)
                print(f"wrote {len(chrome['traceEvents'])} events to "
                      f"{args.output}", file=sys.stderr)
            else:
                print(payload)
            return EXIT_OK
        if args.cmd == "diff":
            result = diff(
                load_run(args.base), load_run(args.candidate),
                threshold=args.threshold, min_seconds=args.min_s,
            )
            if args.json:
                print(json.dumps(result, indent=2, sort_keys=True))
            else:
                print(format_diff(result))
            return EXIT_REGRESSED if result["n_regressed"] else EXIT_OK
    except TraceFormatError as e:
        print(f"obs: malformed trace: {e}", file=sys.stderr)
        return EXIT_MALFORMED
    except OSError as e:
        print(f"obs: {e}", file=sys.stderr)
        return EXIT_MALFORMED
    raise AssertionError(f"unhandled command {args.cmd}")


def _slo_main(args) -> int:
    from . import journey as journey_mod
    from . import slo as slo_mod

    try:
        if args.cmd == "journey":
            j = journey_mod.load_journey(args.state_dir, args.job_id)
            if j is None:
                print(f"obs: no job {args.job_id} under {args.state_dir}",
                      file=sys.stderr)
                return EXIT_NO_TASKS
            if args.json:
                print(json.dumps(j, indent=2, sort_keys=True))
            else:
                print(journey_mod.format_journey(j))
            return EXIT_OK
        if args.cmd == "fleet":
            merged = slo_mod.load_fleet(args.state_dir)
            if not merged["daemons"]:
                print(f"obs: no daemon snapshots under {args.state_dir}",
                      file=sys.stderr)
                return EXIT_NO_TASKS
            print(slo_mod.render_fleet(merged), end="")
            return EXIT_OK
        if args.cmd == "slo":
            objectives = [slo_mod.parse_objective(s)
                          for s in args.objective]
            hists = slo_mod.load_hists_any(args.dir)
            rows = slo_mod.evaluate(hists, objectives)
            if args.json:
                print(json.dumps(rows, indent=2, sort_keys=True))
            else:
                print(slo_mod.format_report(rows))
            # contract: violated (4) outranks no-data (1) outranks met (0);
            # without --fail-on-violation a violation only reports
            if args.fail_on_violation and any(
                r["status"] == "violated" for r in rows
            ):
                return EXIT_STALLED
            if any(r["status"] == "no_data" for r in rows):
                return EXIT_NO_TASKS
            return EXIT_OK
    except ValueError as e:
        # bad --objective spec or foreign histogram edges (version skew)
        print(f"obs: {e}", file=sys.stderr)
        return EXIT_MALFORMED
    except OSError as e:
        print(f"obs: {e}", file=sys.stderr)
        return EXIT_MALFORMED
    raise AssertionError(f"unhandled command {args.cmd}")


def _watch_exit_code(snap, fail_on_stall: bool) -> int:
    if fail_on_stall and snap["n_stale"] > 0:
        return EXIT_STALLED
    return EXIT_OK if snap["progress"] else EXIT_NO_TASKS


def _live_main(args) -> int:
    from .live import (
        LiveRun,
        format_heatmap,
        format_watch,
        render_openmetrics,
        resolve_live_dir,
    )

    run_dir = resolve_live_dir(args.run)
    if args.cmd == "prom":
        if run_dir is None:
            print(f"obs: no run telemetry under {args.run}", file=sys.stderr)
            return EXIT_NO_TASKS
        print(render_openmetrics(LiveRun(run_dir).poll()), end="")
        return EXIT_OK

    if args.cmd == "heatmap":
        if run_dir is None:
            print(f"obs: no run telemetry under {args.run}", file=sys.stderr)
            return EXIT_NO_TASKS
        live = LiveRun(run_dir)
        live.poll()
        hm = live.heatmap(task=args.task)
        if hm is None:
            print("obs: no finished blocks to map yet", file=sys.stderr)
            return EXIT_NO_TASKS
        print(format_heatmap(hm))
        return EXIT_OK

    # watch: poll until progress settles (or forever without --once)
    live = None
    while True:
        if run_dir is None:
            run_dir = resolve_live_dir(args.run)
        if run_dir is not None and live is None:
            live = LiveRun(run_dir, straggler_k=args.straggler_k)
        if live is None:
            if args.once:
                print(f"obs: no run telemetry under {args.run}",
                      file=sys.stderr)
                return EXIT_NO_TASKS
            print(f"waiting for telemetry under {args.run} ...",
                  file=sys.stderr)
        else:
            snap = live.poll()
            if args.json:
                print(json.dumps(snap, sort_keys=True))
            else:
                print(format_watch(snap))
            sys.stdout.flush()
            rc = _watch_exit_code(snap, args.fail_on_stall)
            if args.once:
                return rc
            if rc == EXIT_STALLED:
                return rc
            # a finished run: every heartbeat says exiting and >= 1 task
            # completed — stop polling a corpse
            workers = snap["workers"]
            if (
                workers
                and all(w["exiting"] for w in workers)
                and any(r["complete"] for r in snap["tasks"].values())
            ):
                return EXIT_OK
        try:
            time.sleep(max(args.interval, 0.05))  # ctt: noqa[CTT009] poll cadence, not an IO retry — nothing here is retried
        except KeyboardInterrupt:  # pragma: no cover - interactive only
            return EXIT_OK


if __name__ == "__main__":
    sys.exit(main())
