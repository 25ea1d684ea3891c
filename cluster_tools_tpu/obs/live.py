"""ctt-watch live reader: tail a run's telemetry while it is in flight.

`obs.export` is the post-mortem path — it re-parses every shard from byte
0 and *rejects* malformed input, which is right for CI and wrong for a
run that is still being written.  This module is the in-flight path, one
incremental pass in the streaming-analysis sense:

  * **Per-file offset cursors.**  Every ``spans.p*.jsonl`` shard keeps a
    byte offset; each ``poll()`` reads only the appended suffix.  A torn
    trailing line (a writer mid-``write``) is simply *not consumed* — the
    cursor stays at the line start until the newline lands.  A complete
    line that still fails to parse is counted (``malformed_lines``) and
    skipped: the watcher must outlive a corrupt record, the post-mortem
    exporter is the strict one.
  * **Heartbeats** (``hb.p*.json``, obs.heartbeat) are single small JSON
    objects atomically replaced per beat — re-read whole each poll.
  * **Derived state**: per-task block progress (done/total), block
    throughput and ETA, per-block duration map (the z-slab heatmap),
    straggler flags (in-flight block older than ``k``·median completed
    duration), and suspected-dead workers (heartbeat older than
    ``stale_intervals``·its own promised cadence — catches a hung or
    killed worker *before* the deadline watchdog or scheduler limit).
  * **OpenMetrics export** (:func:`render_openmetrics`): counters/gauges
    plus heartbeat-derived worker/task gauges in Prometheus text
    exposition format, so a scrape job can watch a cluster run.

Ageing across processes uses wall-clock deltas (the same cross-process
contract as the shard-header anchors: good to host clock skew); in-flight
block age combines the writer's own monotonic delta with the wall time
since the beat, so a reader clock jump cannot un-flag a straggler.
"""

from __future__ import annotations

import glob
import json
import os
import re
import time
from typing import Any, Dict, List, Optional, Tuple

from . import hist as hist_mod
from .heartbeat import FILE_PREFIX as HB_PREFIX
from .metrics import METRICS_FILE_PREFIX
from ..runtime.queue import STALE_INTERVALS, STRAGGLER_K

__all__ = [
    "LiveRun", "resolve_live_dir", "format_watch", "format_heatmap",
    "render_openmetrics",
]

SHARD_GLOB = "spans.p*.jsonl"

# span names that represent block *execution* (the things the heatmap and
# progress counters aggregate; ``block:<task>`` counts as ``block``).
# host_io stage spans are excluded: they cover the same blocks again and
# would double-count.
_BLOCK_SPAN_NAMES = {"block", "block_fallback", "block_batch", "stage_compute"}

_now_wall = time.time  # module-level so tests can fake the reader clock


def resolve_live_dir(path: str) -> Optional[str]:
    """Like export.resolve_run_dir but tolerant of a run that has not
    produced anything yet: accepts a dir holding shards OR heartbeats,
    descends one level when exactly one child run exists, and returns
    None (caller keeps polling) instead of raising."""
    def _is_run(d: str) -> bool:
        return bool(
            glob.glob(os.path.join(d, SHARD_GLOB))
            or glob.glob(os.path.join(d, f"{HB_PREFIX}*.json"))
            or glob.glob(os.path.join(d, f"{METRICS_FILE_PREFIX}*.json"))
        )

    if not os.path.isdir(path):
        return None
    if _is_run(path):
        return path
    runs = sorted(d for d in os.listdir(path)
                  if _is_run(os.path.join(path, d)))
    if len(runs) == 1:
        return os.path.join(path, runs[0])
    return None


class LiveRun:
    """Incremental reader over one run directory.  Construct once, call
    :meth:`poll` repeatedly; state accumulates across polls."""

    def __init__(
        self,
        run_dir: str,
        # defaults are THE shared clock-contract constants (CTT204): the
        # live view must age leases/beats exactly like the scheduler does
        straggler_k: float = STRAGGLER_K,
        stale_intervals: float = STALE_INTERVALS,
    ):
        self.run_dir = run_dir
        self.straggler_k = float(straggler_k)
        self.stale_intervals = float(stale_intervals)
        self.run_id: Optional[str] = None
        self.malformed_lines = 0
        self._offsets: Dict[str, int] = {}
        self._anchors: Dict[str, Tuple[float, float]] = {}
        self._pids: set = set()
        # task -> accumulated state
        self._durations: Dict[str, Dict[int, float]] = {}
        self._failed: Dict[str, set] = {}
        self._complete: Dict[str, bool] = {}
        self._dispatch: Dict[str, Dict[str, Any]] = {}
        self._first_wall: Dict[str, float] = {}
        self._last_wall: Dict[str, float] = {}

    # -- incremental shard tailing ----------------------------------------

    def _ingest_shards(self) -> None:
        for path in sorted(
            glob.glob(os.path.join(self.run_dir, SHARD_GLOB))
        ):
            offset = self._offsets.get(path, 0)
            try:
                size = os.path.getsize(path)
            except OSError:
                continue
            if size <= offset:
                continue
            try:
                with open(path, "rb") as f:
                    f.seek(offset)
                    data = f.read()
            except OSError:
                continue
            consumed = len(data)
            if not data.endswith(b"\n"):
                # torn trailing line: leave the cursor at its start; the
                # writer's newline will complete it by the next poll
                nl = data.rfind(b"\n")
                if nl < 0:
                    continue  # nothing complete yet
                consumed = nl + 1
                data = data[:consumed]
            for raw in data.split(b"\n"):
                if not raw.strip():
                    continue
                self._ingest_line(path, raw)
            self._offsets[path] = offset + consumed

    def _ingest_line(self, path: str, raw: bytes) -> None:
        try:
            rec = json.loads(raw)
            if not isinstance(rec, dict):
                raise ValueError
        except (json.JSONDecodeError, ValueError, UnicodeDecodeError):
            self.malformed_lines += 1
            return
        rtype = rec.get("type")
        if rtype == "header":
            try:
                self._anchors[path] = (float(rec["wall"]), float(rec["mono"]))
            except (KeyError, TypeError, ValueError):
                self.malformed_lines += 1
                return
            if self.run_id is None:
                self.run_id = rec.get("run")
            if "pid" in rec:
                self._pids.add(rec["pid"])
            return
        if rtype != "span":
            self.malformed_lines += 1
            return
        anchor = self._anchors.get(path)
        if anchor is None:
            self.malformed_lines += 1
            return
        try:
            t0, t1 = float(rec["t0"]), float(rec["t1"])
        except (KeyError, TypeError, ValueError):
            self.malformed_lines += 1
            return
        wall0, mono0 = anchor
        self._note_span(rec, wall0 + (t0 - mono0), wall0 + (t1 - mono0))

    def _note_span(self, rec: dict, wall_t0: float, wall_t1: float) -> None:
        kind = rec.get("kind")
        attrs = rec.get("attrs") or {}
        name = rec.get("name")
        if kind == "task" and isinstance(name, str):
            self._complete[name] = True
            return
        task = attrs.get("task")
        if not isinstance(task, str):
            return
        if kind == "dispatch":
            info = self._dispatch.setdefault(task, {})
            if isinstance(attrs.get("blocks"), int):
                # retry dispatches carry only the failed share — keep the
                # largest round as the task total fallback
                info["blocks"] = max(info.get("blocks", 0), attrs["blocks"])
            if isinstance(attrs.get("grid"), list):
                info["grid"] = attrs["grid"]
            return
        if (not isinstance(name, str)
                or name.split(":", 1)[0] not in _BLOCK_SPAN_NAMES):
            return
        if "block" in attrs:
            bids = [attrs["block"]]
        elif isinstance(attrs.get("block_ids"), list):
            bids = attrs["block_ids"]
        else:
            return
        try:
            bids = [int(b) for b in bids]
        except (TypeError, ValueError):
            return
        if "error" in attrs:
            failed = self._failed.setdefault(task, set())
            dmap = self._durations.get(task, {})
            failed.update(b for b in bids if b not in dmap)
            return
        dur = (rec.get("t1", 0.0) - rec.get("t0", 0.0)) / max(len(bids), 1)
        dmap = self._durations.setdefault(task, {})
        failed = self._failed.get(task)
        for b in bids:
            dmap[b] = dur
            if failed:
                failed.discard(b)  # retry healed it
        if task not in self._first_wall or wall_t0 < self._first_wall[task]:
            self._first_wall[task] = wall_t0
        if task not in self._last_wall or wall_t1 > self._last_wall[task]:
            self._last_wall[task] = wall_t1

    # -- heartbeat / metrics re-reads -------------------------------------

    def _read_heartbeats(self) -> List[dict]:
        out = []
        for path in sorted(
            glob.glob(os.path.join(self.run_dir, f"{HB_PREFIX}*.json"))
        ):
            try:
                with open(path) as f:
                    hb = json.load(f)
            except (json.JSONDecodeError, OSError):
                continue  # replaced mid-read; next poll sees it whole
            if isinstance(hb, dict) and "pid" in hb:
                out.append(hb)
                self._pids.add(hb["pid"])
        return out

    def _read_metrics(self) -> Tuple[Dict[str, float], Dict[str, Any]]:
        counters: Dict[str, float] = {}
        gauges: Dict[str, Any] = {}
        for path in sorted(glob.glob(
            os.path.join(self.run_dir, f"{METRICS_FILE_PREFIX}*.json")
        )):
            try:
                with open(path) as f:
                    snap = json.load(f)
            except (json.JSONDecodeError, OSError):
                continue
            for k, v in (snap.get("counters") or {}).items():
                try:
                    counters[k] = counters.get(k, 0.0) + float(v)
                except (TypeError, ValueError):
                    continue
            gauges.update(snap.get("gauges") or {})
        return counters, gauges

    def _read_hists(self) -> Dict[str, Any]:
        """Exact merge of every ``hist.p*.json`` snapshot (ctt-slo):
        fixed bucket edges make the cross-process merge bucket-wise
        addition, so the live view's percentiles equal a single merged
        process's.  Torn snapshots are skipped (atomic-replace writers;
        the next poll sees them whole)."""
        return hist_mod.load_run_hists(self.run_dir)

    # -- derived state ------------------------------------------------------

    @staticmethod
    def _median(values: List[float]) -> Optional[float]:
        if not values:
            return None
        vals = sorted(values)
        n = len(vals)
        mid = n // 2
        return vals[mid] if n % 2 else 0.5 * (vals[mid - 1] + vals[mid])

    def _worker_rows(self, hbs: List[dict], now: float) -> List[dict]:
        rows = []
        for hb in hbs:
            interval = float(hb.get("interval_s") or 5.0)
            age = max(0.0, now - float(hb.get("wall") or now))
            exiting = bool(hb.get("exiting"))
            rows.append({
                "pid": hb.get("pid"),
                "host": hb.get("host"),
                "role": hb.get("role", "worker"),
                "job_id": hb.get("job_id"),
                "process_id": hb.get("process_id"),
                "task": hb.get("task"),
                "age_s": age,
                "interval_s": interval,
                "exiting": exiting,
                "stale": (not exiting
                          and age > self.stale_intervals * interval),
                "blocks_total": int(hb.get("blocks_total") or 0),
                "blocks_done": int(hb.get("blocks_done") or 0),
                "blocks_failed": int(hb.get("blocks_failed") or 0),
                "blocks_retried": int(hb.get("blocks_retried") or 0),
                "device_mem_peak_bytes": hb.get("device_mem_peak_bytes"),
                "queue_depth": hb.get("queue_depth"),
                "draining": bool(hb.get("draining")),
                "current_blocks": hb.get("current_blocks") or [],
                "mono": float(hb.get("mono") or 0.0),
                "grid": hb.get("grid"),
            })
        return rows

    def _stragglers(self, workers: List[dict], now: float) -> List[dict]:
        out = []
        for w in workers:
            if w["exiting"] or not w["task"]:
                continue
            med = self._median(
                list(self._durations.get(w["task"], {}).values())
            )
            if not med or med <= 0:
                continue
            for cb in w["current_blocks"]:
                try:
                    start_mono = float(cb["start_mono"])
                    bid = int(cb["id"])
                except (KeyError, TypeError, ValueError):
                    continue
                # age on the writer's own clock up to the beat, plus wall
                # time elapsed since the beat landed
                in_flight = (w["mono"] - start_mono) + w["age_s"]
                if in_flight > self.straggler_k * med:
                    out.append({
                        "task": w["task"], "block": bid, "pid": w["pid"],
                        "in_flight_s": in_flight, "median_s": med,
                    })
        return out

    def _task_rows(self, workers: List[dict]) -> Dict[str, dict]:
        names = (
            set(self._durations) | set(self._complete)
            | set(self._dispatch) | set(self._failed)
            | {w["task"] for w in workers if w["task"]}
        )
        # totals: prefer driver heartbeats (each multi-host driver reports
        # its own shard; workers report sub-shares of one driver's
        # dispatch and would double-count on top of it)
        totals: Dict[str, int] = {}
        for role in ("driver", "worker"):
            for w in workers:
                if w["role"] == role and w["task"] and w["blocks_total"]:
                    totals.setdefault(w["task"], 0)
                    totals[w["task"]] += w["blocks_total"]
            if totals:
                break
        rows: Dict[str, dict] = {}
        for name in sorted(names):
            durs = self._durations.get(name, {})
            done = len(durs)
            total = totals.get(name)
            if total is None:
                total = self._dispatch.get(name, {}).get("blocks")
            if total is not None and total < done:
                total = done  # retries can shrink a dispatch's share
            first = self._first_wall.get(name)
            last = self._last_wall.get(name)
            throughput = None
            eta = None
            if done and first is not None and last is not None and last > first:
                throughput = done / (last - first)
                if total is not None and throughput > 0:
                    eta = max(0, total - done) / throughput
            rows[name] = {
                "blocks_done": done,
                "blocks_total": total,
                "blocks_failed": len(self._failed.get(name, ())),
                "complete": bool(self._complete.get(name)),
                "median_block_s": self._median(list(durs.values())),
                "throughput_bps": throughput,
                "eta_s": eta,
            }
        return rows

    def poll(self) -> Dict[str, Any]:
        """One incremental pass: ingest appended shard lines, re-read
        heartbeats + metrics, return the full derived snapshot."""
        self._ingest_shards()
        now = _now_wall()
        hbs = self._read_heartbeats()
        counters, gauges = self._read_metrics()
        hists = self._read_hists()
        workers = self._worker_rows(hbs, now)
        tasks = self._task_rows(workers)
        stragglers = self._stragglers(workers, now)
        for name, row in tasks.items():
            row["stragglers"] = [s for s in stragglers if s["task"] == name]
        stale = [w for w in workers if w["stale"]]
        progress = (
            any(r["blocks_done"] > 0 for r in tasks.values())
            or any(r["complete"] for r in tasks.values())
        )
        return {
            "run_id": self.run_id,
            "dir": self.run_dir,
            "now_wall": now,
            "progress": progress,
            "malformed_lines": self.malformed_lines,
            "n_processes": len(self._pids),
            "tasks": tasks,
            "workers": workers,
            "stragglers": stragglers,
            "n_stale": len(stale),
            "stale_workers": [
                {"pid": w["pid"], "job_id": w["job_id"], "task": w["task"],
                 "age_s": w["age_s"], "interval_s": w["interval_s"]}
                for w in stale
            ],
            "counters": counters,
            "gauges": gauges,
            # present only when a histogram snapshot exists, so poll
            # snapshots (and the --json watch stream) of runs without
            # latency series stay byte-identical to the pre-slo output
            **({"hists": hists} if hists.get("hists") else {}),
        }

    def task_median_s(self, task: str) -> Optional[float]:
        """Median completed-block duration for one task, from the spans
        ingested so far (incremental — call freely).  The lease-aware
        straggler baseline ``runtime/queue.py`` rides: the work queue's
        duplication threshold uses THIS median instead of recomputing its
        own from item result records, so duplication can fire before the
        queue's first result lands and both detectors agree on what
        'slow' means."""
        self._ingest_shards()
        return self._median(list(self._durations.get(task, {}).values()))

    # -- heatmap ------------------------------------------------------------

    def heatmap_grid(self, task: str) -> Optional[List[int]]:
        """Blocking grid shape for ``task``: dispatch-span attrs first
        (exact), else the latest heartbeat that carried one."""
        grid = self._dispatch.get(task, {}).get("grid")
        if grid:
            return [int(g) for g in grid]
        for hb in self._read_heartbeats():
            if hb.get("task") == task and hb.get("grid"):
                return [int(g) for g in hb["grid"]]
        return None

    def heatmap(self, task: Optional[str] = None) -> Optional[dict]:
        """Per-block duration map for one task (default: the task with the
        most completed blocks).  Returns ``{"task", "grid", "durations"}``
        or None when nothing has finished yet."""
        if task is None:
            if not self._durations:
                return None
            task = max(self._durations, key=lambda t: len(self._durations[t]))
        durs = self._durations.get(task)
        if not durs:
            return None
        return {
            "task": task,
            "grid": self.heatmap_grid(task),
            "durations": dict(durs),
        }


# ---------------------------------------------------------------------------
# rendering


_HEAT_LEVELS = " .:-=+*#%@"  # cold .. hot, 10 levels


def format_heatmap(hm: dict) -> str:
    """Z-slab text heatmap: one character grid per slab along axis 0,
    duration mapped onto 10 shade levels between the observed min and max
    (``_`` = block not finished).  Deterministic for fixed input."""
    task = hm["task"]
    durs: Dict[int, float] = {int(k): float(v)
                              for k, v in hm["durations"].items()}
    lo, hi = min(durs.values()), max(durs.values())

    def shade(bid: int) -> str:
        d = durs.get(bid)
        if d is None:
            return "_"
        if hi <= lo:
            return _HEAT_LEVELS[-1]
        idx = int((d - lo) / (hi - lo) * (len(_HEAT_LEVELS) - 1) + 0.5)
        return _HEAT_LEVELS[idx]

    grid = hm.get("grid")
    lines = [
        f"task {task}  block-duration heatmap  "
        f"({len(durs)} blocks, {lo:.3f}s..{hi:.3f}s, "
        f"'{_HEAT_LEVELS[0]}'=fastest '@'=slowest '_'=pending)"
    ]
    if not grid:
        # no geometry: a flat strip in block-id order, 64 per row
        ids = range(0, max(durs) + 1)
        row: List[str] = []
        for bid in ids:
            row.append(shade(bid))
            if len(row) == 64:
                lines.append("".join(row))
                row = []
        if row:
            lines.append("".join(row))
        return "\n".join(lines)
    if len(grid) == 1:
        grid = [1, 1] + grid
    elif len(grid) == 2:
        grid = [1] + grid
    gz, rest = grid[0], grid[1:]
    per_slab = 1
    for g in rest:
        per_slab *= g
    gy, gx = rest[0], per_slab // max(rest[0], 1)
    for z in range(gz):
        lines.append(f"z-slab {z}:")
        base = z * per_slab
        for y in range(gy):
            lines.append(
                "  " + "".join(shade(base + y * gx + x) for x in range(gx))
            )
    return "\n".join(lines)


def _fmt_lat_s(seconds: float) -> str:
    return (f"{seconds * 1e3:.1f}ms" if seconds < 1.0
            else f"{seconds:.2f}s")


def _format_lat_line(snap: Dict[str, Any]) -> Optional[str]:
    """The ``lat:`` watch line (ctt-slo): e2e p50/p99 per priority class
    from the merged histogram snapshot, tenants aggregated bucket-wise
    (exact).  None when no e2e series exists."""
    series = (snap.get("hists") or {}).get("hists") or []
    by_prio: Dict[str, List[int]] = {}
    for s in series:
        if s.get("name") != "serve.latency.e2e":
            continue
        prio = str((s.get("labels") or {}).get("priority", "?"))
        acc = by_prio.setdefault(prio, [0] * len(s["buckets"]))
        for i, c in enumerate(s["buckets"]):
            acc[i] += int(c)

    def _prio_key(p: str):
        try:
            return (0, -int(p))  # numeric classes, highest first
        except ValueError:
            return (1, 0)

    parts = []
    for prio in sorted(by_prio, key=_prio_key):
        p50 = hist_mod.quantile(by_prio[prio], 0.5)
        p99 = hist_mod.quantile(by_prio[prio], 0.99)
        if p50 is None or p99 is None:
            continue
        parts.append(
            f"prio {prio} p50 {_fmt_lat_s(p50)} p99 {_fmt_lat_s(p99)}"
        )
    return "  lat: e2e " + ", ".join(parts) if parts else None


def format_watch(snap: Dict[str, Any]) -> str:
    """Human watch report for one poll."""
    workers = snap["workers"]
    n_live = sum(1 for w in workers if not w["stale"] and not w["exiting"])
    n_exited = sum(1 for w in workers if w["exiting"])
    header = (
        f"run {snap['run_id'] or '?'}  "
        f"workers: {len(workers)} ({n_live} live, {n_exited} exited, "
        f"{snap['n_stale']} stale)  processes seen: {snap['n_processes']}"
    )
    lines = [header]
    tasks = snap["tasks"]
    if tasks:
        width = max(len(n) for n in tasks) if tasks else 4
        width = max(width, 4)
        lines.append(
            "  ".join([
                "task".ljust(width), "done/total".rjust(12),
                "%".rjust(6), "blk/s".rjust(8), "eta_s".rjust(8),
                "median_s".rjust(9), "flags",
            ])
        )
        for name in sorted(tasks):
            row = tasks[name]
            total = row["blocks_total"]
            done = row["blocks_done"]
            frac = f"{100.0 * done / total:.1f}" if total else "-"
            tput = (f"{row['throughput_bps']:.2f}"
                    if row["throughput_bps"] else "-")
            eta = f"{row['eta_s']:.1f}" if row["eta_s"] is not None else "-"
            med = (f"{row['median_block_s']:.3f}"
                   if row["median_block_s"] is not None else "-")
            flags = []
            if row["complete"]:
                flags.append("complete")
            if row["blocks_failed"]:
                flags.append(f"{row['blocks_failed']} failed")
            if row["stragglers"]:
                flags.append(f"{len(row['stragglers'])} straggler(s)")
            lines.append("  ".join([
                name.ljust(width),
                f"{done}/{total if total is not None else '?'}".rjust(12),
                frac.rjust(6), tput.rjust(8), eta.rjust(8), med.rjust(9),
                ",".join(flags),
            ]).rstrip())
    for s in snap["stragglers"]:
        lines.append(
            f"  straggler: task {s['task']} block {s['block']} in flight "
            f"{s['in_flight_s']:.1f}s (median {s['median_s']:.3f}s) "
            f"on pid {s['pid']}"
        )
    counters = snap.get("counters", {})
    if any(k.startswith("sched.") for k in counters):
        # ctt-steal: one line of scheduler health — how much work remains
        # unclaimed and how the leases have moved
        depth = snap.get("gauges", {}).get("sched.queue_depth")
        parts = [
            f"queue depth {int(depth)}" if isinstance(depth, (int, float))
            else None,
            f"claimed {int(counters.get('sched.leases_claimed', 0))}",
            f"expired {int(counters.get('sched.leases_expired', 0))}",
            f"requeued {int(counters.get('sched.leases_requeued', 0))}",
            f"stolen {int(counters.get('sched.leases_stolen', 0))}",
        ]
        lines.append("  sched: " + ", ".join(p for p in parts if p))
    if any(k.startswith("serve.") for k in counters):
        # ctt-serve: one line of daemon health — queue pressure, admission
        # outcomes, and how warm the compile state is running
        gauges = snap.get("gauges", {})
        parts = []
        for label, key, store in (
            ("queue depth", "serve.queue_depth", gauges),
            ("running", "serve.running_jobs", gauges),
            ("submitted", "serve.submissions", counters),
            ("done", "serve.jobs_done", counters),
            ("failed", "serve.jobs_failed", counters),
            ("rejected", "serve.quota_rejections", counters),
            ("warm", "serve.warm_compile_jobs", counters),
            ("cold", "serve.cold_compile_jobs", counters),
        ):
            val = store.get(key)
            if isinstance(val, (int, float)):
                parts.append(f"{label} {int(val)}")
        lines.append("  serve: " + ", ".join(parts))
    lat = _format_lat_line(snap)
    if lat:
        # ctt-slo: one line of request-latency health — end-to-end
        # p50/p99 per priority class from the merged histograms.  Only
        # rendered when a histogram snapshot exists, so watch output for
        # runs without latency series stays byte-identical
        lines.append(lat)
    if any(k.startswith("serve.microbatch_") for k in counters):
        # ctt-microbatch: one line of aggregation-window economics — how
        # deep the last window filled, how many jobs rode stacked
        # dispatches (jobs/dispatch is the amortization ratio), and how
        # often the window degraded (splits, deadline closes)
        gauges = snap.get("gauges", {})
        batches = counters.get("serve.microbatch_batches", 0)
        jobs = counters.get("serve.microbatch_jobs_batched", 0)
        depth = gauges.get("serve.microbatch_depth")
        parts = [
            (f"depth {int(depth)}"
             if isinstance(depth, (int, float)) else None),
            f"batches {int(batches)}",
            f"jobs batched {int(jobs)}",
            (f"jobs/dispatch {jobs / batches:.1f}" if batches else None),
            f"splits {int(counters.get('serve.microbatch_splits', 0))}",
            "window timeouts "
            f"{int(counters.get('serve.microbatch_window_timeouts', 0))}",
        ]
        lines.append("  batch: " + ", ".join(p for p in parts if p))
    gauges = snap.get("gauges", {})
    if (
        "serve.peers" in gauges
        or "fleet.queue_depth" in gauges
        or any(
            k in counters
            for k in ("serve.jobs_reclaimed", "serve.jobs_quarantined")
        )
    ):
        # ctt-fleet: one line of fleet health — live daemons over the
        # shared state dir, the fleet-wide backlog, and the failure-
        # recovery ledger (fast-path reclaims + quarantined poison jobs)
        parts = []
        for label, key, store in (
            ("peers", "serve.peers", gauges),
            ("queue depth", "fleet.queue_depth", gauges),
            ("reclaimed", "serve.jobs_reclaimed", counters),
            ("quarantined", "serve.jobs_quarantined", counters),
        ):
            val = store.get(key)
            if isinstance(val, (int, float)):
                parts.append(f"{label} {int(val)}")
        lines.append("  fleet: " + ", ".join(parts))
    if (
        "fleet.target_daemons" in gauges
        or any(k.startswith("serve.supervisor_") for k in counters)
    ):
        # ctt-diskless: one line of elastic-fleet actuation — the daemon
        # count the supervisor is converging toward, plus its action
        # ledger (spawns, drains, and beats-only re-adoptions after a
        # supervisor restart)
        parts = []
        for label, key, store in (
            ("target", "fleet.target_daemons", gauges),
            ("spawned", "serve.supervisor_spawns", counters),
            ("drained", "serve.supervisor_drains", counters),
            ("adopted", "serve.supervisor_adoptions", counters),
        ):
            val = store.get(key)
            if isinstance(val, (int, float)):
                parts.append(f"{label} {int(val)}")
        lines.append("  supervisor: " + ", ".join(parts))
    if any(k.startswith("device.") for k in counters):
        # ctt-hbm: one line of device-pipeline health — bytes that crossed
        # to HBM vs uploads the warm buffer cache absorbed, dispatch
        # aggregation, and resident cache pressure.  Sits beside the
        # per-worker device-memory high-water the heartbeats carry
        # (ctt_worker_device_mem_peak_bytes in the prom exposition).
        gauges = snap.get("gauges", {})
        cache_b = gauges.get("device.cache_bytes")
        inflight = gauges.get("device.inflight_uploads")
        parts = [
            "uploaded "
            f"{counters.get('device.upload_bytes', 0) / 1e6:.1f} MB",
            f"skipped {int(counters.get('device.uploads_skipped', 0))}",
            f"dispatches {int(counters.get('device.dispatches', 0))}",
            f"fused blocks {int(counters.get('device.fused_blocks', 0))}",
            f"evictions {int(counters.get('device.cache_evictions', 0))}",
            (f"cache {cache_b / 1e6:.1f} MB"
             if isinstance(cache_b, (int, float)) else None),
            (f"inflight {int(inflight)}"
             if isinstance(inflight, (int, float)) else None),
        ]
        lines.append("  device: " + ", ".join(p for p in parts if p))
    if any(k.startswith("store.remote_") for k in counters):
        # ctt-cloud: one line of remote-IO health — request volume, wire
        # bytes, retries absorbed, and how many requests are in flight
        inflight = snap.get("gauges", {}).get("store.remote_inflight")
        parts = [
            f"reads {int(counters.get('store.remote_reads', 0))}",
            f"writes {int(counters.get('store.remote_writes', 0))}",
            f"retries {int(counters.get('store.remote_retries', 0))}",
            "read "
            f"{counters.get('store.remote_bytes_read', 0) / 1e6:.1f} MB",
            "written "
            f"{counters.get('store.remote_bytes_written', 0) / 1e6:.1f} MB",
            (f"inflight {int(inflight)}"
             if isinstance(inflight, (int, float)) else None),
        ]
        lines.append("  remote: " + ", ".join(p for p in parts if p))
    if any(k.startswith("ingest.") for k in counters):
        # ctt-ingest: streaming-ingest health — the landed-vs-committed
        # frontier, resumes survived, poll volume, carry bytes persisted,
        # and the ingest task's ETA (the incremental driver's note_task
        # row makes the standard rate/ETA machinery apply)
        gauges = snap.get("gauges", {})
        ingested = int(counters.get("ingest.slabs_ingested", 0))
        pending = gauges.get("ingest.slabs_pending")
        pending = int(pending) if isinstance(pending, (int, float)) else 0
        eta = next(
            (row.get("eta_s") for name, row in snap.get("tasks", {}).items()
             if str(name).startswith("ingest")
             and row.get("eta_s") is not None),
            None,
        )
        parts = [
            f"frontier {ingested + pending}",
            f"ingested {ingested}",
            f"pending {pending}",
            f"resumes {int(counters.get('ingest.resumes', 0))}",
            f"polls {int(counters.get('ingest.poll_rounds', 0))}",
            "carry "
            f"{counters.get('ingest.carry_bytes_persisted', 0) / 1e6:.1f} MB",
            (f"eta {eta:.0f}s" if isinstance(eta, (int, float)) else None),
        ]
        lines.append("  ingest: " + ", ".join(p for p in parts if p))
    for w in snap["workers"]:
        if w.get("draining") and not w["exiting"]:
            lines.append(
                f"  DRAINING: pid {w['pid']} ({w['role']}) — finishing "
                "in-flight jobs, submissions refused"
            )
    for w in snap["stale_workers"]:
        where = f"job {w['job_id']}" if w["job_id"] is not None else "driver"
        lines.append(
            f"  STALE: pid {w['pid']} ({where}, task {w['task']}): last "
            f"heartbeat {w['age_s']:.1f}s ago "
            f"(> 3x the {w['interval_s']:.1f}s cadence) — suspected dead"
        )
    if snap["malformed_lines"]:
        lines.append(f"  ({snap['malformed_lines']} malformed line(s) skipped)")
    if not snap["progress"]:
        lines.append("  no progress observed yet")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# OpenMetrics / Prometheus text exposition

_METRIC_NAME_RE = re.compile(r"[^a-zA-Z0-9_]")


def _metric_name(name: str) -> str:
    return "ctt_" + _METRIC_NAME_RE.sub("_", name)


def _escape_label(value: Any) -> str:
    return (
        str(value).replace("\\", r"\\").replace('"', r'\"')
        .replace("\n", r"\n")
    )


def _fmt_value(value: float) -> str:
    return repr(float(value))


def render_openmetrics(snap: Dict[str, Any]) -> str:
    """OpenMetrics 1.0 text exposition of a poll snapshot: every obs
    counter (as ``ctt_<name>_total``) and numeric gauge, plus
    heartbeat-derived per-worker and per-task gauges.  Ends with the
    mandatory ``# EOF``."""
    lines: List[str] = []
    families: set = set()

    def family(name: str, mtype: str, help_text: str) -> str:
        # one TYPE line per family; counters whose raw name already ends
        # in _total keep one suffix only
        if mtype == "counter" and name.endswith("_total"):
            name = name[: -len("_total")]
        while name in families:
            name += "_"
        families.add(name)
        lines.append(f"# TYPE {name} {mtype}")
        if help_text:
            lines.append(f"# HELP {name} {help_text}")
        return name

    merged_counters: Dict[str, float] = {}
    for raw, val in snap.get("counters", {}).items():
        name = _metric_name(raw)
        if name.endswith("_total"):
            name = name[: -len("_total")]
        merged_counters[name] = merged_counters.get(name, 0.0) + float(val)
    for name in sorted(merged_counters):
        fam = family(name, "counter", "")
        lines.append(f"{fam}_total {_fmt_value(merged_counters[name])}")

    for raw in sorted(snap.get("gauges", {})):
        val = snap["gauges"][raw]
        if isinstance(val, bool) or not isinstance(val, (int, float)):
            continue
        fam = family(_metric_name(raw), "gauge", "")
        lines.append(f"{fam} {_fmt_value(val)}")

    # ctt-slo latency histograms (``_bucket``/``_sum``/``_count``);
    # empty when the run recorded none — the exposition is then
    # byte-identical to the pre-slo output
    lines.extend(hist_mod.render_openmetrics(snap.get("hists") or {}))

    workers = snap.get("workers", [])
    if workers:
        specs = [
            ("ctt_worker_up", "gauge",
             "1 while the process heartbeats, 0 when stale or exited",
             lambda w: 0.0 if (w["stale"] or w["exiting"]) else 1.0),
            ("ctt_worker_stale", "gauge",
             "1 when the last heartbeat is older than 3x its cadence",
             lambda w: 1.0 if w["stale"] else 0.0),
            ("ctt_worker_heartbeat_age_seconds", "gauge", "",
             lambda w: w["age_s"]),
            ("ctt_worker_blocks_done", "gauge", "",
             lambda w: float(w["blocks_done"])),
            ("ctt_worker_blocks_total", "gauge", "",
             lambda w: float(w["blocks_total"])),
            ("ctt_worker_blocks_failed", "gauge", "",
             lambda w: float(w["blocks_failed"])),
            ("ctt_worker_in_flight_blocks", "gauge", "",
             lambda w: float(len(w["current_blocks"]))),
            ("ctt_worker_device_mem_peak_bytes", "gauge", "",
             lambda w: (float(w["device_mem_peak_bytes"])
                        if w["device_mem_peak_bytes"] is not None else None)),
            ("ctt_worker_queue_depth", "gauge",
             "unclaimed work-queue items at the worker's last pull (ctt-steal)",
             lambda w: (float(w["queue_depth"])
                        if w.get("queue_depth") is not None else None)),
            # only emitted for processes that ever raised the flag, so
            # non-serve expositions are byte-unchanged
            ("ctt_worker_draining", "gauge",
             "1 while a serve daemon drains (alive, refusing submissions)",
             lambda w: 1.0 if w.get("draining") else None),
        ]
        for name, mtype, help_text, fn in specs:
            rows = []
            for w in workers:
                val = fn(w)
                if val is None:
                    continue
                labels = (
                    f'pid="{_escape_label(w["pid"])}",'
                    f'role="{_escape_label(w["role"])}"'
                )
                if w["job_id"] is not None:
                    labels += f',job="{_escape_label(w["job_id"])}"'
                rows.append(f"{name}{{{labels}}} {_fmt_value(val)}")
            if rows:
                family(name, mtype, help_text)
                lines.extend(rows)

    tasks = snap.get("tasks", {})
    if tasks:
        tspecs = [
            ("ctt_task_blocks_done", "", lambda r: float(r["blocks_done"])),
            ("ctt_task_blocks_total", "",
             lambda r: (float(r["blocks_total"])
                        if r["blocks_total"] is not None else None)),
            ("ctt_task_blocks_failed", "",
             lambda r: float(r["blocks_failed"])),
            ("ctt_task_throughput_blocks_per_second", "",
             lambda r: r["throughput_bps"]),
            ("ctt_task_eta_seconds", "estimated seconds to completion",
             lambda r: r["eta_s"]),
            ("ctt_task_stragglers", "in-flight blocks beyond k x median",
             lambda r: float(len(r["stragglers"]))),
            ("ctt_task_complete", "",
             lambda r: 1.0 if r["complete"] else 0.0),
        ]
        for name, help_text, fn in tspecs:
            rows = []
            for tname in sorted(tasks):
                val = fn(tasks[tname])
                if val is None:
                    continue
                rows.append(
                    f'{name}{{task="{_escape_label(tname)}"}} '
                    f"{_fmt_value(val)}"
                )
            if rows:
                family(name, "gauge", help_text)
                lines.extend(rows)

    fam = family("ctt_watch_malformed_lines", "gauge",
                 "complete-but-unparsable shard lines skipped by the tailer")
    lines.append(f"{fam} {_fmt_value(snap.get('malformed_lines', 0))}")
    lines.append("# EOF")
    return "\n".join(lines) + "\n"
