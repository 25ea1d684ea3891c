"""ctt-obs export: merge per-process shards, summarize, Chrome trace, diff.

A run directory (``<CTT_TRACE_DIR>/<run_id>/``) holds one
``spans.p<pid>.t<tid>.jsonl`` shard per writer thread per process plus
one ``metrics.p<pid>.json`` snapshot per process.  This module is the
read side:

  * `load_run` — merge every shard into one event list.  Durations stay
    on each process's monotonic clock (exact); *placement* on a shared
    wall-clock axis uses the per-shard (wall, mono) anchor pair from the
    shard header — good to cross-process clock skew, which is fine for
    eyeballing concurrency in Perfetto and irrelevant for the summaries.
  * `summarize` — per-task breakdown into distinct buckets: ``host_io``
    (chunk reads/writes), ``host_compute`` (blocking device dispatches,
    timed on the host clock — device time itself is in a profiler trace),
    ``collective`` (mesh programs), ``host`` (other host work).  Bucket
    sums use *self time* (span duration minus its children's durations),
    so a batch span that encloses a host-IO read is never double-counted,
    and ``host_io + host_compute + host > dispatch wall`` is exactly the
    pipeline overlap (host IO hidden behind device execution).
  * `to_chrome_trace` — Chrome ``trace_event`` JSON (load it in Perfetto
    or ``chrome://tracing``).
  * `diff` — compare two runs task by task and flag wall-clock
    regressions beyond a threshold: the machine half of the BENCH
    trajectory (two bench runs with tracing on are machine-comparable).

Malformed shards raise :class:`TraceFormatError` — the CLI maps it to a
nonzero exit so CI catches truncated/corrupt traces instead of
summarizing garbage.
"""

from __future__ import annotations

import glob
import json
import os
from typing import Any, Dict, List, Optional

from . import hist as hist_mod
from .metrics import METRICS_FILE_PREFIX

__all__ = [
    "TraceFormatError", "resolve_run_dir", "load_run",
    "summarize", "format_summary", "to_chrome_trace",
    "diff", "format_diff",
]

SHARD_GLOB = "spans.p*.jsonl"

# span kinds → summary buckets; structural/bridge kinds are excluded from
# the bucket sums (see summarize)
_BUCKETS = {"host_io": "host_io_s", "host_compute": "host_compute_s",
            "collective": "collective_s"}
_EXCLUDED_KINDS = {"task", "dispatch", "run", "timing"}


class TraceFormatError(ValueError):
    """A shard or metrics file is not valid ctt-obs output."""


def resolve_run_dir(path: str) -> str:
    """Accept either a run directory or a trace dir containing runs.
    A trace dir with exactly one run resolves to it; several runs is an
    error naming them (the caller must pick)."""
    if glob.glob(os.path.join(path, SHARD_GLOB)):
        return path
    if not os.path.isdir(path):
        raise TraceFormatError(f"no such trace directory: {path}")
    runs = sorted(
        d for d in os.listdir(path)
        if glob.glob(os.path.join(path, d, SHARD_GLOB))
    )
    if len(runs) == 1:
        return os.path.join(path, runs[0])
    if not runs:
        raise TraceFormatError(f"no trace shards under {path}")
    raise TraceFormatError(
        f"{len(runs)} runs under {path} — pass one of: "
        + ", ".join(runs[:5])
    )


_SPAN_KEYS = ("id", "name", "kind", "t0", "t1", "pid", "tid")


def _load_shard(path: str, spans: List[dict], headers: List[dict]) -> None:
    anchor = None  # (wall, mono) of this shard
    with open(path) as f:
        for lineno, line in enumerate(f, start=1):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError as e:
                raise TraceFormatError(
                    f"{path}:{lineno}: not JSON ({e.msg})"
                ) from None
            if not isinstance(rec, dict):
                raise TraceFormatError(f"{path}:{lineno}: not an object")
            rtype = rec.get("type")
            if rtype == "header":
                anchor = (float(rec["wall"]), float(rec["mono"]))
                headers.append(rec)
            elif rtype == "span":
                if anchor is None:
                    raise TraceFormatError(
                        f"{path}:{lineno}: span before shard header"
                    )
                missing = [k for k in _SPAN_KEYS if k not in rec]
                if missing:
                    raise TraceFormatError(
                        f"{path}:{lineno}: span missing {missing}"
                    )
                wall0, mono0 = anchor
                rec = dict(rec)
                rec["wall_t0"] = wall0 + (float(rec["t0"]) - mono0)
                rec["wall_t1"] = wall0 + (float(rec["t1"]) - mono0)
                spans.append(rec)
            else:
                raise TraceFormatError(
                    f"{path}:{lineno}: unknown record type {rtype!r}"
                )


def _load_metrics(run_dir: str) -> Dict[str, Any]:
    counters: Dict[str, float] = {}
    gauges: Dict[str, Any] = {}
    for path in sorted(glob.glob(
        os.path.join(run_dir, f"{METRICS_FILE_PREFIX}*.json")
    )):
        try:
            with open(path) as f:
                snap = json.load(f)
        except (json.JSONDecodeError, OSError) as e:
            raise TraceFormatError(f"{path}: bad metrics file ({e})") from None
        for k, v in snap.get("counters", {}).items():
            counters[k] = counters.get(k, 0.0) + float(v)
        gauges.update(snap.get("gauges", {}))
    return {"counters": counters, "gauges": gauges}


def load_run(path: str) -> Dict[str, Any]:
    """Merge every shard of a run.  Returns ``{"run_id", "dir", "spans",
    "headers", "counters", "gauges"}`` with spans carrying both monotonic
    (``t0``/``t1``, duration-exact) and wall (``wall_t0``/``wall_t1``,
    placement) endpoints."""
    run_dir = resolve_run_dir(path)
    spans: List[dict] = []
    headers: List[dict] = []
    for shard in sorted(glob.glob(os.path.join(run_dir, SHARD_GLOB))):
        _load_shard(shard, spans, headers)
    if not headers:
        raise TraceFormatError(f"no shard headers under {run_dir}")
    run_ids = sorted({h["run"] for h in headers})
    if len(run_ids) > 1:
        raise TraceFormatError(
            f"shards from different runs in {run_dir}: {run_ids}"
        )
    metrics = _load_metrics(run_dir)
    spans.sort(key=lambda s: s["wall_t0"])
    return {
        "run_id": run_ids[0],
        "dir": run_dir,
        "spans": spans,
        "headers": headers,
        "counters": metrics["counters"],
        "gauges": metrics["gauges"],
        # ctt-slo: exact cross-process merge of hist.p*.json (the fixed
        # bucket edges make it bucket-wise addition)
        "hists": hist_mod.load_run_hists(run_dir),
    }


# ---------------------------------------------------------------------------
# summarize


def _task_of(span: dict, by_id: Dict[int, dict]) -> Optional[str]:
    """Nearest explicit ``task=`` attribute or enclosing task span."""
    seen = 0
    node: Optional[dict] = span
    while node is not None and seen < 64:  # cycle guard
        attrs = node.get("attrs") or {}
        if "task" in attrs:
            return str(attrs["task"])
        if node.get("kind") == "task":
            return str(node["name"])
        node = by_id.get(node.get("parent"))
        seen += 1
    return None


def _new_row() -> Dict[str, float]:
    return {
        "wall_s": 0.0, "host_io_s": 0.0, "host_compute_s": 0.0,
        "collective_s": 0.0, "host_s": 0.0, "dispatch_wall_s": 0.0,
        "overlap_hidden_s": 0.0, "n_spans": 0,
    }


def summarize(run: Dict[str, Any]) -> Dict[str, Any]:
    spans = run["spans"]
    by_id = {s["id"]: s for s in spans}
    child_time: Dict[int, float] = {}
    for s in spans:
        parent = s.get("parent")
        if parent is not None and s.get("kind") != "timing":
            child_time[parent] = (
                child_time.get(parent, 0.0) + (s["t1"] - s["t0"])
            )

    tasks: Dict[str, Dict[str, float]] = {}
    n_task_spans = 0
    for s in spans:
        name = _task_of(s, by_id) or "(no task)"
        row = tasks.setdefault(name, _new_row())
        dur = s["t1"] - s["t0"]
        self_t = max(0.0, dur - child_time.get(s["id"], 0.0))
        kind = s["kind"]
        row["n_spans"] += 1
        if kind == "task":
            n_task_spans += 1
            row["wall_s"] += dur
        elif kind == "dispatch":
            row["dispatch_wall_s"] += dur
        elif kind in _EXCLUDED_KINDS:
            pass
        else:
            row[_BUCKETS.get(kind, "host_s")] += self_t
    for row in tasks.values():
        if row["dispatch_wall_s"] > 0.0:
            busy = row["host_io_s"] + row["host_compute_s"] + row["host_s"]
            row["overlap_hidden_s"] = max(0.0, busy - row["dispatch_wall_s"])
    return {
        "run_id": run["run_id"],
        "n_task_spans": n_task_spans,
        "n_processes": len({h["pid"] for h in run["headers"]}),
        "tasks": tasks,
        "counters": run["counters"],
        "gauges": run["gauges"],
        # ctt-slo: the key appears only when the run recorded histograms,
        # so the machine-readable golden stays unchanged without them
        **({"hists": run["hists"]}
           if (run.get("hists") or {}).get("hists") else {}),
    }


def format_summary(summary: Dict[str, Any]) -> str:
    cols = ["wall_s", "host_io_s", "host_compute_s", "collective_s", "host_s",
            "overlap_hidden_s", "n_spans"]
    names = sorted(
        summary["tasks"],
        key=lambda n: -summary["tasks"][n]["wall_s"],
    )
    width = max([len(n) for n in names] + [4])
    cw = [max(9, len(c)) for c in cols]
    lines = [
        f"run {summary['run_id']}  "
        f"({summary['n_task_spans']} task spans, "
        f"{summary['n_processes']} processes)",
        "  ".join(["task".ljust(width)]
                  + [c.rjust(w) for c, w in zip(cols, cw)]),
    ]
    for n in names:
        row = summary["tasks"][n]
        cells = [
            (f"{row[c]:.3f}" if c != "n_spans" else f"{int(row[c])}").rjust(w)
            for c, w in zip(cols, cw)
        ]
        lines.append("  ".join([n.ljust(width)] + cells))
    counters = summary["counters"]
    if counters:
        lines.append("counters:")
        for k in sorted(counters):
            v = counters[k]
            lines.append(f"  {k} = {v:.0f}" if float(v).is_integer()
                         else f"  {k} = {v:.3f}")
    # ctt-slo: only when the run actually carries histograms, so existing
    # summary output stays byte-identical for runs without them.
    hists = (summary.get("hists") or {}).get("hists") or []
    if hists:
        lines.append("latency (s):")
        for s in hists:
            buckets = list(s["buckets"])
            p50 = hist_mod.quantile(buckets, 0.50)
            p99 = hist_mod.quantile(buckets, 0.99)
            lbl = ",".join(f"{k}={v}" for k, v in
                           sorted(s.get("labels", {}).items()))
            series = s["name"] + (f"{{{lbl}}}" if lbl else "")
            lines.append(
                f"  {series} p50={p50:.6f} p99={p99:.6f} n={int(s['count'])}"
            )
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# Chrome trace_event export (Perfetto / chrome://tracing)


def to_chrome_trace(run: Dict[str, Any]) -> Dict[str, Any]:
    events: List[dict] = []
    for h in run["headers"]:
        events.append({
            "ph": "M", "name": "process_name", "pid": h["pid"], "tid": 0,
            "args": {"name": f"pid {h['pid']} ({h.get('host', '?')})"},
        })
    for s in run["spans"]:
        args = dict(s.get("attrs") or {})
        args["span_id"] = s["id"]
        if s.get("parent") is not None:
            args["parent_id"] = s["parent"]
        events.append({
            "ph": "X",
            "name": s["name"],
            "cat": s["kind"],
            "ts": s["wall_t0"] * 1e6,
            "dur": (s["t1"] - s["t0"]) * 1e6,
            "pid": s["pid"],
            "tid": s["tid"],
            "args": args,
        })
    return {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": {"run_id": run["run_id"], "tool": "ctt-obs"},
    }


# ---------------------------------------------------------------------------
# run diff


def diff(
    run_a: Dict[str, Any],
    run_b: Dict[str, Any],
    threshold: float = 0.2,
    min_seconds: float = 0.01,
) -> Dict[str, Any]:
    """Per-task wall-clock comparison of two runs (a = baseline, b =
    candidate).  A task regresses when its wall grows by more than
    ``threshold`` (fractional) AND by more than ``min_seconds`` (absolute
    floor: microsecond jitter on trivial tasks is not a regression)."""
    sa, sb = summarize(run_a), summarize(run_b)
    rows: List[dict] = []
    names = sorted(set(sa["tasks"]) | set(sb["tasks"]))
    for name in names:
        a = sa["tasks"].get(name)
        b = sb["tasks"].get(name)
        if a is None or b is None:
            rows.append({
                "task": name,
                "a_wall_s": a["wall_s"] if a else None,
                "b_wall_s": b["wall_s"] if b else None,
                "ratio": None,
                "regressed": False,
                "note": "only in baseline" if b is None else "only in candidate",
            })
            continue
        aw, bw = a["wall_s"], b["wall_s"]
        ratio = (bw / aw) if aw > 0 else None
        regressed = (
            bw > aw * (1.0 + threshold) and (bw - aw) > min_seconds
        )
        rows.append({
            "task": name, "a_wall_s": aw, "b_wall_s": bw,
            "ratio": ratio, "regressed": regressed, "note": "",
        })
    return {
        "a": sa["run_id"], "b": sb["run_id"],
        "threshold": threshold, "rows": rows,
        "n_regressed": sum(1 for r in rows if r["regressed"]),
    }


def format_diff(result: Dict[str, Any]) -> str:
    width = max([len(r["task"]) for r in result["rows"]] + [4])
    lines = [
        f"diff {result['a']} -> {result['b']} "
        f"(threshold {result['threshold']:.0%})",
        "  ".join(["task".ljust(width), "base_s".rjust(9),
                   "cand_s".rjust(9), "ratio".rjust(7), "flag"]),
    ]
    for r in result["rows"]:
        a = "-" if r["a_wall_s"] is None else f"{r['a_wall_s']:.3f}"
        b = "-" if r["b_wall_s"] is None else f"{r['b_wall_s']:.3f}"
        ratio = "-" if r["ratio"] is None else f"{r['ratio']:.2f}x"
        flag = "REGRESSED" if r["regressed"] else r["note"]
        lines.append("  ".join([
            r["task"].ljust(width), a.rjust(9), b.rjust(9),
            ratio.rjust(7), flag,
        ]).rstrip())
    lines.append(
        f"{result['n_regressed']} task(s) regressed beyond the threshold"
    )
    return "\n".join(lines)
