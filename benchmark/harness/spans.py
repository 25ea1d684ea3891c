"""The program's own records: spans of ``CTT_TRACE_DIR`` (monotonic clock,
one JSONL shard per process and thread) and the per-task walls of each
job's status files."""

from __future__ import annotations

import glob
import json
import os
from typing import Dict, List, Sequence, Tuple


def read_spans(run_dir: str) -> List[dict]:
    out = []
    for path in glob.glob(os.path.join(run_dir, "**", "spans.*.jsonl"),
                          recursive=True):
        with open(path) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue  # a torn last line
                if rec.get("type") == "span":
                    out.append(rec)
    return out


def inside(spans: Sequence[dict], lo: float, hi: float) -> List[dict]:
    return [s for s in spans if s["t0"] >= lo and s["t1"] <= hi]


def union_seconds(intervals: Sequence[Tuple[float, float]]) -> float:
    total, end = 0.0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def counter_delta(before: Dict[str, float],
                  after: Dict[str, float]) -> Dict[str, float]:
    return {k: v - before.get(k, 0.0) for k, v in after.items()}


def _statuses(tmp_folder: str):
    """``(file name, record)`` of each readable status file of a job."""
    sdir = os.path.join(tmp_folder, "status")
    if not os.path.isdir(sdir):
        return
    for name in sorted(os.listdir(sdir)):
        if not name.endswith(".status.json"):
            continue
        try:
            with open(os.path.join(sdir, name)) as fh:
                yield name, json.load(fh)
        except (OSError, ValueError):
            continue


def task_walls(tmp_folder: str) -> Dict[str, float]:
    """Per-task busy seconds from a job's status files: the local
    executor's ``blocks_total`` records and the tpu executor's per-batch
    ``batch_*`` walls, one aggregate per dispatch round."""
    out: Dict[str, float] = {}
    for name, st in _statuses(tmp_folder):
        disp = sum(
            float(t.get("seconds", 0.0)) for t in st.get("timings", [])
            if t.get("label") == "blocks_total"
            or str(t.get("label", "")).startswith("batch_")
        )
        blk = sum(float(r) for r in st.get("block_runtimes", []))
        # one status file per process under the same task identifier
        key = st.get("task", name)
        out[key] = out.get(key, 0.0) + max(disp, blk)
    return out


def stage_walls(tmp_folder: str) -> Dict[str, float]:
    """Per-stage seconds of the three-stage executor summed over a job's
    status files (its ``stage_{read,compute,write}_total`` records, one
    per dispatch round); empty where no staged dispatch ran."""
    totals: Dict[str, float] = {}
    for _, st in _statuses(tmp_folder):
        for rec in st.get("timings", []):
            label = str(rec.get("label", ""))
            if label.startswith("stage_") and label.endswith("_total"):
                key = label[len("stage_"):-len("_total")]
                if key in ("read", "compute", "write"):
                    totals[key] = totals.get(key, 0.0) + float(
                        rec.get("seconds", 0.0))
    return totals
