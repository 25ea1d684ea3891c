"""ctt-obs span recorder: low-overhead, process-safe structured tracing.

Where a workflow's wall-clock goes was previously invisible: the only
telemetry was per-dispatch ``time.time()`` deltas buried in status JSON
(`Task.record_timing`).  This module records *spans* — named, nested
intervals on the monotonic clock — into per-(pid, thread) JSONL shards
that `obs.export` merges across processes into one run:

  run (``build``) → task → dispatch → block-batch / block → host-IO,
  plus collective spans from ``parallel/sharded*.py``.

Design constraints (the reasons it looks the way it does):

  * **No-op fast path.**  Tracing is off unless ``CTT_TRACE_DIR`` is set
    (or `enable()` is called): ``span()`` then returns a shared singleton
    context manager — no allocation, no clock read, no lock.  Hot paths
    (per-chunk store IO) use `obs.metrics` counters instead of spans.
  * **One writer per shard.**  Every (pid, thread) pair appends to its own
    ``spans.p<pid>.t<tid>.jsonl`` — the same pid+thread-uniqueness
    convention as the store's atomic tmp files (utils/store.py
    ``_atomic_write_bytes``) — so concurrent block threads never interleave
    partial lines and no cross-process lock exists.
  * **Monotonic durations, wall-clock anchors.**  Span endpoints are
    ``time.monotonic()`` (immune to clock jumps — the same fix applied to
    the task deadlines, see CTT008); each shard's header records one
    (wall, mono) anchor pair so the exporter can place spans on a shared
    wall-clock axis across processes.
  * **Cross-process-unique span ids**: ``pid << 24 | counter`` — shards
    from any number of single-host processes merge without collisions.
  * **Parents are best-effort.**  Nesting is tracked per thread; spans
    opened in worker threads (executor pipelining) carry an explicit
    ``task=...`` attribute instead, and the exporter resolves task
    attribution through either route.

Clock vocabulary for the rest of the codebase (enforced by lint rule
CTT008): ``time.time()`` is for *timestamps* only; durations and deadlines
use ``obs.trace.monotonic()`` (= ``time.monotonic()``) so a host clock
jump can never fire or stall a timeout.

The artifact formats below are REGISTRY-DERIVED: the machine-readable
source of truth is ``analysis/protocols.py`` (one ``ArtifactSchema`` per
file kind — required/optional keys, producers, consumers, torn-write
tolerance), and ``analysis.check_docstring_sync()`` asserts every
registered required key still appears in this docstring (whole-tree test
in tests/test_ctt_proto.py).  Edit the registry first; this prose
follows it.

Run-directory file formats (everything ``obs.live`` tails)::

    spans.p<pid>.t<tid>.jsonl   append-only; line 1 a header record
                                {"type": "header", "run", "pid", "tid",
                                 "host", "wall", "mono"}  (the (wall, mono)
                                anchor pair), then span records
                                {"type": "span", "id", "parent", "name",
                                 "kind", "t0", "t1", "pid", "tid",
                                 "attrs"?}  with monotonic endpoints.
    metrics.p<pid>.json         one snapshot per process, atomically
                                replaced on flush: {"counters", "gauges"}.
    hist.p<pid>.json            ctt-slo latency-histogram snapshot per
                                process, atomically replaced on flush:
                                {"schema", "edges" (the FIXED log2 bucket
                                edges every histogram shares — merging
                                two snapshots is bucket-wise addition,
                                exact), "hists": [{"name", "labels",
                                "buckets", "sum", "count"}, ...]}.
    hb.p<pid>.json              ctt-watch heartbeat, atomically replaced
                                every CTT_HEARTBEAT_S while the process
                                executes blocks: liveness + role/job id +
                                progress counters + in-flight block ids +
                                device-memory high-water + an (wall, mono)
                                anchor and the promised cadence — full
                                field list in obs/heartbeat.py.

Work-queue file formats (ctt-steal; live in ``<job_dir>/queue/`` next to
the cluster job scripts, not the trace dir — documented here beside the
heartbeat schema because leases follow the same clock contract: wall
stamps for cross-process ageing, monotonic for the writer's diagnostics)::

    manifest.json               written once by the driver (fsync'd
                                atomic): {"task", "items": [[block ids],
                                ...], "lease_s", "duplicate",
                                "created_wall"}.
    lease.<k>.g<g>.json         generation-g ownership of item k, created
                                by an exclusive os.link publish (the claim
                                race's arbiter) and atomically re-stamped
                                every lease_s by the owner: {"item",
                                "gen", "blocks", "owner_pid", "job_id",
                                "host", "claim_wall", "wall", "mono"}.
                                A stamp older than 3 x lease_s means the
                                owner is dead (the heartbeat-staleness
                                rule) — any worker may claim gen g+1.
    result.<k>.json             item k's terminal record, published
                                first-writer-wins via the same link
                                idiom: {"item", "gen", "done", "failed",
                                "errors", "pid", "job_id", "duplicate",
                                "seconds", "wall"}.

Serving-daemon file formats (ctt-serve; live in the daemon's state dir —
the same lease clock contract, lifted from block-batch grain to job
grain; the HTTP wire schema is documented in ``serve/protocol.py``)::

    serve.json                  the endpoint record, atomically replaced
                                at daemon start with mode 0600: {"host",
                                "port", "pid", "daemon_id",
                                "started_wall", "run_id",
                                "token"} — clients discover the daemon by
                                file, not by port convention, and
                                "token" (required on every request
                                except /healthz, via X-CTT-Serve-Token
                                or Authorization: Bearer) makes reading
                                this file the authorization: loopback
                                reachability alone grants nothing.
    jobs/job.<id>.json          one submission, published exactly once
                                (exclusive link): {"id", "seq", "schema",
                                "workflow", "kwargs", "configs",
                                "tenant", "priority", "submit_wall"}.
    jobs/lease.<id>.g<g>.json   generation-g execution ownership,
                                re-stamped every lease_s by the running
                                daemon: {"job", "gen", "owner_pid",
                                "daemon" (the claiming daemon's fleet id,
                                stamped at claim time so peers can judge
                                the lease even if the owner dies before
                                its first renewal), "claim_wall", "wall",
                                "mono", optional "dispatch_wall" (ctt-slo:
                                when this generation's execution began,
                                after any microbatch window — re-stamped
                                on every renewal so it survives to the
                                post-mortem)}.  Stale beyond 3 x lease_s = the
                                daemon died mid-job; the next daemon on
                                the same state dir claims gen g+1 — or
                                immediately, if the owner's fleet beat
                                (below) already proves it dead.  A lease
                                re-stamped with {"released": true, "wall":
                                0} is a voluntary give-back (drain suspend
                                of a long-lived ingest job): it classifies
                                expired at once, and released generations
                                do not count against the retry budget.
    jobs/admit.<id>.json        ctt-fleet two-phase admission marker,
                                exclusive link: {"id", "wall", "daemon"}.
                                A record published with "admitted": false
                                is claimable only once this lands; a
                                rejected submission is retracted as a
                                result with "rejected": true instead.
    jobs/result.<id>.json       terminal record, first writer wins:
                                {"id", "gen", "ok", "error", "seconds",
                                "warm", "compile_cache": {"hits",
                                "misses"}, "tenant", "pid", "daemon",
                                "finished_wall", plus the ctt-slo phase
                                walls "claimed_wall"/"dispatch_wall"/
                                "published_wall" of the winning
                                generation — ``obs journey`` rebuilds
                                the per-phase breakdown from this record
                                alone}.  A quarantined poison
                                job (retry budget exhausted) parks here
                                with {"ok": false, "quarantined": true,
                                "failure_log": [each burned generation's
                                last lease stamp], "gen" = max_job_gens};
                                an admission retraction with {"ok":
                                false, "rejected": true, "gen": -1}.
    daemon.<id>.json            ctt-fleet heartbeat, atomically replaced
                                every CTT_HEARTBEAT_S (the ctt-watch
                                cadence — NOT lease_s: failover latency
                                is bounded by this beat): {"id", "pid",
                                "host", "port", "wall", "mono",
                                "interval_s" (the promised cadence),
                                "seq", "draining", "exiting",
                                "running_jobs", "queued", "concurrency"}.
                                A beat older than 3 x its interval_s, or
                                stamped "exiting": true, marks the daemon
                                dead: peers expire its job leases on the
                                spot (serve.jobs_reclaimed) instead of
                                waiting out lease staleness.
    snap.<id>.json              ctt-slo per-daemon telemetry snapshot,
                                atomically replaced on the fleet-beat
                                cadence: {"schema", "daemon", "pid",
                                "wall", "counters", "gauges", "hists"
                                (a hist.p-format snapshot)}.  ``obs
                                fleet`` merges every daemon's snap over
                                one backend listing — counters summed,
                                gauges last-writer in sorted-daemon
                                order, histograms bucket-wise (exact) —
                                into one OpenMetrics rollup with
                                fleet-wide p50/p99 latency gauges, and
                                ``obs slo`` gates objectives against it.

Hierarchy artifact (ctt-hier; lives BESIDE the labels volume —
``<output_path>/<output_key>_hierarchy.npz`` by default — because it is
part of the segmentation product, not run scratch; documented here with
the other cross-process file contracts)::

    <key>_hierarchy.npz         np.savez, written atomically: {"schema"
                                (ops/hier.HIER_SCHEMA_VERSION), "a", "b"
                                (int64 GLOBAL region-id pairs, a < b),
                                "saddle" (float32, ascending — the sorted
                                order IS the contract: re-cutting at any
                                threshold is one searchsorted over this
                                column), "n_labels", "shape",
                                "block_shape"}.  Saddle of a pair = min
                                over the regions' shared boundary of
                                max(h(p), h(q)) on the flood's working
                                input.
    hier_offsets.npz            tmp-folder scratch (the merge_offsets
                                idiom): {"offsets" (exclusive prefix sum
                                of per-block max ids), "n_labels"}.
    data.zarr/hier/*            ragged per-block scratch: ``max_ids``,
                                ``pairs``/``saddles`` (in-block table,
                                block-LOCAL ids, (k,2) int64 flattened +
                                (k,) float32), ``face_pairs``/
                                ``face_saddles`` (cross-block table,
                                GLOBAL ids).

Streaming-ingest control dir (ctt-ingest; a POSIX dir or object-store
prefix the acquisition writer and the ingest daemon share — the watcher's
poll primitive is one listing GET over it)::

    ingest.manifest.json        stream geometry, published once
                                (publish_once) by the writer before the
                                first slab: {"schema", "domain"
                                ("volume"/"frames"), "shape" (final),
                                "slab_depth" (extent along axis 0 per
                                landing), "slabs_total", "created_wall"}.
    slab.NNNNNN.json            per-slab landing marker, create-only,
                                published AFTER the slab's data is
                                durably written: {"slab", "wall",
                                optional "digest"}.  The marker is the
                                commit point; a torn marker is skipped
                                until a later poll reads it whole, and
                                the watcher's ready-frontier (count of
                                consecutive markers from 0) never
                                regresses.
    ingest.carry.sNNNNNN.json   carry snapshot after chunk N committed,
                                create-only (a lost race = a concurrent
                                successor committed the same slab):
                                {"schema", "chain", "slab", "slabs_done",
                                "carry" (pickle+zlib+base64 of the
                                _ChainRunner carry: max-id offsets,
                                face-edge tables), "carry_bytes"
                                (raw pickle size), "cap_hint"
                                (ops.events._CAP_HINT snapshot — the
                                frame domain's zero-recompile warmup),
                                "wall"}.  A resuming process loads the
                                highest readable record and skips its
                                chunks; an unreadable record falls back
                                one slab (idempotent block writes make
                                the re-run harmless).
    ingest.frontier.json        advisory commit frontier, atomically
                                replaced after every slab: {"schema",
                                "slabs_done", "slabs_total", "resumes",
                                "wall"}.  Torn reads degrade to the
                                carry records, which are the truth.
"""

from __future__ import annotations

import atexit
import functools
import itertools
import json
import os
import socket
import sys
import threading
import time
from typing import Any, Dict, IO, Optional, Tuple

__all__ = [
    "enabled", "enable", "disable", "flush", "span", "event", "traced",
    "current_run_id", "run_dir", "monotonic", "new_run_id",
]

ENV_DIR = "CTT_TRACE_DIR"
ENV_RUN = "CTT_RUN_ID"

# duration clock for the whole codebase (CTT008: wall clock is for
# timestamps only) — a named alias so call sites read as intent
monotonic = time.monotonic

_SPAN_ID_PID_SHIFT = 24  # pid << 24 | counter: unique across processes


def new_run_id() -> str:
    """Human-sortable, collision-safe run id (wall stamp + pid + nonce)."""
    stamp = time.strftime("%Y%m%d_%H%M%S")
    nonce = os.urandom(2).hex()
    return f"run_{stamp}_p{os.getpid()}_{nonce}"


class _RunState:
    """Open shard handles + per-thread span stacks for one enabled run."""

    def __init__(self, trace_dir: str, run_id: str):
        self.trace_dir = trace_dir
        self.run_id = run_id
        self.dir = os.path.join(trace_dir, run_id)
        self.lock = threading.Lock()  # guards the shard-handle dict only
        self.shards: Dict[Tuple[int, int], IO[str]] = {}
        self.local = threading.local()
        self.counter = itertools.count(1)

    # -- per-thread span stack (parent tracking) --------------------------

    def stack(self):
        st = getattr(self.local, "stack", None)
        if st is None:
            st = []
            self.local.stack = st
        return st

    # -- shard IO ----------------------------------------------------------

    def _shard(self) -> IO[str]:
        key = (os.getpid(), threading.get_ident())
        f = self.shards.get(key)
        if f is None or f.closed:
            with self.lock:
                f = self.shards.get(key)
                if f is None or f.closed:
                    os.makedirs(self.dir, exist_ok=True)
                    path = os.path.join(
                        self.dir, f"spans.p{key[0]}.t{key[1]}.jsonl"
                    )
                    f = open(path, "a", buffering=1)
                    # anchor pair: the exporter maps mono -> wall with it.
                    # time.time() here is a timestamp, not duration math.
                    f.write(json.dumps({
                        "type": "header",
                        "run": self.run_id,
                        "pid": key[0],
                        "tid": key[1],
                        "host": socket.gethostname(),
                        "wall": time.time(),
                        "mono": monotonic(),
                    }) + "\n")
                    self.shards[key] = f
        return f

    def write(self, record: Dict[str, Any]) -> None:
        self._shard().write(json.dumps(record) + "\n")

    def next_span_id(self) -> int:
        return (os.getpid() << _SPAN_ID_PID_SHIFT) | (
            next(self.counter) & ((1 << _SPAN_ID_PID_SHIFT) - 1)
        )

    def flush(self) -> None:
        with self.lock:
            for f in list(self.shards.values()):
                try:
                    if not f.closed:
                        f.flush()
                except OSError:  # pragma: no cover - flush is best-effort
                    pass

    def close(self) -> None:
        with self.lock:
            for f in list(self.shards.values()):
                try:
                    if not f.closed:
                        f.close()
                except OSError:  # pragma: no cover
                    pass
            self.shards.clear()


_RUN: Optional[_RunState] = None
_ATEXIT_REGISTERED = False


def enabled() -> bool:
    return _RUN is not None


def current_run_id() -> Optional[str]:
    return _RUN.run_id if _RUN is not None else None


def run_dir() -> Optional[str]:
    """Directory holding this run's shards (``<trace_dir>/<run_id>``)."""
    return _RUN.dir if _RUN is not None else None


def enable(
    trace_dir: Optional[str] = None,
    run_id: Optional[str] = None,
    export_env: bool = True,
) -> str:
    """Turn tracing on (idempotent for an identical dir+run).

    ``export_env=True`` publishes CTT_TRACE_DIR / CTT_RUN_ID so child
    processes (bench subprocesses, scheduler workers, multi-host peers
    launched from here) join the SAME run — the cross-process contract.
    Returns the run id.
    """
    global _RUN, _ATEXIT_REGISTERED
    if trace_dir is None:
        trace_dir = os.environ.get(ENV_DIR)
        if not trace_dir:
            raise ValueError(
                "enable() needs a trace_dir (argument or CTT_TRACE_DIR)"
            )
    if run_id is None:
        run_id = os.environ.get(ENV_RUN) or new_run_id()
    if _RUN is not None:
        if _RUN.trace_dir == trace_dir and _RUN.run_id == run_id:
            return run_id
        disable()
    _RUN = _RunState(trace_dir, run_id)
    if export_env:
        os.environ[ENV_DIR] = trace_dir
        os.environ[ENV_RUN] = run_id
    if not _ATEXIT_REGISTERED:
        atexit.register(flush)
        _ATEXIT_REGISTERED = True
    return run_id


def disable() -> None:
    """Flush and stop recording (the env vars are left untouched so an
    explicit disable() sticks for this process only)."""
    global _RUN
    if _RUN is not None:
        try:
            from . import metrics as _metrics

            _metrics.flush()
        except Exception:  # pragma: no cover  # ctt: noqa[CTT009] teardown is best-effort: a metrics flush failure must not block disable()
            pass
        _RUN.flush()
        _RUN.close()
        _RUN = None


def flush() -> None:
    """Flush every open shard (and the metrics snapshot) to disk — called
    at the end of ``runtime.build`` and atexit, so short-lived processes
    (scheduler workers, bench subprocesses) never lose buffered spans."""
    if _RUN is not None:
        try:
            from . import metrics as _metrics

            _metrics.flush()
        except Exception:  # pragma: no cover  # ctt: noqa[CTT009] flush is best-effort by contract (atexit path)
            pass
        _RUN.flush()


def _bootstrap_from_env() -> None:
    trace_dir = os.environ.get(ENV_DIR)
    if trace_dir:
        enable(trace_dir)


# ---------------------------------------------------------------------------
# spans


class _NoopSpan:
    """Shared do-nothing span: the disabled fast path allocates nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **attrs) -> None:
        pass


_NOOP = _NoopSpan()


def _annotation(name: str):
    """A ``jax.profiler.TraceAnnotation`` of ``name``, so the span also
    lands in a profiler trace's host plane, on the device trace's clock;
    None in a process that has not imported jax (no profiler can run
    there, and a span never imports it)."""
    profiler = getattr(sys.modules.get("jax"), "profiler", None)
    if profiler is None:
        return None
    return profiler.TraceAnnotation(name)


class _Span:
    __slots__ = ("name", "kind", "attrs", "sid", "parent", "t0", "_st",
                 "_ann")

    def __init__(self, st: _RunState, name: str, kind: str, attrs):
        self._st = st
        self.name = name
        self.kind = kind
        self.attrs = attrs
        self.sid = st.next_span_id()
        self.parent = None
        self.t0 = 0.0
        self._ann = None

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)

    def __enter__(self) -> "_Span":
        stack = self._st.stack()
        if stack:
            self.parent = stack[-1].sid
        stack.append(self)
        self._ann = _annotation(self.name)
        if self._ann is not None:
            self._ann.__enter__()
        self.t0 = monotonic()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        t1 = monotonic()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        stack = self._st.stack()
        if stack and stack[-1] is self:
            stack.pop()
        if exc_type is not None:
            self.attrs["error"] = exc_type.__name__
        record = {
            "type": "span",
            "id": self.sid,
            "parent": self.parent,
            "name": self.name,
            "kind": self.kind,
            "t0": self.t0,
            "t1": t1,
            "pid": os.getpid(),
            "tid": threading.get_ident(),
        }
        if self.attrs:
            record["attrs"] = self.attrs
        self._st.write(record)
        return False


def span(name: str, kind: str = "host", **attrs):
    """Context manager recording one interval.

    ``kind`` buckets the summarize table: ``host_io`` (chunk reads/writes),
    ``host_compute`` (a blocking device dispatch, timed on the host clock
    from launch to the results on the host), ``collective`` (mesh programs
    in parallel/), ``task``/``dispatch``/``run`` (structural), ``barrier``
    (peer waits), ``host`` (everything else), ``timing`` (retroactive
    record_timing bridge events — excluded from bucket sums).  Pass
    ``task=<identifier>`` when the span may open in a worker thread, where
    the per-thread parent stack cannot see the task span.  An enabled span
    also enters a ``jax.profiler.TraceAnnotation`` of its name, so a
    profiler trace shows it beside the device's operations.
    """
    st = _RUN
    if st is None:
        return _NOOP
    return _Span(st, name, kind, attrs)


def traced(name: Optional[str] = None, kind: str = "host", **attrs):
    """Decorator form of :func:`span` for whole functions (e.g. the
    collective entry points in ``parallel/sharded*.py``).  When tracing is
    disabled the only overhead is one module-global None check."""

    def deco(fn):
        label = name or fn.__name__

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            if _RUN is None:
                return fn(*args, **kwargs)
            with span(label, kind=kind, **attrs):
                return fn(*args, **kwargs)

        return wrapper

    return deco


def event(name: str, kind: str, seconds: float, **attrs) -> None:
    """Record a retroactive, already-measured interval ending now (the
    bridge for `Task.record_timing`'s after-the-fact durations).  The
    placement on the time axis is approximate (ends at 'now'); the
    duration is exact."""
    st = _RUN
    if st is None:
        return
    t1 = monotonic()
    record = {
        "type": "span",
        "id": st.next_span_id(),
        "parent": None,
        "name": name,
        "kind": kind,
        "t0": t1 - float(seconds),
        "t1": t1,
        "pid": os.getpid(),
        "tid": threading.get_ident(),
    }
    if attrs:
        record["attrs"] = attrs
    st.write(record)


_bootstrap_from_env()
