"""ctt-obs counters and gauges: cheap aggregates for hot paths.

Spans (obs.trace) are the right tool for intervals; per-chunk store IO is
far too hot for a JSONL line per operation.  These process-local counters
cost one enabled-check + one dict update per call and flush as ONE
``metrics.p<pid>.json`` snapshot per process into the active run's
directory (atomic tmp+replace, the store convention), where
``obs.export`` sums them across processes.

Wired in:

  * ``utils/store.py`` — ``store.bytes_read`` / ``store.bytes_written`` /
    ``store.chunks_read`` / ``store.chunks_written`` (chunk payload sizes
    at the codec boundary: what actually crossed the filesystem);
    ``store.chunk_cache_hits`` / ``store.chunk_cache_misses`` (the decoded-
    chunk LRU: hits are decodes the cache absorbed, e.g. overlapping halo
    reads) and ``store.aligned_chunk_writes`` (region writes that took the
    chunk-aligned encode fast path instead of read-modify-write);
  * ``utils/compile_cache.py`` — ``compile_cache.cache_hits`` /
    ``compile_cache.cache_misses`` via a ``jax.monitoring`` event
    listener, plus an ``entries_at_enable`` gauge; ``jit.compiles`` /
    ``jit.compile_s``, the count and seconds of JAX's backend compiles
    (a load from the persistent cache included), via a duration listener;
  * ``tasks/base.py`` ``count_block_rounds`` — the block programs' loop
    rounds, outputs of the programs themselves: ``flood.rounds`` (global
    altitude + assignment loops of every flood), ``flood.tile_rounds``
    (tile warm-start loops), ``cc.rounds`` (CC fixpoint loops) and
    ``blocks.computed`` (the blocks they cover);
  * ``runtime/task.py`` — ``task.blocks_failed`` / ``task.blocks_retried``;
  * ``faults/`` + the resilience paths it validates (ctt-fault) —
    ``faults.injected`` / ``faults.injected.<site>`` (every fired
    injection), ``store.io_retries`` (backoff sleeps absorbed by
    ``utils/retry.py`` on transient chunk IO), ``executor.blocks_timed_out``
    (blocks the soft-deadline watchdog converted into failures), and
    ``sharded.fallback_local`` (collective→local kernel degradations) —
    so a chaos run's injections AND recoveries are diffable with
    ``obs diff``;
  * ``runtime/executor.py`` — ``executor.batches`` /
    ``executor.batch_s`` (summed in-flight batch seconds) /
    ``executor.dispatch_wall_s`` (wall of the whole dispatch round):
    ``batch_s - dispatch_wall_s > 0`` is host IO hidden behind device
    execution by the pipeline (depth > 1).  The three-stage pipeline
    (split-protocol tasks at depth > 1) additionally reports per-stage
    occupancy — ``executor.stage_read_s`` / ``executor.stage_compute_s`` /
    ``executor.stage_write_s`` / ``executor.stage_batches`` — and
    ``executor.stage_hidden_io_s``, the read+write seconds hidden behind
    the serialized compute stage.

  * ``runtime/stream.py`` (ctt-stream) — ``stream.chains`` /
    ``stream.slabs`` / ``stream.elided_bytes`` (intermediate bytes that
    never reached the store) / ``stream.fallbacks`` plus the
    ``stream.carry_bytes`` peak gauge: how much a fused chain streamed,
    elided, and carried.

Enabled exactly when tracing is enabled (one switch: CTT_TRACE_DIR).

Naming: every counter/gauge name is listed in :mod:`obs.registry`
(dynamic families like ``faults.injected.<site>`` by prefix) and lint
rule CTT010 flags literals absent from it — a typo'd name would
otherwise silently create a series nothing reads.
"""

from __future__ import annotations

import json
import os
import threading
from typing import Any, Dict

from . import trace

__all__ = [
    "inc", "set_gauge", "snapshot", "flush",
    "install_compile_cache_listener", "reset",
]

_LOCK = threading.Lock()
_COUNTERS: Dict[str, float] = {}
_GAUGES: Dict[str, Any] = {}

METRICS_FILE_PREFIX = "metrics.p"


def inc(name: str, value: float = 1.0) -> None:
    if not trace.enabled():
        return
    with _LOCK:
        _COUNTERS[name] = _COUNTERS.get(name, 0.0) + value


def set_gauge(name: str, value: Any) -> None:
    if not trace.enabled():
        return
    with _LOCK:
        _GAUGES[name] = value


def snapshot() -> Dict[str, Any]:
    with _LOCK:
        return {"counters": dict(_COUNTERS), "gauges": dict(_GAUGES)}


def reset() -> None:
    """Drop all accumulated values (test isolation helper)."""
    with _LOCK:
        _COUNTERS.clear()
        _GAUGES.clear()


def flush() -> None:
    """Write this process's snapshot into the active run directory.
    Atomic (tmp + os.replace); repeated flushes overwrite with the latest
    totals, so the last write per process wins."""
    rdir = trace.run_dir()
    if rdir is None:
        return
    snap = snapshot()
    if not snap["counters"] and not snap["gauges"]:
        return
    os.makedirs(rdir, exist_ok=True)
    path = os.path.join(rdir, f"{METRICS_FILE_PREFIX}{os.getpid()}.json")
    tmp = path + f".tmp{os.getpid()}.{threading.get_ident()}"
    with open(tmp, "w") as f:
        json.dump(snap, f, indent=2, sort_keys=True)
    os.replace(tmp, path)


# ---------------------------------------------------------------------------
# jax compile-cache hit/miss listener

_CACHE_LISTENER_INSTALLED = False

# jax.monitoring event names emitted by the persistent compilation cache
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "compile_cache.cache_hits",
    "/jax/compilation_cache/cache_misses": "compile_cache.cache_misses",
    "/jax/compilation_cache/tasks_using_cache": "compile_cache.tasks_using_cache",
}
# the duration event JAX records around each backend compile (or load of
# an executable from the persistent cache), jax/_src/dispatch.py
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def install_compile_cache_listener() -> bool:
    """Count persistent-compile-cache hits/misses and backend compiles via
    ``jax.monitoring`` (idempotent).  Returns False when the monitoring API
    is unavailable — the cache keeps working, only the metrics are
    absent."""
    global _CACHE_LISTENER_INSTALLED
    if _CACHE_LISTENER_INSTALLED:
        return True
    try:
        from jax import monitoring
    except ImportError:  # pragma: no cover - jax is baked into the image
        return False

    def _listener(event: str, **kwargs) -> None:
        name = _CACHE_EVENTS.get(event)
        if name is not None:
            inc(name)

    def _duration_listener(event: str, duration: float, **kwargs) -> None:
        if event == _COMPILE_EVENT:
            inc("jit.compiles")
            inc("jit.compile_s", duration)

    try:
        monitoring.register_event_listener(_listener)
        monitoring.register_event_duration_secs_listener(_duration_listener)
    except Exception:  # pragma: no cover - API drift must not break callers
        return False
    _CACHE_LISTENER_INSTALLED = True
    return True
