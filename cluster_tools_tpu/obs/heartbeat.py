"""ctt-watch heartbeats: each process's "I am alive and here is where I am".

Span shards (obs.trace) only show work that *finished* — a hung worker is
exactly the process that stops producing them.  This module gives every
participating process (the driver executor and each scheduler worker) a
tiny periodic liveness record: a daemon thread writes one atomic
``hb.p<pid>.json`` file into the active run directory every
``CTT_HEARTBEAT_S`` seconds (default 5).  The live reader (obs.live)
re-reads these files each poll — they are single small JSON objects, not
append logs — and derives worker liveness, in-flight block age, and
per-process progress gauges from them.

Heartbeat file schema (one JSON object, atomically replaced per beat)::

    {
      "pid": 1234, "host": "...", "role": "driver" | "worker",
      "job_id": 3 | null,            # scheduler job id for workers
      "process_id": 0 | null,        # multi-host rank (CTT_PROCESS_ID)
      "run": "<run id>",
      "wall": 1722772000.1,          # time of this beat (timestamp)
      "mono": 5531.2,                # same instant, writer's monotonic clock
      "interval_s": 5.0,             # the cadence THIS writer promised
      "seq": 17,                     # beats written so far
      "exiting": false,              # true on the final beat (clean exit)
      "task": "watershed" | null,    # current task identifier
      "blocks_total": 64,            # this process's share of the dispatch
      "blocks_done": 24, "blocks_failed": 1, "blocks_retried": 1,
      "grid": [2, 4, 4] | null,      # blocking grid (heatmap geometry)
      "current_blocks": [{"id": 17, "start_mono": 5529.9}, ...],
      "queue_depth": 3 | null,       # unclaimed work-queue items as last
                                     # seen by this worker's pull loop
                                     # (ctt-steal; null outside steal runs)
      "draining": false,             # true once a serve daemon started its
                                     # SIGTERM drain (ctt-serve): still
                                     # alive, finishing in-flight jobs,
                                     # refusing new submissions
      "device_mem_peak_bytes": 1048576 | null
    }

Design constraints, mirroring the rest of ctt-obs:

  * **Same single switch.**  Nothing starts unless tracing is enabled
    (``CTT_TRACE_DIR``): ``ensure_started()`` is then one global check.
    The disabled-overhead smoke asserts no thread and no files.
  * **Atomic writes.**  tmp + ``os.replace`` (the store convention, minus
    fsync — heartbeats are advisory, durability would cost cadence).
  * **Monotonic durations, wall anchors.**  ``start_mono``/``mono`` are
    writer-clock; readers age a heartbeat via wall deltas (good to
    cross-process clock skew, exactly like the shard-header anchors).
  * **Never in the way.**  The beat thread swallows its own IO errors;
    ``note_*`` hooks are a lock + dict update when enabled, one global
    load when not.

``install_sigterm_flush()`` is the preemption hook (ctt-watch satellite):
scheduler SIGTERM → flush metrics + trace + one final ``exiting`` beat,
then chain to the previous handler / default die.
"""

from __future__ import annotations

import atexit
import json
import os
import signal
import socket
import sys
import threading
import time
from typing import Any, Dict, Optional

from . import trace

__all__ = [
    "ensure_started", "stop", "beat", "running", "interval_s",
    "note_task", "note_blocks_done", "note_blocks_failed",
    "note_blocks_retried", "note_block_start", "note_block_end",
    "note_queue_depth", "note_draining", "set_role",
    "install_sigterm_flush", "FILE_PREFIX", "ENV_INTERVAL",
]

ENV_INTERVAL = "CTT_HEARTBEAT_S"
DEFAULT_INTERVAL_S = 5.0
FILE_PREFIX = "hb.p"

# cap the in-flight list in the file: a wide thread pool should not make
# the heartbeat grow unboundedly — the oldest entries are the interesting
# ones (straggler detection keys on age)
_MAX_CURRENT_BLOCKS = 16


def interval_s() -> float:
    """Beat cadence: ``CTT_HEARTBEAT_S``, malformed/nonpositive values
    degrade to the default like every other CTT_* switch."""
    raw = os.environ.get(ENV_INTERVAL)
    try:
        val = float(raw) if raw is not None else DEFAULT_INTERVAL_S
    except (TypeError, ValueError):
        val = DEFAULT_INTERVAL_S
    return val if val > 0 else DEFAULT_INTERVAL_S


class _BeatState:
    """Mutable progress fields shared between the note_* hooks (hot path)
    and the beat thread (cold path)."""

    def __init__(self) -> None:
        self.lock = threading.Lock()
        self.role = "driver"
        self.job_id: Optional[int] = None
        self.task: Optional[str] = None
        self.blocks_total = 0
        self.blocks_done = 0
        self.blocks_failed = 0
        self.blocks_retried = 0
        self.grid: Optional[list] = None
        self.queue_depth: Optional[int] = None  # ctt-steal pull loops only
        self.draining = False  # ctt-serve SIGTERM drain in progress
        self.current: Dict[int, float] = {}  # block id -> start mono
        self.seq = 0
        self.thread: Optional[threading.Thread] = None
        self.wake = threading.Event()
        self.stopping = False


_STATE: Optional[_BeatState] = None
_STATE_LOCK = threading.Lock()
_ATEXIT_REGISTERED = False


def _topology_rank() -> Optional[int]:
    """Multi-host rank (``CTT_PROCESS_ID``, the runtime/config.py process
    topology) — None for single-host runs and scheduler workers."""
    raw = os.environ.get("CTT_PROCESS_ID")
    try:
        return int(raw) if raw is not None else None
    except (TypeError, ValueError):
        return None


def _device_mem_peak_bytes() -> Optional[int]:
    """High-water device memory across local devices, when jax is already
    up.  Never *triggers* backend init: a heartbeat must not be the thing
    that initializes a device backend (and claims the chip)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    try:
        peak = None
        for dev in jax.local_devices():
            stats_fn = getattr(dev, "memory_stats", None)
            stats = stats_fn() if stats_fn is not None else None
            if not stats:
                continue
            val = stats.get("peak_bytes_in_use", stats.get("bytes_in_use"))
            if val is not None:
                peak = max(peak or 0, int(val))
        return peak
    except Exception:  # pragma: no cover - backend quirks must not kill beats
        return None


def _write_beat(st: _BeatState, exiting: bool) -> None:
    rdir = trace.run_dir()
    if rdir is None:
        return
    with st.lock:
        st.seq += 1
        current = sorted(st.current.items(), key=lambda kv: kv[1])
        record = {
            "pid": os.getpid(),
            "host": socket.gethostname(),
            "role": st.role,
            "job_id": st.job_id,
            "process_id": _topology_rank(),
            "run": trace.current_run_id(),
            # wall is a timestamp (reader-side ageing), mono the same
            # instant on this process's duration clock
            "wall": time.time(),
            "mono": trace.monotonic(),
            "interval_s": interval_s(),
            "seq": st.seq,
            "exiting": bool(exiting),
            "task": st.task,
            "blocks_total": st.blocks_total,
            "blocks_done": st.blocks_done,
            "blocks_failed": st.blocks_failed,
            "blocks_retried": st.blocks_retried,
            "grid": st.grid,
            "current_blocks": [
                {"id": int(b), "start_mono": float(t0)}
                for b, t0 in current[:_MAX_CURRENT_BLOCKS]
            ],
            "queue_depth": st.queue_depth,
            "draining": st.draining,
            "device_mem_peak_bytes": _device_mem_peak_bytes(),
        }
    path = os.path.join(rdir, f"{FILE_PREFIX}{os.getpid()}.json")
    tmp = path + f".tmp{os.getpid()}.{threading.get_ident()}"
    try:
        os.makedirs(rdir, exist_ok=True)
        with open(tmp, "w") as f:
            json.dump(record, f)
        os.replace(tmp, path)
    except OSError:
        # liveness reporting is best-effort: a full disk must not take the
        # worker down with it
        try:
            os.unlink(tmp)
        except OSError:
            pass


def _beat_loop(st: _BeatState) -> None:
    while not st.stopping:
        _write_beat(st, exiting=False)
        st.wake.wait(interval_s())
        st.wake.clear()


def ensure_started(role: Optional[str] = None,
                   job_id: Optional[int] = None) -> bool:
    """Start the beat thread (idempotent).  No-op — no thread, no file —
    unless tracing is enabled; returns True when beating."""
    global _STATE, _ATEXIT_REGISTERED
    if not trace.enabled():
        return False
    st = _STATE
    if st is None or st.thread is None or not st.thread.is_alive():
        with _STATE_LOCK:
            st = _STATE
            if st is None or st.thread is None or not st.thread.is_alive():
                st = _STATE if st is not None else _BeatState()
                st.stopping = False
                st.thread = threading.Thread(
                    target=_beat_loop, args=(st,),
                    name="ctt-heartbeat", daemon=True,
                )
                _STATE = st
                st.thread.start()
                if not _ATEXIT_REGISTERED:
                    atexit.register(stop)
                    _ATEXIT_REGISTERED = True
    if role is not None or job_id is not None:
        with st.lock:
            if role is not None:
                st.role = role
            if job_id is not None:
                st.job_id = int(job_id)
    return True


def running() -> bool:
    st = _STATE
    return st is not None and st.thread is not None and st.thread.is_alive()


def beat(exiting: bool = False) -> None:
    """Write one heartbeat now (final beats, tests).  No-op when disabled
    or never started."""
    st = _STATE
    if st is None or not trace.enabled():
        return
    _write_beat(st, exiting=exiting)


def stop(final: bool = True) -> None:
    """Stop the beat thread; with ``final``, stamp one last ``exiting``
    beat so readers can tell clean exit from death."""
    global _STATE
    st = _STATE
    if st is None:
        return
    st.stopping = True
    st.wake.set()
    thread = st.thread
    if thread is not None and thread.is_alive():
        if thread is not threading.current_thread():
            thread.join(timeout=2.0)
    st.thread = None
    if final and trace.enabled():
        _write_beat(st, exiting=True)


# ---------------------------------------------------------------------------
# progress hooks (called from runtime/{task,executor}.py hot-ish paths)


def _state_if_enabled() -> Optional[_BeatState]:
    if not trace.enabled():
        return None
    return _STATE


def set_role(role: str, job_id: Optional[int] = None) -> None:
    st = _state_if_enabled()
    if st is None:
        return
    with st.lock:
        st.role = role
        if job_id is not None:
            st.job_id = int(job_id)


def note_task(identifier: str, total: int,
              grid: Optional[Any] = None) -> None:
    """A new dispatch round: reset the per-task share counters.  ``total``
    is THIS process's block share (multi-host peers each report theirs)."""
    st = _state_if_enabled()
    if st is None:
        return
    with st.lock:
        if st.task != identifier:
            st.blocks_done = 0
            st.blocks_failed = 0
            st.blocks_retried = 0
        st.task = identifier
        st.blocks_total = int(total)
        if grid is not None:
            st.grid = [int(g) for g in grid]


def note_blocks_done(n: int = 1) -> None:
    st = _state_if_enabled()
    if st is None:
        return
    with st.lock:
        st.blocks_done += int(n)


def note_blocks_failed(n: int = 1) -> None:
    st = _state_if_enabled()
    if st is None:
        return
    with st.lock:
        st.blocks_failed += int(n)


def note_blocks_retried(n: int = 1) -> None:
    st = _state_if_enabled()
    if st is None:
        return
    with st.lock:
        st.blocks_retried += int(n)


def note_queue_depth(n: int) -> None:
    """ctt-steal: unclaimed work-queue items at this worker's last pull
    scan — `obs watch` shows how much stealable work remains."""
    st = _state_if_enabled()
    if st is None:
        return
    with st.lock:
        st.queue_depth = int(n)


def note_draining() -> None:
    """ctt-serve: the daemon entered its SIGTERM drain — readers (`obs
    watch`, /metrics scrapes) distinguish 'alive, finishing, refusing
    submissions' from both healthy and dead."""
    st = _state_if_enabled()
    if st is None:
        return
    with st.lock:
        st.draining = True


def note_block_start(block_id: int) -> None:
    st = _state_if_enabled()
    if st is None:
        return
    with st.lock:
        st.current[int(block_id)] = trace.monotonic()


def note_block_end(block_id: int) -> None:
    st = _state_if_enabled()
    if st is None:
        return
    with st.lock:
        st.current.pop(int(block_id), None)


# ---------------------------------------------------------------------------
# preemption: flush telemetry before the scheduler's SIGTERM kills us


def install_sigterm_flush() -> bool:
    """Install a SIGTERM handler that flushes metrics + trace shards and
    writes a final ``exiting`` heartbeat before re-raising (chaining any
    previously installed handler).  The common scheduler preemption path
    sends SIGTERM with a grace window — without this, the process's
    metrics snapshot and buffered shard tail die with it.

    Returns False (and installs nothing) off the main thread, where the
    signal module refuses handlers."""
    if threading.current_thread() is not threading.main_thread():
        return False
    prev = signal.getsignal(signal.SIGTERM)

    def _handler(signum, frame):
        try:
            beat(exiting=True)
            stop(final=False)
            trace.flush()  # flushes the metrics snapshot too
        finally:
            if callable(prev):
                prev(signum, frame)
            else:
                # restore default disposition and re-raise so the exit
                # status still says "killed by SIGTERM"
                signal.signal(signal.SIGTERM, signal.SIG_DFL)
                os.kill(os.getpid(), signal.SIGTERM)

    signal.signal(signal.SIGTERM, _handler)
    return True
