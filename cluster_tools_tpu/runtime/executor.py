"""Execution backends — the ``target=`` seam.

The reference fans per-block work out as independent scheduler processes
(Slurm ``sbatch`` / LSF ``bsub`` / local ProcessPool — reference
cluster_tasks.py:388-624).  On TPU the unit of dispatch is a *device program*, not a
process, so the backends here are:

  * ``local`` — host loop (optionally a thread pool for IO overlap); runs the same
    kernels on whatever the default jax backend is.  This is the parity oracle.
  * ``tpu``   — prefers a task's ``process_block_batch``: blocks are grouped into
    fixed-size batches (static shapes for XLA), padded, and executed as one jit
    dispatch, vmapped over the batch and — when several devices are visible —
    sharded over a ``jax.sharding.Mesh`` by the task's kernels.  Tasks that
    additionally implement the split ``read_batch`` / ``compute_batch`` /
    ``write_batch`` protocol run under an explicit three-stage pipeline
    (read pool → serialized compute → write pool, bounded to
    ``pipeline_depth`` batches per stage), so chunk reads of batch i+1 and
    chunk writes of batch i−1 both hide behind batch i's device program.

Both report per-block success/failure so the task layer can retry exactly the
failed blocks.

The split protocol has a cross-TASK generalization in ``runtime/stream.py``
(ctt-stream): a workflow-declared FusedChain runs several split-protocol
tasks as one streaming pass — one read per slab, all compute stages on
device, elided intermediates never reach the store.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
import traceback
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from concurrent.futures import TimeoutError as FutureTimeout
from typing import Any, Dict, List, Sequence, Tuple

from .. import faults
from ..obs import heartbeat as obs_heartbeat
from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace
from ..utils.blocking import Blocking

RunResult = Tuple[List[int], List[int], Dict[int, str]]  # done, failed, errors


def block_deadline_s(config: Dict[str, Any]) -> float:
    """Per-block soft deadline in seconds (0 = watchdog off): the
    ``block_deadline_s`` config key, else ``CTT_BLOCK_DEADLINE_S``;
    malformed values degrade to off like every other CTT_* switch.

    "Soft" because Python cannot kill a thread: a block that exceeds the
    deadline is *recorded failed* (``executor.blocks_timed_out``) and fed
    to the task retry loop, while the hung call is left to finish in the
    background — idempotent blocks make the possible late completion
    harmless (the same contract block retry already relies on)."""
    raw = config.get("block_deadline_s")
    if raw is None:
        raw = os.environ.get("CTT_BLOCK_DEADLINE_S")
    try:
        deadline = float(raw) if raw is not None else 0.0
    except (TypeError, ValueError):
        deadline = 0.0
    return max(deadline, 0.0)


def _record(task, label: str, n_blocks: int, seconds: float) -> None:
    rec = getattr(task, "record_timing", None)
    if rec is not None:
        rec(label, n_blocks, seconds)


def stacked_dispatch(task, compute_fn, payload, blocking, config,
                     all_ids: List[int], fused: bool):
    """ONE guarded device dispatch over a (possibly stacked) payload —
    the compute core of the staged pipeline's dispatch group, shared
    with the ctt-microbatch job-batch runner (serve/microbatch.py),
    which lifts the same ``stack_payloads``/``unstack_results`` contract
    from block batches to whole jobs.  Same fault site
    (``executor.stage_compute``), same span shape, same dispatch
    counters — obs watch and the chip-mode accounting see a job-stacked
    dispatch exactly like an hbm-stacked one.  The hbm use_guard pins
    evicted-entry deletes past the dispatch (a concurrent serve job's
    eviction must not free buffers an in-flight program still reads)."""
    from . import hbm

    faults.check("executor.stage_compute", id=all_ids[0])
    with obs_trace.span(
        "stage_compute", kind="host_compute", task=task.identifier,
        blocks=len(all_ids), block_ids=list(all_ids),
    ), hbm.use_guard():
        result = compute_fn(payload, blocking, config)
    obs_metrics.inc("device.dispatches")
    if fused:
        obs_metrics.inc("device.fused_blocks", len(all_ids))
    return result


def run_split_batch(task, block_ids, blocking, config) -> None:
    """One batch of a split-protocol task through its three stages in turn
    on the calling thread — the monolithic ``process_block_batch`` of the
    tasks that implement ``read_batch`` / ``compute_batch`` /
    ``write_batch``.  Each stage runs in a child span of the caller's
    (``block_batch`` or ``block:<task>``), so a trace tells the batch's
    host IO from its device compute."""
    with obs_trace.span("batch_read", kind="host_io", task=task.identifier):
        payload = task.read_batch(block_ids, blocking, config)
    with obs_trace.span("batch_compute", kind="host_compute",
                        task=task.identifier):
        result = task.compute_batch(payload, blocking, config)
    with obs_trace.span("batch_write", kind="host_io", task=task.identifier):
        task.write_batch(result, blocking, config)


def profiler_trace(config: Dict[str, Any]):
    """jax profiler context when the ``profile_dir`` config knob is set:
    dispatches inside are captured as a TensorBoard/XPlane trace
    (SURVEY.md §5 — the reference has log-derived timing only; device traces
    are the strictly-additive TPU upgrade)."""
    profile_dir = config.get("profile_dir")
    if not profile_dir:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.trace(profile_dir)


# blocks per device dispatch when neither the config nor a pin sets it.
# On a TPU v5e the components program at block (50, 512, 512) took 7.6 s
# for one block and had not returned after more than 300 s for a batch of
# 8; why a batch is so much slower than its blocks one at a time is not
# known yet.  On one core of XLA-CPU two singles beat a pair ~2x.
# tests/test_tpu_compile.py compiles the watershed program at this batch
# and at 8 against one v5e's HBM.
DEVICE_BATCH = 1


def resolve_batch_size(config: Dict[str, Any]) -> int:
    """Blocks per device dispatch: the ``device_batch_size`` config knob,
    else the measured pin (CTT_DEVICE_BATCH / chip pin file), else
    ``DEVICE_BATCH`` — times the visible device count.  Shared by the
    TpuExecutor and the fused-chain runner (ctt-stream) so a fused and an
    unfused run chunk the block list identically."""
    bs_conf = config.get("device_batch_size")
    if bs_conf is None:
        # measured pin (env var, else the backend-tagged pin file —
        # tools/chip_session.py writes CTT_DEVICE_BATCH), else the
        # default; malformed pins degrade to the default
        # like every other CTT_* switch
        from ..ops import _backend

        pin = _backend.pinned_value("CTT_DEVICE_BATCH")
        try:
            bs_conf = int(pin)
        except (TypeError, ValueError):
            bs_conf = DEVICE_BATCH
    batch_size = max(int(bs_conf), 1)
    devices = config.get("devices")
    if devices and devices != "global":
        n_dev = len(devices)
    else:
        # resolved once per process via the execution context (ctt-serve):
        # a long-lived daemon dispatches thousands of batches and must not
        # re-query the backend for a constant on each one
        from .workflow import ExecutionContext

        n_dev = ExecutionContext.process_context().local_device_count()
    return batch_size * n_dev


class BaseExecutor:
    name = "base"

    def __init__(self, config: Dict[str, Any]):
        self.config = config
        # ctt-watch: every executing process (driver dispatch loop, or the
        # LocalExecutor inside a scheduler worker) heartbeats while it
        # owns blocks; one global check + no thread when tracing is off
        obs_heartbeat.ensure_started()

    def run_blocks(
        self, task, blocking: Blocking, block_ids: Sequence[int], config: Dict[str, Any]
    ) -> RunResult:  # pragma: no cover - abstract
        raise NotImplementedError


class LocalExecutor(BaseExecutor):
    """Host loop / thread pool over ``process_block``."""

    name = "local"

    def run_blocks(self, task, blocking, block_ids, config) -> RunResult:
        n_workers = max(int(config.get("max_jobs", 1)), 1)
        if not getattr(task, "pipeline_safe", True):
            # same contract as the TpuExecutor pipeline: blocks that read
            # regions concurrent blocks write (two-pass pass 2) run serially
            # so the visible neighbor labels are not timing-dependent
            n_workers = 1
        done: List[int] = []
        failed: List[int] = []
        errors: Dict[int, str] = {}

        durations: List[float] = []

        def _one(bid: int):
            obs_heartbeat.note_block_start(bid)
            try:
                faults.check("executor.block", id=bid)
                t0 = time.perf_counter()
                # explicit task= attribute: under a thread pool the span
                # opens in a worker thread where the per-thread parent
                # stack cannot see the enclosing task span
                with obs_trace.span(
                    f"block:{task.identifier}", kind="host",
                    task=task.identifier, block=bid,
                ):
                    task.process_block(bid, blocking, config)
                durations.append(time.perf_counter() - t0)
                obs_heartbeat.note_blocks_done()
                return bid, None
            except Exception:
                obs_heartbeat.note_blocks_failed()
                return bid, traceback.format_exc()
            finally:
                obs_heartbeat.note_block_end(bid)

        deadline = block_deadline_s(config)
        with profiler_trace(config):
            if deadline > 0:
                results = self._run_with_watchdog(
                    _one, block_ids, n_workers, deadline
                )
            elif n_workers == 1:
                results = [_one(b) for b in block_ids]
            else:
                with ThreadPoolExecutor(n_workers) as pool:
                    results = list(pool.map(_one, block_ids))
        if durations:
            # one aggregate record per dispatch round: a per-block record
            # would make the status JSON O(n_blocks) at production scale
            _record(task, "blocks_total", len(durations), sum(durations))
            _record(task, "block_max", 1, max(durations))
        for bid, err in results:
            if err is None:
                done.append(bid)
            else:
                failed.append(bid)
                errors[bid] = err
        return done, failed, errors

    @staticmethod
    def _run_with_watchdog(fn, block_ids, n_workers: int, deadline: float):
        """Run ``fn(bid) -> (bid, err)`` per block under the soft-deadline
        watchdog: a block that doesn't resolve within ``deadline`` seconds
        is converted into a failed block (the task retry loop re-runs it)
        instead of hanging the dispatch.  Always pool-based (even at one
        worker) so the waiter can abandon a hung call; the pool is shut
        down without joining — hung threads are left to finish in the
        background (see :func:`block_deadline_s`)."""
        pool = ThreadPoolExecutor(
            max(n_workers, 1), thread_name_prefix="ctt-watchdog"
        )
        results = []
        try:
            futures = [(bid, pool.submit(fn, bid)) for bid in block_ids]
            for bid, fut in futures:
                try:
                    results.append(fut.result(timeout=deadline))
                except FutureTimeout:
                    # not-yet-started blocks behind a hung worker cancel
                    # cleanly; running ones are abandoned to the background
                    fut.cancel()
                    obs_metrics.inc("executor.blocks_timed_out")
                    results.append((
                        bid,
                        f"block {bid} exceeded the soft deadline "
                        f"({deadline:.1f}s) — recorded failed for retry; "
                        "the hung call is left to finish in the background",
                    ))
                except Exception:
                    # fn reports its own errors; this only guards cancelled
                    # futures racing the result() call
                    results.append((bid, traceback.format_exc()))
        finally:
            pool.shutdown(wait=False)
        return results


class TpuExecutor(BaseExecutor):
    """Batched device dispatch: group blocks, let the task jit over the batch."""

    name = "tpu"

    def run_blocks(self, task, blocking, block_ids, config) -> RunResult:
        batch_fn = getattr(task, "process_block_batch", None)
        if batch_fn is None:
            return LocalExecutor(self.config).run_blocks(
                task, blocking, block_ids, config
            )

        batch_size = resolve_batch_size(config)

        done: List[int] = []
        failed: List[int] = []
        errors: Dict[int, str] = {}
        ids = list(block_ids)
        trace = profiler_trace(config)
        with trace:
            self._run_batches(
                task, blocking, config, ids, batch_size, batch_fn,
                done, failed, errors,
            )
        return done, failed, errors

    @staticmethod
    def _staged_fns(task):
        """The split batch protocol: a task that implements all of
        ``read_batch`` / ``compute_batch`` / ``write_batch`` opts into the
        three-stage pipeline; ``process_block_batch`` stays the monolithic
        composition (used at depth 1 and by the per-block fallback)."""
        fns = tuple(
            getattr(task, name, None)
            for name in ("read_batch", "compute_batch", "write_batch")
        )
        return fns if all(fns) else None

    def _per_block_fallback(
        self, task, blocking, config, chunk, done, failed, errors, tb
    ) -> None:
        """Re-run a failed batch block by block so a single poisoned block
        doesn't fail the whole batch."""
        from . import hbm

        for bid in chunk:
            try:
                with obs_trace.span(
                    "block_fallback", kind="host",
                    task=task.identifier, block=bid,
                ), hbm.use_guard():
                    task.process_block(bid, blocking, config)
                done.append(bid)
                obs_heartbeat.note_blocks_done()
            except Exception:
                failed.append(bid)
                errors[bid] = traceback.format_exc()
                obs_heartbeat.note_blocks_failed()
        if not any(b in errors for b in chunk):
            # batch path is broken but every block succeeded per-block;
            # surface why without mislabeling a done block as failed
            print(
                f"[{self.name}] batch dispatch failed, per-block fallback "
                f"succeeded for blocks {chunk[0]}..{chunk[-1]}:\n{tb}"
            )

    def _run_batches(
        self, task, blocking, config, ids, batch_size, batch_fn,
        done, failed, errors,
    ) -> None:
        from ..parallel.dispatch import form_batches
        from . import hbm

        chunks = form_batches(ids, batch_size)

        # ctt-hbm aggregated dispatch for MONOLITHIC tasks: the staged
        # pipeline fuses hbm_stack read payloads into one device program
        # (_run_staged), but a task exposing only process_block_batch (the
        # inference path) used to be stuck at batch_size blocks/dispatch.
        # Its batch fn stacks whatever id list it is handed, so handing it
        # hbm_stack consecutive chunks IS the aggregated dispatch — same
        # blocks, same order, fewer, larger programs.  The per-block
        # fallback grain is unchanged (a failed fused batch degrades block
        # by block, exactly like an unfused one).
        stack_n = hbm.hbm_stack(config)
        if self._staged_fns(task) is None and stack_n > 1 and len(chunks) > 1:
            chunks = [
                [bid for chunk in chunks[i: i + stack_n] for bid in chunk]
                for i in range(0, len(chunks), stack_n)
            ]

        batch_seconds: List[float] = []  # list.append: safe from pool threads

        def _one_batch(chunk):
            # the batch's first block stands in for the whole batch in the
            # heartbeat's in-flight list (straggler age tracking)
            obs_heartbeat.note_block_start(chunk[0])
            try:
                faults.check("executor.batch", id=chunk[0])
                t0 = time.perf_counter()
                # block_ids lets the live reader attribute the batch wall
                # to each block (the spatial latency heatmap)
                # the guard pins evicted-entry deletes past this dispatch
                # (a concurrent serve job's eviction must not free buffers
                # an in-flight batch still reads — runtime/hbm.py)
                with obs_trace.span(
                    "block_batch", kind="host", task=task.identifier,
                    blocks=len(chunk), block_ids=list(chunk),
                ), hbm.use_guard():
                    batch_fn(chunk, blocking, config)
                obs_metrics.inc("device.dispatches")
                if len(chunk) > batch_size:
                    # blocks that rode a fused (aggregated) dispatch —
                    # the hbm_stack economics, monolithic-path edition
                    obs_metrics.inc("device.fused_blocks", len(chunk))
                dt = time.perf_counter() - t0
                batch_seconds.append(dt)
                _record(
                    task,
                    f"batch_{chunk[0]}_{chunk[-1]}",
                    len(chunk),
                    dt,
                )
                done.extend(chunk)
                obs_heartbeat.note_blocks_done(len(chunk))
            except Exception:
                self._per_block_fallback(
                    task, blocking, config, chunk, done, failed, errors,
                    traceback.format_exc(),
                )
            finally:
                obs_heartbeat.note_block_end(chunk[0])

        # Batch pipelining (the reference's dask IO/compute overlap,
        # inference.py:319-327, moved into the executor).  A task whose
        # blocks read regions other blocks of the SAME dispatch write (e.g.
        # two-pass pass 2: the halo'd read overlaps a same-color *diagonal*
        # neighbor's inner box) declares ``pipeline_safe = False`` — chunk
        # writes are atomic (os.replace), so concurrency would not tear
        # data, but it would make which neighbor labels a batch sees
        # timing-dependent; depth 1 (the strictly serial loop) keeps the
        # output deterministic.
        #
        # Two pipelined forms, best first:
        #   * tasks implementing the split protocol (``_staged_fns``) run a
        #     true three-stage pipeline: a read pool prefetches batch i+1's
        #     chunks, the dispatching thread runs every device program IN
        #     ORDER (deterministic dispatch), and a write pool drains batch
        #     i−1's chunk encodes — reads AND writes both overlap compute;
        #   * monolithic ``process_block_batch`` tasks keep the depth-d
        #     thread pool (whole batches overlap).
        depth = max(int(config.get("pipeline_depth", 2)), 1)
        if not getattr(task, "pipeline_safe", True):
            depth = 1
        staged = self._staged_fns(task)
        t_wall0 = time.perf_counter()
        if depth == 1 or len(chunks) == 1:
            for chunk in chunks:
                _one_batch(chunk)
        elif staged is not None:
            self._run_staged(
                task, blocking, config, chunks, depth, staged,
                done, failed, errors, batch_seconds,
            )
        else:
            with ThreadPoolExecutor(depth) as pool:
                list(pool.map(_one_batch, chunks))
        # pipeline overlap efficiency: with depth > 1, summed in-flight
        # batch seconds exceeding the dispatch wall is exactly the host-IO
        # time hidden behind device execution
        obs_metrics.inc("executor.batches", len(chunks))
        obs_metrics.inc("executor.batch_s", sum(batch_seconds))
        obs_metrics.inc(
            "executor.dispatch_wall_s", time.perf_counter() - t_wall0
        )

    def _run_staged(
        self, task, blocking, config, chunks, depth, staged,
        done, failed, errors, batch_seconds,
    ) -> None:
        """Three-stage pipeline: read → device compute → write over bounded
        in-flight deques (the explicit-stage successor of the depth-N
        read→compute→write pool).

        Up to ``depth`` reads and ``depth`` writes ride small thread pools
        while the calling thread is the ONE compute stage, consuming read
        results in submission order — so the device sees the exact dispatch
        sequence of the serial loop while batch i+1's chunk decodes and
        batch i−1's chunk encodes both happen under batch i's program (XLA
        releases the GIL during execution).  A stage failure for a batch
        degrades that batch to the per-block fallback; other batches are
        unaffected.

        Async prefetch (ctt-cloud): tasks exposing ``prefetch_batch``
        additionally get a lookahead stage that warms the decoded-chunk
        LRU up to ``depth`` batches BEYOND the read stage's own window —
        chunk fetches overlap as many concurrent range requests as the
        store backend allows instead of one blocking slice per read
        thread, so the read stage of a high-latency object store degrades
        to LRU hits.  Prefetch is advisory (failures surface on the real
        read) and disabled with ``prefetch: false``.

        ctt-hbm adds two device-side levers on the same skeleton:

          * **aggregated dispatch** — with ``hbm_stack: k`` (or
            ``CTT_HBM_STACK``) and a task implementing
            ``stack_payloads``/``unstack_results``, up to ``k``
            consecutive read payloads concatenate into ONE ``(sum_B,
            ...)`` stacked device dispatch (the coarse-CC ``(n_tiles,
            ...)`` shape generalized); results split back per batch for
            the write pool, so host IO granularity is unchanged while
            dispatch count drops k-fold.  Kernels are vmapped over the
            leading axis — the stacked dispatch is byte-identical to the
            per-batch (and per-block) path, which remains the fallback.
          * **double-buffered device prefetch** — tasks exposing
            ``upload_batch`` get a transfer stage between read and
            compute: while batch k's device program runs, batch k+1's
            host arrays are already crossing to HBM on a transfer
            thread, bounded to ``runtime.hbm.UPLOAD_SLOTS`` (2) in-flight
            uploads (the same process-wide gate interleaves two serve
            jobs' uploads at ``concurrency > 1``).  Disabled together
            with the prefetch lookahead by ``prefetch: false``."""
        read_fn, compute_fn, write_fn = staged
        from . import hbm

        stage_s = {"read": 0.0, "compute": 0.0, "write": 0.0,
                   "prefetch": 0.0, "upload": 0.0}
        acc_lock = threading.Lock()

        stack_n = 1
        stack_fn = getattr(task, "stack_payloads", None)
        unstack_fn = getattr(task, "unstack_results", None)
        if stack_fn is not None and unstack_fn is not None:
            stack_n = hbm.hbm_stack(config)
        upload_fn = getattr(task, "upload_batch", None)
        # ``prefetch: false`` opts out of ALL lookahead (the acceptance
        # switch restoring pre-hbm execution together with
        # CTT_HBM_CACHE_MB=0); ``hbm_prefetch: false`` disables only the
        # device transfer stage, leaving the ctt-cloud LRU prefetch alone
        # (the honest A/B baseline for the hbm bench)
        if not config.get("prefetch", True) or not config.get(
            "hbm_prefetch", True
        ):
            upload_fn = None

        def _acc(stage: str, dt: float) -> None:
            with acc_lock:
                stage_s[stage] += dt

        def _read(chunk):
            obs_heartbeat.note_block_start(chunk[0])
            faults.check("executor.stage_read", id=chunk[0])
            t0 = time.perf_counter()
            with obs_trace.span(
                "stage_read", kind="host_io", task=task.identifier,
                blocks=len(chunk), block_ids=list(chunk),
            ):
                payload = read_fn(chunk, blocking, config)
            _acc("read", time.perf_counter() - t0)
            return payload

        def _write(chunk, result):
            faults.check("executor.stage_write", id=chunk[0])
            t0 = time.perf_counter()
            with obs_trace.span(
                "stage_write", kind="host_io", task=task.identifier,
                blocks=len(chunk), block_ids=list(chunk),
            ):
                write_fn(result, blocking, config)
            _acc("write", time.perf_counter() - t0)

        prefetch_fn = getattr(task, "prefetch_batch", None)
        if not config.get("prefetch", True):
            prefetch_fn = None

        def _prefetch(chunk):
            t0 = time.perf_counter()
            try:
                with obs_trace.span(
                    "stage_prefetch", kind="host_io", task=task.identifier,
                    blocks=len(chunk), block_ids=list(chunk),
                ):
                    prefetch_fn(chunk, blocking, config)
                obs_metrics.inc("executor.prefetch_batches")
            except Exception:  # ctt: noqa[CTT009] prefetch is advisory — the read stage re-raises and classifies any real failure
                pass
            _acc("prefetch", time.perf_counter() - t0)

        n_blocks = sum(len(c) for c in chunks)
        reads: deque = deque()    # (chunk, Future[payload])
        uploads: deque = deque()  # (group, counts, Future[payload])
        writes: deque = deque()   # (chunk, Future[None], t_batch0)
        with ThreadPoolExecutor(
            depth, thread_name_prefix="ctt-read"
        ) as read_pool, ThreadPoolExecutor(
            depth, thread_name_prefix="ctt-write"
        ) as write_pool, ThreadPoolExecutor(
            depth, thread_name_prefix="ctt-prefetch-stage"
        ) as prefetch_pool, ThreadPoolExecutor(
            1, thread_name_prefix="ctt-hbm-upload"
        ) as upload_pool:
            # lookahead frontier: the first ``depth`` chunks go straight
            # to the read pool (prefetching them would double-fetch), so
            # the prefetch stage starts ``depth`` ahead and stays ``depth``
            # beyond the read window throughout
            next_prefetch = depth

            def _advance_prefetch(upto: int) -> None:
                nonlocal next_prefetch
                if prefetch_fn is None:
                    return
                while next_prefetch < min(upto, len(chunks)):
                    prefetch_pool.submit(_prefetch, chunks[next_prefetch])
                    next_prefetch += 1

            def _drain_write():
                chunk, fut, t_batch0 = writes.popleft()
                try:
                    fut.result()
                except Exception:
                    self._per_block_fallback(
                        task, blocking, config, chunk, done, failed,
                        errors, traceback.format_exc(),
                    )
                    obs_heartbeat.note_block_end(chunk[0])
                    return
                batch_seconds.append(time.perf_counter() - t_batch0)
                done.extend(chunk)
                obs_heartbeat.note_blocks_done(len(chunk))
                obs_heartbeat.note_block_end(chunk[0])

            def _fallback_group(group):
                # called from an except block: every batch of the failed
                # dispatch group degrades to the per-block path
                for chunk in group:
                    self._per_block_fallback(
                        task, blocking, config, chunk, done, failed,
                        errors, traceback.format_exc(),
                    )
                    obs_heartbeat.note_block_end(chunk[0])

            def _upload(payload):
                # transfer thread (ctt-hbm): batch k+1 crosses to HBM
                # while batch k's device program runs
                t0 = time.perf_counter()
                out = upload_fn(payload, blocking, config)
                _acc("upload", time.perf_counter() - t0)
                return out

            def _compute_group(group, counts, payload):
                all_ids = [b for c in group for b in c]
                t_batch0 = time.perf_counter()
                try:
                    t0 = time.perf_counter()
                    result = stacked_dispatch(
                        task, compute_fn, payload, blocking, config,
                        all_ids, fused=len(group) > 1,
                    )
                    dt = time.perf_counter() - t0
                    _acc("compute", dt)
                    _record(task, f"batch_{all_ids[0]}_{all_ids[-1]}",
                            len(all_ids), dt)
                    results = (
                        unstack_fn(result, counts, blocking, config)
                        if len(group) > 1 else [result]
                    )
                except Exception:
                    _fallback_group(group)
                    return
                for chunk, res in zip(group, results):
                    writes.append(
                        (chunk, write_pool.submit(_write, chunk, res),
                         t_batch0)
                    )
                while len(writes) > depth:
                    _drain_write()

            def _drain_upload():
                group, counts, fut = uploads.popleft()
                try:
                    payload = fut.result()
                except Exception:
                    _fallback_group(group)
                    return
                _compute_group(group, counts, payload)

            def _consume():
                """Form one dispatch group (up to ``stack_n`` read
                payloads, stacked) and move it down the pipeline — the
                upload stage when armed, else straight to compute.  The
                deques are FIFO throughout, so the device sees the exact
                dispatch sequence of the serial loop."""
                group, payloads = [], []
                while reads and len(group) < stack_n:
                    chunk, fut = reads.popleft()
                    try:
                        payloads.append(fut.result())
                        group.append(chunk)
                    except Exception:
                        self._per_block_fallback(
                            task, blocking, config, chunk, done, failed,
                            errors, traceback.format_exc(),
                        )
                        obs_heartbeat.note_block_end(chunk[0])
                if not group:
                    return
                counts = [len(c) for c in group]
                try:
                    payload = (
                        stack_fn(payloads, blocking, config)
                        if len(group) > 1 else payloads[0]
                    )
                except Exception:
                    _fallback_group(group)
                    return
                if upload_fn is None:
                    _compute_group(group, counts, payload)
                    return
                uploads.append(
                    (group, counts, upload_pool.submit(_upload, payload))
                )
                while len(uploads) >= hbm.UPLOAD_SLOTS:
                    _drain_upload()

            t_wall0 = time.perf_counter()
            for i, chunk in enumerate(chunks):
                _advance_prefetch(i + 1 + depth)
                reads.append((chunk, read_pool.submit(_read, chunk)))
                while len(reads) >= max(depth, stack_n):
                    _consume()
            while reads:
                _consume()
            while uploads:
                _drain_upload()
            while writes:
                _drain_write()
        wall = time.perf_counter() - t_wall0

        # one aggregate record per stage per dispatch round (per-batch
        # stage records would make the status JSON O(n_batches) × 3); the
        # per-batch compute walls above keep the task_breakdown contract
        _record(task, "stage_read_total", n_blocks, stage_s["read"])
        _record(task, "stage_compute_total", n_blocks, stage_s["compute"])
        _record(task, "stage_write_total", n_blocks, stage_s["write"])
        obs_metrics.inc("executor.stage_batches", len(chunks))
        obs_metrics.inc("executor.stage_read_s", stage_s["read"])
        obs_metrics.inc("executor.stage_compute_s", stage_s["compute"])
        obs_metrics.inc("executor.stage_write_s", stage_s["write"])
        obs_metrics.inc("executor.stage_prefetch_s", stage_s["prefetch"])
        obs_metrics.inc("executor.stage_upload_s", stage_s["upload"])
        # IO seconds the pipeline hid behind (serialized) compute: summed
        # read+write stage time minus the wall the compute stage left open
        obs_metrics.inc(
            "executor.stage_hidden_io_s",
            max(
                0.0,
                stage_s["read"] + stage_s["write"]
                - max(0.0, wall - stage_s["compute"]),
            ),
        )



_EXECUTORS = {
    "local": LocalExecutor,
    "tpu": TpuExecutor,
}


def get_executor(target: str, config: Dict[str, Any]) -> BaseExecutor:
    if target not in _EXECUTORS:
        # the batch-scheduler backends register on import
        from . import cluster_executor  # noqa: F401
    try:
        return _EXECUTORS[target](config)
    except KeyError:
        raise ValueError(
            f"unknown target {target!r}; available: {sorted(_EXECUTORS)}"
        ) from None


def register_executor(name: str, cls) -> None:
    """Seam for additional backends (the reference's slurm/lsf equivalents)."""
    _EXECUTORS[name] = cls
