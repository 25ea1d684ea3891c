"""ctt-stream: cross-task fused streaming execution.

The reference (and PRs 1-6 of this port) runs workflows task-at-a-time:
threshold → CC → watershed each materialize a full intermediate volume to
the store and re-read it, so the same voxels cross the host/store boundary
4-5× per pipeline — the file-target model luigi imposes.  This module
generalizes the split-protocol executor (PR 3's ``read_batch`` /
``compute_batch`` / ``write_batch`` three-stage pipeline) from *intra-task*
pipelining to *cross-task* fusion: a :class:`FusedChain` declared by a
workflow executes as ONE streaming pass over the volume —

  * each z-slab block batch is read from the store once (at the chain's
    maximum halo; downstream members' smaller reads are crops of the same
    host buffer — the "halo reconciliation" between stages);
  * the batch flows through every member's ``compute_batch`` in declared
    order; a member consuming an in-chain product receives the upstream
    member's *device handoff* directly (``fused_read_batch``), so an elided
    intermediate never leaves HBM, let alone reaches the store;
  * only non-elided members' outputs are written back, plus small carried
    merge state (per-slab uniques / max ids, face-edge equivalence tables,
    histograms — the ``fusion_carry_*`` protocol) that replaces the
    downstream re-reads of scratch volumes.

Fallback contract: a chain that is not eligible (member opted out or
partially complete, ``stream_fusion`` disabled, multi-host topology, ROI
restriction, missing contracts) silently degrades to task-at-a-time
execution — declaring a chain never changes *what* is computed, only how
many times the voxels cross the store boundary.  Output is byte-identical
to the unfused pipeline by construction: members run their own unchanged
read/compute/write code against the same bytes.

Shape citations: arXiv:1711.00975 (one incremental pass, bounded memory,
small carried state) and arXiv:2210.06438 (fusing fine-grained stages into
resident device work); the fused ``ShardedWsProblemTask`` proved the
device-resident two-stage pattern this generalizes.

ctt-hbm: member uploads route through the warm device-buffer cache
(``runtime/hbm.py``) inside their own compute helpers — a back-to-back
fused serve job on the same volume skips the head member's store upload
— and each member dispatch is accounted under ``device.dispatches``.
"""

from __future__ import annotations

import os
import threading
import time
import traceback
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import faults
from ..obs import heartbeat as obs_heartbeat
from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace
from ..parallel.dispatch import BlockReadCache, use_read_cache
from ..utils.blocking import Blocking
from . import config as cfg
from .executor import resolve_batch_size


@dataclass
class FusedChain:
    """A declared fusible chain of split-protocol block tasks.

    ``members`` run as one streaming pass in declared order (producers
    before consumers).  ``elide`` names member identifiers whose volume
    output is never materialized (their ``write_batch``/``prepare`` are
    skipped; in-chain consumers take the device handoff instead) — the
    lint rule CTT011 statically verifies no out-of-chain task consumes an
    elided intermediate.  ``covers`` lists downstream tasks whose outputs
    the chain produces from carried state at finalize (e.g. the
    merge-offsets npz and block-face equivalence chunks) — they are
    stamped complete without running.
    """

    name: str
    members: List[Any]
    elide: frozenset = frozenset()
    covers: List[Any] = field(default_factory=list)

    def __post_init__(self) -> None:
        self.elide = frozenset(self.elide)
        ids = [m.identifier for m in self.members]
        if len(set(ids)) != len(ids):
            raise ValueError(
                f"fused chain {self.name!r}: duplicate member identifiers {ids}"
            )
        unknown = self.elide - set(ids)
        if unknown:
            raise ValueError(
                f"fused chain {self.name!r}: elide names non-members {sorted(unknown)}"
            )


class ChainFallback(RuntimeError):
    """Raised during planning when a declared chain cannot run fused; the
    caller degrades to task-at-a-time execution (never an error)."""


def fusion_enabled(gconf: Dict[str, Any]) -> bool:
    """The opt-out switches: ``stream_fusion`` in the global config (default
    on) and the ``CTT_STREAM_FUSION`` environment (``0``/``false``/``off``
    kills fusion process-wide — the chaos/CI escape hatch)."""
    env = os.environ.get("CTT_STREAM_FUSION", "").strip().lower()
    if env in ("0", "false", "off", "no"):
        return False
    return bool(gconf.get("stream_fusion", True))


@dataclass
class _ChainPlan:
    chain: FusedChain
    gconf: Dict[str, Any]
    mconfs: Dict[str, Dict[str, Any]]
    blocking: Blocking
    block_ids: List[int]
    chunks: List[List[int]]
    # external (path, key) -> max halo over members reading it
    prefetch: Dict[Tuple[str, str], Tuple[int, ...]]
    # in-chain (path, key) -> producing member identifier
    produced: Dict[Tuple[str, str], str]
    depth: int
    retries: int


def _member_output_pair(member) -> Optional[Tuple[str, str]]:
    path = getattr(member, "output_path", None)
    key = getattr(member, "output_key", None)
    if path is None or key is None:
        return None
    return (path, key)


def _has_split_protocol(member) -> bool:
    return all(
        callable(getattr(member, name, None))
        for name in ("read_batch", "compute_batch", "write_batch")
    )


def plan_chain(chain: FusedChain) -> _ChainPlan:
    """Validate eligibility and build the execution plan.  Raises
    :class:`ChainFallback` with a human-readable reason otherwise."""
    from .task import BlockTask  # local import to avoid cycle

    members = list(chain.members)
    if not members:
        raise ChainFallback("empty chain")
    head = members[0]
    gconf = head.global_config()
    if not fusion_enabled(gconf):
        raise ChainFallback("stream_fusion disabled")
    _, num = cfg.process_topology(gconf)
    if num > 1:
        raise ChainFallback(
            "multi-host topology (carry state is per-process; the "
            "round-robin block shard would split neighbor faces)"
        )
    if gconf.get("roi_begin") is not None or gconf.get("block_list_path"):
        raise ChainFallback(
            "ROI/block-list restriction (carried face state needs the "
            "full block grid)"
        )

    produced: Dict[Tuple[str, str], str] = {}
    prefetch: Dict[Tuple[str, str], Tuple[int, ...]] = {}
    mconfs: Dict[str, Dict[str, Any]] = {}
    for m in members:
        if not isinstance(m, BlockTask):
            raise ChainFallback(f"{m!r} is not a block task")
        if not getattr(m, "fusable", False) or not _has_split_protocol(m):
            raise ChainFallback(
                f"{m.identifier} is not a fusable split-protocol task"
            )
        if not getattr(m, "pipeline_safe", True):
            raise ChainFallback(
                f"{m.identifier} declares pipeline_safe=False (reads "
                "regions written by concurrent blocks of the same dispatch)"
            )
        mconf = {**gconf, **m.get_task_config()}
        mconfs[m.identifier] = mconf
        inputs = list(m.fusion_inputs(mconf) or [])
        halo = m.fusion_halo(mconf)
        for pair in inputs:
            if pair in produced:
                if type(m).fused_read_batch is BlockTask.fused_read_batch:
                    raise ChainFallback(
                        f"{m.identifier} consumes in-chain product {pair} "
                        "but does not implement fused_read_batch"
                    )
                continue
            have = prefetch.get(pair)
            h = tuple(int(x) for x in (halo or ()))
            if have is None:
                prefetch[pair] = h
            else:
                prefetch[pair] = tuple(
                    max(a, b) for a, b in zip(
                        have + (0,) * (len(h) - len(have)),
                        h + (0,) * (len(have) - len(h)),
                    )
                ) or have
        out_pair = _member_output_pair(m)
        if out_pair is not None:
            produced[out_pair] = m.identifier

    # no member (or covered task) may have prior progress: resumes mix
    # task-at-a-time state with streamed state — fall back and let the
    # per-task retry/resume machinery finish the run
    for t in members + list(chain.covers):
        status = t.output().read()
        if status.get("complete") or status.get("done"):
            raise ChainFallback(
                f"{t.identifier} has prior progress (resumed run)"
            )

    # blocking geometry from the head; every member reading external data
    # must agree (members consuming in-chain products inherit it — their
    # input dataset does not exist yet when the producer is elided)
    shape = tuple(head.get_shape())
    block_shape = head.get_block_shape(gconf)
    blocking = Blocking(shape, block_shape)
    for m in members[1:]:
        ext = [p for p in (m.fusion_inputs(mconfs[m.identifier]) or [])
               if p not in produced or produced[p] == m.identifier]
        consumes_inchain = any(
            p in produced and produced[p] != m.identifier
            for p in (m.fusion_inputs(mconfs[m.identifier]) or [])
        )
        if consumes_inchain and not ext:
            continue
        if not consumes_inchain and tuple(m.get_shape()) != shape:
            raise ChainFallback(
                f"{m.identifier} shape {tuple(m.get_shape())} != head "
                f"shape {shape}"
            )
    block_ids = head.get_block_list(blocking, gconf)
    if list(block_ids) != list(range(blocking.n_blocks)):
        raise ChainFallback("block list is not the full grid")

    # normalize prefetch halos to the blocking rank
    ndim = blocking.ndim
    prefetch = {
        pair: tuple((list(h) + [0] * ndim)[:ndim])
        for pair, h in prefetch.items()
        if pair not in produced
    }

    batch_size = resolve_batch_size(gconf)
    chunks = [
        list(block_ids[i: i + batch_size])
        for i in range(0, len(block_ids), batch_size)
    ]
    depth = max(int(gconf.get("pipeline_depth", 2)), 1)
    retries = max(int(gconf.get("max_num_retries", 0)), 0)
    return _ChainPlan(
        chain=chain, gconf=gconf, mconfs=mconfs, blocking=blocking,
        block_ids=list(block_ids), chunks=chunks, prefetch=prefetch,
        produced=produced, depth=depth, retries=retries,
    )


def try_run_chain(chain: FusedChain) -> bool:
    """Attempt a fused execution of ``chain``.  Returns True when the chain
    ran to completion (members + covered tasks stamped complete); False when
    it declined or failed — the caller then runs task-at-a-time, which is
    always safe: nothing is stamped on failure and all block writes are
    idempotent."""
    try:
        plan = plan_chain(chain)
    except ChainFallback as e:
        obs_metrics.inc("stream.fallbacks")
        print(f"[ctt-stream] chain {chain.name!r}: falling back to "
              f"task-at-a-time ({e})")
        return False
    try:
        _execute(plan)
        return True
    except Exception:
        obs_metrics.inc("stream.fallbacks")
        print(f"[ctt-stream] chain {chain.name!r} failed mid-stream; "
              f"falling back to task-at-a-time (idempotent block writes "
              f"make the partial pass harmless):\n{traceback.format_exc()}")
        return False


# ---------------------------------------------------------------------------
# execution


def _carry_nbytes(member, carry) -> int:
    fn = getattr(member, "fusion_carry_nbytes", None)
    if fn is None or carry is None:
        return 0
    try:
        return int(fn(carry))
    except Exception:
        return 0


class _ChainRunner:
    """One streaming pass: read pool → in-order fused compute → write pool.

    The structural twin of ``TpuExecutor._run_staged`` with the compute
    stage widened to the whole member sequence.  Determinism: the compute
    stage (and the carry updates) run on the calling thread in submission
    order, so device dispatch order and carried state are identical to the
    strictly serial loop; read/write pools only move IO off the critical
    path.  A failed batch is retried whole (read + every member's compute)
    before its carry is applied — carried state never sees a half-computed
    slab, which is what makes mid-slab fault injection recoverable."""

    def __init__(self, plan: _ChainPlan):
        self.plan = plan
        self.members = list(plan.chain.members)
        self.elide = plan.chain.elide
        self.carry: Dict[str, Any] = {}
        self.carry_peak = 0
        self.stage_s = {"read": 0.0, "compute": 0.0, "write": 0.0}
        self._acc_lock = threading.Lock()

    def _acc(self, stage: str, dt: float) -> None:
        with self._acc_lock:
            self.stage_s[stage] += dt

    # -- stages -------------------------------------------------------------

    def _read(self, chunk: List[int]):
        """Read stage for one batch: prefetch every external input's blocks
        at the chain-max halo into a batch-local cache, then run each
        store-reading member's own ``read_batch`` against it — the member's
        unchanged pad/normalize/stack code path runs over crops of the one
        shared read, so byte-identity with the unfused pipeline is
        structural, not re-implemented."""
        plan = self.plan
        obs_heartbeat.note_block_start(chunk[0])
        faults.check("executor.stage_read", id=chunk[0])
        t0 = time.perf_counter()
        cache = BlockReadCache()
        with obs_trace.span(
            "stage_read", kind="host_io", chain=plan.chain.name,
            blocks=len(chunk), block_ids=list(chunk),
        ):
            from ..utils import store as store_mod

            for (path, key), halo in plan.prefetch.items():
                ds = store_mod.file_reader(path, "r")[key]
                cache.prefetch(ds, path, key, plan.blocking, chunk, halo)
            payloads = {}
            with use_read_cache(cache):
                for m in self.members:
                    if self._consumes_inchain(m):
                        continue
                    payloads[m.identifier] = m.read_batch(
                        chunk, plan.blocking, plan.mconfs[m.identifier]
                    )
        self._acc("read", time.perf_counter() - t0)
        return payloads

    def _consumes_inchain(self, member) -> bool:
        pairs = member.fusion_inputs(self.plan.mconfs[member.identifier]) or []
        return any(
            p in self.plan.produced
            and self.plan.produced[p] != member.identifier
            for p in pairs
        )

    def _compute(self, chunk: List[int], payloads) -> Dict[str, Any]:
        """Serialized compute stage: every member's device program for this
        batch, in declared order; handoffs chain members device-side."""
        handoffs: Dict[Tuple[str, str], Any] = {}
        results: Dict[str, Any] = {}
        t0 = time.perf_counter()
        from . import hbm

        with hbm.use_guard():
            self._compute_members(chunk, payloads, handoffs, results)
        self._acc("compute", time.perf_counter() - t0)
        return results

    def _compute_members(self, chunk, payloads, handoffs, results) -> None:
        """Member loop of :meth:`_compute`, inside the hbm eviction guard
        (device handoffs + cached uploads stay alive across members)."""
        plan = self.plan
        for m in self.members:
            mid = m.identifier
            faults.check("executor.stage_compute", id=chunk[0])
            mconf = plan.mconfs[mid]
            if mid in payloads:
                payload = payloads[mid]
            else:
                payload = m.fused_read_batch(
                    handoffs, chunk, plan.blocking, mconf
                )
            t1 = time.perf_counter()
            with obs_trace.span(
                "stage_compute", kind="host_compute", task=mid,
                chain=plan.chain.name, blocks=len(chunk),
                block_ids=list(chunk),
            ):
                result, handoff = m.fused_compute_batch(
                    payload, plan.blocking, mconf, elided=mid in self.elide
                )
            # ctt-hbm accounting: one device dispatch per member per slab
            # (member uploads route through the warm device-buffer cache
            # via their own compute helpers — see tasks/threshold.py)
            obs_metrics.inc("device.dispatches")
            m.record_timing(
                f"batch_{chunk[0]}_{chunk[-1]}", len(chunk),
                time.perf_counter() - t1,
            )
            results[mid] = result
            out_pair = _member_output_pair(m)
            if out_pair is not None:
                handoffs[out_pair] = handoff
            if mid in self.elide:
                obs_metrics.inc(
                    "stream.elided_bytes",
                    int(m.fused_elided_nbytes(handoff, plan.blocking, mconf)),
                )

    def _apply_carry(self, chunk: List[int], results) -> None:
        plan = self.plan
        for m in self.members:
            mid = m.identifier
            self.carry[mid] = m.fusion_carry_update(
                self.carry.get(mid), results[mid], chunk, plan.blocking,
                plan.mconfs[mid],
            )
            self.carry_peak = max(
                self.carry_peak, _carry_nbytes(m, self.carry.get(mid))
            )

    def _write(self, chunk: List[int], results) -> None:
        plan = self.plan
        faults.check("executor.stage_write", id=chunk[0])
        t0 = time.perf_counter()
        with obs_trace.span(
            "stage_write", kind="host_io", chain=plan.chain.name,
            blocks=len(chunk), block_ids=list(chunk),
        ):
            for m in self.members:
                mid = m.identifier
                if mid in self.elide:
                    continue
                m.write_batch(results[mid], plan.blocking, plan.mconfs[mid])
        self._acc("write", time.perf_counter() - t0)

    # -- batch with retry ----------------------------------------------------

    def _run_batch_synchronous(self, chunk, apply_carry: bool) -> None:
        """Serial read→compute(→carry)→write for one batch — the retry and
        write-failure recovery path (recompute is deterministic, block
        writes idempotent; ``apply_carry=False`` prevents double-counting
        state that an earlier attempt already carried)."""
        payloads = self._read(chunk)
        results = self._compute(chunk, payloads)
        if apply_carry:
            self._apply_carry(chunk, results)
        self._write(chunk, results)

    def _attempt(self, fn, chunk, what: str):
        """Run ``fn`` with up to ``retries`` full re-attempts.  The retry
        re-runs read AND compute for the batch (mid-slab faults must not
        leave carried state half-applied)."""
        retries = self.plan.retries
        for attempt in range(retries + 1):
            try:
                return fn()
            except Exception:
                if attempt >= retries:
                    raise
                obs_metrics.inc("task.blocks_retried", len(chunk))
                obs_heartbeat.note_blocks_retried(len(chunk))
                print(f"[ctt-stream] {what} for blocks "
                      f"{chunk[0]}..{chunk[-1]} failed (attempt "
                      f"{attempt + 1}/{retries + 1}); retrying:\n"
                      f"{traceback.format_exc()}")
        return None  # pragma: no cover - loop always returns or raises

    # -- ctt-ingest seam -----------------------------------------------------
    #
    # The incremental driver (ingest/runner.py) runs the SAME pass one
    # chunk at a time, persisting the carry between chunks: prepare() +
    # run_chunk()* + finalize() is run() with the pipelining removed —
    # compute and carry application already happen on the calling thread
    # in chunk order in both, which is what makes the outputs
    # byte-identical.

    def prepare(self) -> None:
        """Output-dataset creation for every non-elided member + carry
        init — the head of :meth:`run`, factored out for incremental
        drivers.  Elided members' outputs intentionally never exist."""
        plan = self.plan
        for m in self.members:
            if m.identifier not in self.elide:
                m.prepare(plan.blocking, plan.mconfs[m.identifier])
            self.carry[m.identifier] = m.fusion_carry_init(
                plan.blocking, plan.mconfs[m.identifier]
            )

    def run_chunk(self, chunk: List[int]) -> None:
        """One batch, serially (read → compute → carry → write) with the
        full retry budget — the per-slab step of an incremental pass."""
        self._attempt(
            lambda: self._run_batch_synchronous(chunk, True),
            chunk, "ingest batch",
        )
        obs_metrics.inc("stream.slabs")
        obs_heartbeat.note_blocks_done(len(chunk))
        obs_heartbeat.note_block_end(chunk[0])

    def export_carry(self) -> Dict[str, Any]:
        """Picklable snapshot of the carried merge state (per-member carry
        + peak accounting) — what ctt-ingest persists after each slab
        commit so a successor process can resume the stream."""
        return {"carry": dict(self.carry), "carry_peak": int(self.carry_peak)}

    def import_carry(self, state: Dict[str, Any]) -> None:
        self.carry = dict(state["carry"])
        self.carry_peak = max(self.carry_peak, int(state.get("carry_peak", 0)))

    def finalize(self, wall: float) -> None:
        """Member finalizers, carry finalizers and completion stamps —
        the tail of :meth:`run`, public for incremental drivers."""
        self._finish(wall)

    # -- main loop -----------------------------------------------------------

    def run(self) -> None:
        plan = self.plan
        chain = plan.chain
        members = self.members
        obs_metrics.inc("stream.chains")
        obs_heartbeat.note_task(
            f"chain:{chain.name}", len(plan.block_ids),
            grid=plan.blocking.grid_shape,
        )
        self.prepare()

        t_wall0 = obs_trace.monotonic()
        reads: deque = deque()   # (chunk, Future[payloads])
        writes: deque = deque()  # (chunk, Future[None])
        depth = plan.depth
        with obs_trace.span(
            "fused_chain", kind="dispatch", task=f"chain:{chain.name}",
            chain=chain.name, members=[m.identifier for m in members],
            blocks=len(plan.block_ids), grid=list(plan.blocking.grid_shape),
        ), ThreadPoolExecutor(
            depth, thread_name_prefix="ctt-stream-read"
        ) as read_pool, ThreadPoolExecutor(
            depth, thread_name_prefix="ctt-stream-write"
        ) as write_pool:

            def _drain_write():
                chunk, fut = writes.popleft()
                try:
                    fut.result()
                except Exception:
                    # the write ran detached from its compute; recover by
                    # re-running the whole batch serially (carry already
                    # applied — deterministic recompute, idempotent writes)
                    self._attempt(
                        lambda: self._run_batch_synchronous(chunk, False),
                        chunk, "write recovery",
                    )
                obs_metrics.inc("stream.slabs")
                obs_heartbeat.note_blocks_done(len(chunk))
                obs_heartbeat.note_block_end(chunk[0])

            def _drain_read():
                chunk, fut = reads.popleft()
                try:
                    payloads = fut.result()
                    results = self._compute(chunk, payloads)
                except Exception:
                    # pipelined attempt failed before carry: retry the
                    # batch whole (read included), serially
                    if self.plan.retries <= 0:
                        raise
                    obs_metrics.inc("task.blocks_retried", len(chunk))
                    obs_heartbeat.note_blocks_retried(len(chunk))
                    print(f"[ctt-stream] batch {chunk[0]}..{chunk[-1]} "
                          f"failed in flight; retrying serially:\n"
                          f"{traceback.format_exc()}")
                    self._attempt(
                        lambda: self._run_batch_synchronous(chunk, True),
                        chunk, "batch retry",
                    )
                    obs_metrics.inc("stream.slabs")
                    obs_heartbeat.note_blocks_done(len(chunk))
                    obs_heartbeat.note_block_end(chunk[0])
                    return
                self._apply_carry(chunk, results)
                writes.append(
                    (chunk, write_pool.submit(self._write, chunk, results))
                )
                while len(writes) > depth:
                    _drain_write()

            for chunk in plan.chunks:
                reads.append((chunk, read_pool.submit(self._read, chunk)))
                while len(reads) >= depth:
                    _drain_read()
            while reads:
                _drain_read()
            while writes:
                _drain_write()

        wall = obs_trace.monotonic() - t_wall0
        self._finish(wall)

    def _finish(self, wall: float) -> None:
        plan = self.plan
        members = self.members
        n_blocks = len(plan.block_ids)

        # finalize hooks (same order as task-at-a-time), then the carry
        # finalizers that write the covered tasks' outputs
        for m in members:
            m.finalize(plan.blocking, plan.mconfs[m.identifier], plan.block_ids)
        for m in members:
            m.fusion_finalize(
                self.carry.get(m.identifier), plan.blocking,
                plan.mconfs[m.identifier],
            )

        obs_metrics.set_gauge("stream.carry_bytes", int(self.carry_peak))
        # pipeline stage aggregates land on the head member's status (the
        # chain shares one read/write pipeline); per-member compute walls
        # were recorded per batch above
        head = members[0]
        head.record_timing("stage_read_total", n_blocks, self.stage_s["read"])
        head.record_timing(
            "stage_compute_total", n_blocks, self.stage_s["compute"]
        )
        head.record_timing(
            "stage_write_total", n_blocks, self.stage_s["write"]
        )
        obs_metrics.inc("executor.stage_batches", len(plan.chunks))
        obs_metrics.inc("executor.stage_read_s", self.stage_s["read"])
        obs_metrics.inc("executor.stage_compute_s", self.stage_s["compute"])
        obs_metrics.inc("executor.stage_write_s", self.stage_s["write"])
        obs_metrics.inc(
            "executor.stage_hidden_io_s",
            max(
                0.0,
                self.stage_s["read"] + self.stage_s["write"]
                - max(0.0, wall - self.stage_s["compute"]),
            ),
        )

        # positive completion records: each member's status says every
        # block is done (resume/retry and downstream completion checks read
        # these exactly as after a task-at-a-time run)
        done = set(plan.block_ids)
        for m in members:
            m._write_status(
                m.output(), plan.block_ids, done, [], [wall], True
            )
            m.log(f"done {m.identifier} (fused chain "
                  f"{plan.chain.name!r}) in {wall:.2f}s")
        for t in plan.chain.covers:
            t.output().write({
                "task": t.identifier,
                "complete": True,
                "fused_chain": plan.chain.name,
                "runtime_s": 0.0,
                "timings": [],
            })


def _execute(plan: _ChainPlan) -> None:
    _ChainRunner(plan).run()
