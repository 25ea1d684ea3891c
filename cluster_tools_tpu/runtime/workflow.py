"""Workflow DAG runner: topological execution with resume-from-checkpoint.

The analog of running luigi with the local scheduler in the reference
(reference workflows.py + cluster_tasks.py:644-675): a workflow's ``requires()``
builds a dependency chain; ``build([task])`` executes incomplete tasks in
topological order, skipping tasks whose completion target already exists —
re-running a workflow resumes from the first incomplete task.

Submission vs execution (ctt-serve): ``build()`` historically fused the
two — every call also (re)armed the per-process amortizable state (the
persistent XLA compile cache, heartbeats, devices).  That state now lives
in :class:`ExecutionContext`: a cold process still gets one implicitly
(``ExecutionContext.process_context()``, identical behavior), while a
long-lived host — the ``cluster_tools_tpu.serve`` daemon — creates ONE
context at startup and passes it to every submitted build, so mesh
resolution, compiled executables, and the decoded-chunk LRU stay warm
across jobs instead of dying with each driver process.
"""

from __future__ import annotations

import os
from typing import Any, Dict, List, Optional, Sequence

from . import config as cfg
from .task import Target, Task


class ExecutionContext:
    """The amortizable per-process execution state, made explicit.

    Owns exactly what a fresh workflow process pays to set up and then
    throws away: the persistent XLA compile-cache wiring
    (``utils/compile_cache.py``), the decoded-chunk LRU budget
    (``utils/store.py`` — the cache itself is process-global; the context
    pins its budget), the resolved local device set (``resolve_batch_size``
    asks the context instead of re-querying jax per dispatch), and the
    trace/heartbeat wiring (``obs/heartbeat.py``).  ``activate()`` is
    idempotent; ``build()`` activates the process-wide singleton on every
    call — byte-for-byte the old cold-process behavior — while the serve
    daemon activates one context once and reuses it for every job,
    which is where the amortization lives: the SECOND job submitted to a
    warm context pays neither interpreter+jax import nor jit compiles.
    """

    _PROCESS: Optional["ExecutionContext"] = None

    def __init__(
        self,
        chunk_cache_mb: Optional[float] = None,
        role: Optional[str] = None,
        hbm_cache_mb: Optional[float] = None,
    ):
        self._chunk_cache_mb = chunk_cache_mb
        self._hbm_cache_mb = hbm_cache_mb
        self._role = role
        self._activated = False
        self._n_devices: Optional[int] = None
        self._device_cache = None
        self.compile_cache_dir: Optional[str] = None
        self.builds_executed = 0

    def activate(self) -> "ExecutionContext":
        """Arm the warm state (idempotent).  Never raises for cache
        trouble — the context is an optimization layer, not a gate."""
        if self._activated:
            return self
        from ..obs import heartbeat as obs_heartbeat
        from ..utils.compile_cache import enable_compile_cache

        self.compile_cache_dir = enable_compile_cache()
        if self._chunk_cache_mb is not None:
            from ..utils import store

            store.set_chunk_cache_budget(
                int(float(self._chunk_cache_mb) * (1 << 20))
            )
        # liveness from the moment the context exists (no-op, no thread,
        # when tracing is off — the one ctt-obs switch)
        obs_heartbeat.ensure_started(role=self._role)
        self._activated = True
        return self

    def device_cache(self):
        """The context's warm device-buffer cache (ctt-hbm), created
        lazily: budget from the ``hbm_cache_mb`` constructor argument
        (the serve daemon's config — cross-job HBM reuse lives there),
        else ``CTT_HBM_CACHE_MB`` (default 0 = disabled).  Owned here so
        the cache's lifetime IS the warm process state's lifetime."""
        if self._device_cache is None:
            from . import hbm

            budget = (
                int(float(self._hbm_cache_mb) * (1 << 20))
                if self._hbm_cache_mb is not None
                else hbm.cache_budget_bytes()
            )
            self._device_cache = hbm.DeviceBufferCache(max(budget, 0))
        return self._device_cache

    def local_device_count(self) -> int:
        """Visible local devices, resolved once per context — the
        executor's batch sizing rides this instead of asking jax on every
        dispatch (on a serving host that is thousands of dispatches)."""
        if self._n_devices is None:
            try:
                import jax

                self._n_devices = max(int(jax.local_device_count()), 1)
            except Exception:  # pragma: no cover - no backend at all
                self._n_devices = 1
        return self._n_devices

    def describe(self) -> Dict[str, Any]:
        """Introspection snapshot (the serve daemon's /healthz payload)."""
        from ..utils import store

        return {
            "activated": self._activated,
            "role": self._role,
            "compile_cache_dir": self.compile_cache_dir,
            "chunk_cache_budget_bytes": store.chunk_cache_budget(),
            "device_cache": self.device_cache().describe(),  # ctt-hbm
            "devices": self._n_devices,  # None until first dispatch asks
            "builds_executed": self.builds_executed,
            "pid": os.getpid(),
        }

    @classmethod
    def process_context(cls) -> "ExecutionContext":
        """The implicit per-process context every plain ``build()`` call
        uses — what a cold workflow process always paid, now nameable."""
        if cls._PROCESS is None:
            cls._PROCESS = ExecutionContext()
        return cls._PROCESS.activate()

    def install(self) -> "ExecutionContext":
        """Make THIS context the process-wide one (the serve daemon calls
        it once at startup, so in-process builds and the executor's device
        resolution all share the daemon's warm state)."""
        ExecutionContext._PROCESS = self
        return self.activate()


class WorkflowBase(Task):
    """A composite task: ``requires()`` returns the dependency chain, completion
    mirrors the last member task (reference cluster_tasks.py:667-669)."""

    def __init__(
        self,
        tmp_folder: str,
        config_dir: Optional[str] = None,
        max_jobs: Optional[int] = None,
        target: Optional[str] = None,
        dependencies: Sequence[Task] = (),
    ):
        super().__init__(tmp_folder, config_dir, max_jobs, dependencies)
        self.target = target  # informational; the global config decides

    def run(self) -> None:
        pass  # members do the work

    def output(self) -> Target:
        reqs = list(self.requires())
        if reqs:
            return reqs[-1].output()
        return super().output()

    def complete(self) -> bool:
        reqs = list(self.requires())
        if reqs:
            return all(r.complete() for r in reqs)
        return super().complete()

    @classmethod
    def get_config(cls) -> Dict[str, dict]:
        """Default configs of all member tasks, for users to edit and write to the
        config dir (reference workflows.py:102-107)."""
        return {"global": dict(cfg.DEFAULT_GLOBAL_CONFIG)}

    def fused_chains(self) -> List:
        """Declared fusible chains (ctt-stream): a list of
        ``runtime.stream.FusedChain`` over member tasks.  ``build()``
        attempts each chain as one streaming pass before running its
        members task-at-a-time; any ineligible chain silently falls back.
        Lint rule CTT011 statically validates declarations."""
        return []


def _task_key(task: Task) -> str:
    return f"{type(task).__module__}.{type(task).__qualname__}:{task.output().path}"


def _toposort(roots: Sequence[Task]) -> List[Task]:
    order: List[Task] = []
    seen: Dict[str, Task] = {}
    visiting: set = set()

    def visit(task: Task) -> None:
        key = _task_key(task)
        if key in seen:
            return
        if key in visiting:
            raise RuntimeError(f"dependency cycle at {task!r}")
        visiting.add(key)
        for dep in task.requires():
            visit(dep)
        visiting.discard(key)
        seen[key] = task
        order.append(task)

    for t in roots:
        visit(t)
    return order


def _collect_chains(order: Sequence[Task]):
    """Fused-chain declarations from the workflow nodes of a build, mapped
    by member/covered task key so the build loop can attempt a chain when
    it reaches the first incomplete task the chain would satisfy.  A
    declaration that raises is dropped loudly (declarations must never
    break a build)."""
    by_key: Dict[str, object] = {}
    for task in order:
        if not isinstance(task, WorkflowBase):
            continue
        try:
            chains = list(task.fused_chains())
        except Exception as e:
            print(f"[ctt-stream] ignoring fused_chains() of {task!r}: "
                  f"{type(e).__name__}: {e}")
            continue
        for chain in chains:
            for member in list(chain.members) + list(chain.covers):
                by_key.setdefault(_task_key(member), chain)
    return by_key


def build(
    tasks: Sequence[Task],
    raise_on_failure: bool = True,
    context: Optional[ExecutionContext] = None,
) -> bool:
    """Run a set of root tasks and their dependencies.  Returns success.

    ``context`` carries the warm per-process execution state (compile
    cache, chunk LRU budget, devices, heartbeats).  None — the normal
    cold-process call — activates the process-wide singleton, which is
    exactly the setup every ``build()`` performed inline before; a
    long-lived submitter (the serve daemon) passes its own context so
    that state is armed once and shared across many builds."""
    from ..obs import trace as obs_trace

    ctx = (context or ExecutionContext.process_context()).activate()
    ctx.builds_executed += 1
    order = _toposort(tasks)
    for task in order:
        # resume after a multi-host failure: stale aborted flags from the
        # prior run would otherwise fail peers' barriers immediately
        task.clear_stale_abort()
    chains_by_key = _collect_chains(order)
    attempted: set = set()
    try:
        with obs_trace.span("build", kind="run", n_tasks=len(order)):
            for task in order:
                if task.complete():
                    continue
                # ctt-stream: an incomplete task covered by a declared
                # fused chain triggers ONE attempt at running the whole
                # chain as a streaming pass; on success the members' and
                # covered tasks' status files are complete and the loop
                # skips them.  A declined/failed chain leaves no status
                # behind, so execution proceeds task-at-a-time unchanged.
                chain = chains_by_key.get(_task_key(task))
                if chain is not None and id(chain) not in attempted:
                    attempted.add(id(chain))
                    from . import stream

                    if stream.try_run_chain(chain) and task.complete():
                        continue
                try:
                    task.run()
                except Exception:
                    if raise_on_failure:
                        raise
                    import traceback

                    traceback.print_exc()
                    return False
                if isinstance(task, WorkflowBase):
                    continue
                if not task.complete():
                    msg = f"task {task!r} ran but did not reach completion"
                    if raise_on_failure:
                        raise RuntimeError(msg)
                    print(msg)
                    return False
        return True
    finally:
        # in-process callers (tests, notebooks) see complete shards without
        # waiting for interpreter exit
        obs_trace.flush()
