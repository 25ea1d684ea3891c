"""ctt-obs metric-name registry: the canonical list of series names.

Counters and gauges are stringly-typed at the call site
(``metrics.inc("store.bytes_read")``) — a typo there does not fail, it
silently creates a fresh series that no dashboard, bench contract, or
``obs diff`` ever looks at.  This module is the single source of truth:

  * every known counter/gauge name, grouped by owning subsystem;
  * the allowed *dynamic* prefixes (``faults.injected.<site>`` is one
    series per injection site by design);
  * lint rule CTT010 (analysis/ast_rules.py) flags any
    ``metrics.inc``/``set_gauge`` call whose literal name is not listed
    here, so adding a metric means adding it to this registry — which is
    exactly where README/COMPONENTS readers go looking for it.

The live exporter (obs.live ``prom``) exposes whatever a run actually
recorded; this registry is a *lint* namespace, not a runtime filter —
dynamic names and future names degrade to "unknown series", never to
dropped data.
"""

from __future__ import annotations

__all__ = ["COUNTERS", "GAUGES", "HISTOGRAMS", "DYNAMIC_PREFIXES",
           "is_known_counter", "is_known_gauge", "is_known_histogram"]

# -- counters (metrics.inc) -------------------------------------------------

COUNTERS = frozenset({
    # utils/store.py — chunk IO at the codec boundary
    "store.bytes_read",
    "store.bytes_written",
    "store.chunks_read",
    "store.chunks_written",
    "store.chunk_cache_hits",
    "store.chunk_cache_misses",
    "store.aligned_chunk_writes",
    # utils/retry.py — backoff sleeps absorbed on transient chunk IO
    "store.io_retries",
    # utils/store_backend.py — ctt-cloud object-store backend: HTTP
    # requests (GET/HEAD = reads, PUT/DELETE = writes), wire bytes, and
    # backoff sleeps absorbed on transient remote requests
    "store.remote_reads",
    "store.remote_writes",
    "store.remote_retries",
    "store.remote_bytes_read",
    "store.remote_bytes_written",
    # ctt-diskless: S3 multipart uploads taken for oversized payloads
    # (one count per whole upload, not per part), and requests the store
    # rejected 401/403 — each such rejection surfaces as a retryable
    # auth error riding the same request-level retry
    "store.remote_multipart_uploads",
    "store.remote_auth_retries",
    # utils/compile_cache.py — jax.monitoring persistent-cache events
    "compile_cache.cache_hits",
    "compile_cache.cache_misses",
    "compile_cache.tasks_using_cache",
    # utils/compile_cache.py — jax.monitoring backend-compile durations
    # (a load from the persistent cache included): count and seconds
    "jit.compiles",
    "jit.compile_s",
    # tasks/base.py count_block_rounds — loop rounds the block programs
    # return beside their labels (ops/watershed.py, ops/cc.py)
    "blocks.computed",          # blocks of the programs that counted
    "flood.rounds",             # global altitude + assignment loop rounds
    "flood.tile_rounds",        # tile warm-start loop rounds
    "cc.rounds",                # CC fixpoint loop rounds
    # runtime/task.py — retry machinery
    "task.blocks_failed",
    "task.blocks_retried",
    # runtime/executor.py — dispatch + pipeline occupancy
    "executor.batches",
    "executor.batch_s",
    "executor.dispatch_wall_s",
    "executor.blocks_timed_out",
    "executor.stage_batches",
    "executor.stage_read_s",
    "executor.stage_compute_s",
    "executor.stage_write_s",
    "executor.stage_hidden_io_s",
    # ctt-cloud async-prefetch lookahead stage (advisory LRU warming
    # ahead of the in-order compute stage)
    "executor.prefetch_batches",
    "executor.stage_prefetch_s",
    # ctt-hbm double-buffered transfer stage: seconds the upload thread
    # spent moving batches to HBM (overlap vs compute derives from this)
    "executor.stage_upload_s",
    # runtime/hbm.py — ctt-hbm device-resident pipelines
    "device.upload_bytes",      # host bytes that actually crossed to HBM
    "device.uploads_skipped",   # batches served from the warm buffer cache
    "device.cache_evictions",   # LRU evictions (explicit .delete() frees)
    "device.dispatches",        # device program launches (batch grain)
    "device.fused_blocks",      # blocks that rode an aggregated (stacked)
                                # dispatch — hbm_stack > 1 economics
    "device.deferred_deletes",  # evicted batches whose .delete() waited
                                # for the active dispatch guards to exit
                                # (the eviction/in-flight race fix)

    # ops/hier.py + tasks/hier.py — ctt-hier one-flood hierarchical
    # segmentation (host-side emission only, never inside jit)
    "hier.tables_built",        # blocks whose in-block merge table landed
    "hier.edges",               # saddle edges persisted into an artifact
    "hier.cut_edges",           # edges selected (saddle <= t) across cuts
    "hier.resegment_jobs",      # serve `resegment` jobs run to success

    # ops/events.py + tasks/events.py — ctt-events high-rate event
    # building (host-side emission from the build_events wrapper)
    "events.frames",            # detector frames labeled + summarized
    "events.clusters",          # clusters (events) extracted across frames
    "events.batches",           # batched (n_frames, h, w) device dispatches

    # ops/cc.py — ctt-cc coarse-to-fine kernel stats (host-side emission
    # from the connected_components_coarse wrapper, never inside jit)
    "cc.fixpoint_iters",
    "cc.live_tiles",
    "cc.merge_pairs",
    # faults/ — every fired injection (per-site series via prefix below)
    "faults.injected",
    # parallel/sharded.py — collective→local degradations
    "sharded.fallback_local",
    # tasks/thresholded_components.py ShardedComponentsTask — what the
    # collective CC program returns beside its labels (parallel/sharded.py)
    "scc.shards",               # mesh shards of the programs that counted
    "scc.local_rounds",         # stage-1 fixpoint rounds, summed over shards
    "scc.face_pairs",           # live cross-shard face pairs in the table
    # runtime/queue.py — ctt-steal work-stealing scheduler
    "sched.leases_claimed",      # lease links won (gen 0 + requeues)
    "sched.leases_expired",      # leases found stale (3x cadence) on claim
    "sched.leases_requeued",     # expired leases taken over at gen+1
    "sched.leases_stolen",       # straggler items duplicated (no lease;
                                 # first-writer-wins result)
    "sched.driver_drain_blocks",  # blocks the driver backstop pulled after
                                  # every scheduler job had exited
    # runtime/stream.py — ctt-stream fused-chain execution
    "stream.chains",        # fused chains executed to completion
    "stream.slabs",         # block batches (z-slabs) streamed through a chain
    "stream.elided_bytes",  # intermediate bytes neither written nor re-read
    "stream.fallbacks",     # declared chains that declined/failed to fuse
    # serve/ — ctt-serve persistent serving daemon
    "serve.submissions",        # admitted job submissions
    "serve.quota_rejections",   # 429s: queue depth or tenant quota said no
    "serve.jobs_done",          # jobs executed to a successful result
    "serve.jobs_failed",        # jobs whose build raised/failed
    "serve.warm_compile_jobs",  # jobs whose (workflow, block-shape)
                                # signature already ran on this daemon —
                                # served from warm in-process compile
                                # caches (per-job persistent-cache deltas
                                # ride the job result)
    "serve.cold_compile_jobs",  # first job of a signature: pays compiles
    "serve.leases_requeued",    # stale job leases taken over at gen+1
                                # (a predecessor daemon died mid-job)
    "serve.jobs_reclaimed",     # ctt-fleet fast-path takeovers: the
                                # owner's fleet heartbeat proved it dead,
                                # so the lease expired at heartbeat (not
                                # lease) staleness — a subset of
                                # serve.leases_requeued
    "serve.jobs_quarantined",   # jobs parked as failed results after
                                # exhausting max_job_gens generations
                                # (the poison-job retry budget)
    # ctt-proto: the publish_once lost-race branches made observable —
    # each counts a benign first-writer-wins collision with a peer
    "serve.jobs_admitted",      # two-phase admissions this daemon won
    "serve.retract_races",      # retractions a peer's limbo reaper beat
    "serve.result_races",       # job results where a gen+1 re-run won
    # ctt-microbatch: cross-tenant job aggregation in the executor loop —
    # queued jobs sharing a microbatch signature coalesce into ONE
    # stacked dispatch (serve/microbatch.py); accounting stays per member
    "serve.microbatch_batches",     # stacked dispatches with >= 2 members
    "serve.microbatch_jobs_batched",  # member jobs that rode a stacked
                                      # dispatch (jobs/batches = the
                                      # aggregation ratio)
    "serve.microbatch_splits",  # members re-dispatched individually after
                                # a batch-path failure (poison isolation:
                                # only the culprit burns retry budget)
    "serve.microbatch_window_timeouts",  # aggregation windows that closed
                                         # on the deadline, not early-fill
    # ingest/ — ctt-ingest streaming ingest of a growing source
    "ingest.slabs_ingested",    # chunks committed through the chain
    "ingest.resumes",           # streams resumed from a persisted carry
    "ingest.poll_rounds",       # source listing scans (one per poll)
    "ingest.carry_bytes_persisted",  # carry-record bytes published
    # serve/supervisor.py — ctt-diskless elastic-fleet actor
    "serve.supervisor_spawns",  # daemon processes forked on scale-up
    "serve.supervisor_drains",  # surplus daemons SIGTERMed into a drain
    "serve.supervisor_adoptions",  # running daemons a (re)started
                                   # supervisor found via beats without
                                   # having spawned them — the
                                   # SIGKILL-the-supervisor recovery path
})

# -- gauges (metrics.set_gauge) ---------------------------------------------

GAUGES = frozenset({
    "compile_cache.entries_at_enable",
    # utils/store_backend.py — remote HTTP requests currently in flight
    "store.remote_inflight",
    # runtime/hbm.py — ctt-hbm: resident HBM buffer-cache bytes and
    # host→device transfers currently in flight (the two-slot gate)
    "device.cache_bytes",
    "device.inflight_uploads",
    # runtime/stream.py — peak carried merge-state bytes of a fused chain
    "stream.carry_bytes",
    # runtime/queue.py — unclaimed work-queue items at the last pull scan
    "sched.queue_depth",
    # serve/ — the daemon's job queue: queued (unleased) jobs + builds
    # currently executing
    "serve.queue_depth",
    "serve.running_jobs",
    # ctt-microbatch: member count of the most recent aggregation window
    # (1 = the window closed with a solo claim)
    "serve.microbatch_depth",
    # ctt-fleet: live (beating, non-exiting) daemons sharing the state
    # dir, and the fleet-wide queued-job backlog (the shared-dir count —
    # identical on every daemon, unlike per-daemon serve.queue_depth
    # history before the fleet)
    "serve.peers",
    "fleet.queue_depth",
    # serve/supervisor.py — ctt-diskless: the clamped daemon count the
    # supervisor is converging the fleet toward
    "fleet.target_daemons",
    # ingest/ — slabs landed (incl. out-of-order parked) but not yet
    # committed through the chain: the watcher/ingester gap
    "ingest.slabs_pending",
})

# -- histograms (hist.observe) ----------------------------------------------
#
# ctt-slo request-grain latency distributions.  Every name is a seconds
# histogram on the FIXED log2 bucket edges of obs/hist.py (exact
# cross-daemon merge), labeled by tenant + priority at the observe site.

HISTOGRAMS = frozenset({
    # serve/server.py — per-phase request latencies.  Phase walls are
    # also stamped durably (job/lease/result records), so `obs journey`
    # can reconstruct the same breakdown per job from disk.
    "serve.latency.admission",    # submit() entry -> admit/reject decision
    "serve.latency.queue_wait",   # admit wall -> lease claim_wall
    "serve.latency.window_wait",  # claim_wall -> dispatch_wall (microbatch
                                  # aggregation-window residency; ~0 when
                                  # the window is off)
    "serve.latency.execution",    # dispatch_wall -> build returned
    "serve.latency.publish",      # build returned -> result record durable
    "serve.latency.e2e",          # job submit_wall -> result published
})

# dynamic name families: one series per <suffix>, allowed by prefix
DYNAMIC_PREFIXES = (
    "faults.injected.",  # per injection site (faults/__init__.py)
)


def _matches_prefix(name: str) -> bool:
    return any(name.startswith(p) for p in DYNAMIC_PREFIXES)


def is_known_counter(name: str) -> bool:
    return name in COUNTERS or _matches_prefix(name)


def is_known_gauge(name: str) -> bool:
    return name in GAUGES or _matches_prefix(name)


def is_known_histogram(name: str) -> bool:
    return name in HISTOGRAMS or _matches_prefix(name)
