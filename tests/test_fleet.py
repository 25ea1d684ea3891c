"""ctt-fleet: fault-tolerant multi-daemon serve fleet tests.

Covers the fleet hardening end to end:

  * fleet heartbeats + peer liveness: the 3 x promised-cadence dead rule,
    ``exiting`` fast exit, three-valued verdicts (no beat = unknown, NOT
    dead), torn ``daemon.<id>.json`` beats (``fleet.write`` chaos)
    degrading to mtime ageing;
  * peer failover: an orphan lease whose owner's beat proves it dead is
    expired at heartbeat staleness, not lease staleness — including a
    fabricated orphan from the claim-to-first-renewal window (the daemon
    id is stamped at claim time); no beat at all falls back to the slow
    rule;
  * retry budgets: a poison job burns exactly ``max_job_gens``
    generations, then parks as a quarantined failed result carrying every
    generation's lease stamp; between-generation backoff rides
    ``utils.retry.backoff_delay_s``;
  * fleet-consistent admission: k daemons over one state dir cannot
    jointly overshoot ``max_queue_depth`` or a tenant quota (the
    two-phase recount regression), and ``/healthz`` exports the decision
    inputs;
  * cross-host work stealing: the block-grain ``WorkQueue`` runs over an
    HTTP object store (conditional-PUT ``publish_once``), exactly-once
    under ``sched.claim`` stall chaos + seeded 503s;
  * zero-loss chaos gate (subprocess): two real daemons, mid-run SIGKILL
    — every job completes byte-identically and recovery is bounded by
    the heartbeat rule (not the 3 x lease_s window).
"""

import hashlib
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from objstub import StubObjectStore

from cluster_tools_tpu import faults
from cluster_tools_tpu.obs import metrics as obs_metrics
from cluster_tools_tpu.obs import trace as obs_trace
from cluster_tools_tpu.runtime.queue import (
    STALE_INTERVALS, WorkQueue, publish_once,
)
from cluster_tools_tpu.serve import (
    JobQueue, QuotaRejected, ServeClient, ServeDaemon,
)
from cluster_tools_tpu.serve.fleet import (
    FleetBeat, FleetView, beat_path, default_daemon_id, read_peers,
    scale_advice,
)
from cluster_tools_tpu.utils import file_reader

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _digest(root):
    h = hashlib.sha256()
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames.sort()
        for name in sorted(filenames):
            p = os.path.join(dirpath, name)
            h.update(os.path.relpath(p, root).encode())
            with open(p, "rb") as f:
                h.update(f.read())
    return h.hexdigest()


def _sleep_vol_job(td, tag, sleep_s, tenant="default", priority=0):
    """A submission payload for a calibrated-cost job (the ctt-steal
    skewed-cost fixture task): one block, deterministic output
    (input * 2 + 1), every block costs ``sleep_s``."""
    path = os.path.join(td, f"{tag}.n5")
    if not os.path.exists(path):
        file_reader(path).create_dataset(
            "x", data=np.ones((2, 8, 8), dtype="float32"), chunks=(2, 8, 8)
        )
    return {
        "workflow": "bench_e2e_lib:SkewedCostTask",
        "kwargs": {
            "tmp_folder": os.path.join(td, f"tmp_{tag}"),
            "config_dir": os.path.join(td, f"configs_{tag}"),
            "input_path": path, "input_key": "x",
            "output_path": path, "output_key": "y",
        },
        "configs": {
            "global": {"block_shape": [2, 8, 8]},
            "skewed_cost": {
                "hot_z_end": 0, "base_s": float(sleep_s), "hot_s": 99.0,
            },
        },
        "tenant": tenant,
        "priority": priority,
    }


def _submit_kw(payload):
    return {
        "workflow": payload["workflow"],
        "kwargs": payload["kwargs"],
        "configs": payload["configs"],
        "tenant": payload["tenant"],
        "priority": payload["priority"],
    }


def _backdate(path, seconds):
    """Age a lease/beat file's wall stamp (and mtime) into the past —
    deterministic staleness without real sleeps."""
    rec = json.load(open(path))
    rec["wall"] = rec.get("wall", time.time()) - seconds
    with open(path, "w") as f:
        json.dump(rec, f)
    past = time.time() - seconds
    os.utime(path, (past, past))


@pytest.fixture
def traced(tmp_path):
    """Counters move only while tracing is on (the one ctt-obs switch)."""
    was_on = obs_trace.enabled()
    if not was_on:
        obs_trace.enable(str(tmp_path / "trace"), "fleet_unit",
                         export_env=False)
    try:
        yield obs_metrics
    finally:
        if not was_on:
            obs_trace.disable()


@pytest.fixture
def daemon_factory(tmp_path):
    """In-process daemons with tracing scoped to this test."""
    was_on = obs_trace.enabled()
    if not was_on:
        obs_trace.enable(str(tmp_path / "trace"), "fleet_test",
                         export_env=False)
    daemons = []

    def make(state_dir, **conf):
        d = ServeDaemon(str(state_dir), config=conf)
        d.start()
        daemons.append(d)
        return d

    yield make
    for d in daemons:
        d.request_drain()
        if d._httpd is not None:
            d._httpd.shutdown()
            d._httpd.server_close()
        for t in d._threads:
            if t.name.startswith("ctt-serve-exec"):
                t.join(timeout=60)
        d._fleet_beat.stop(final=True)
    if not was_on:
        obs_trace.disable()


# --------------------------------------------------------------------------
# fleet heartbeats + peer liveness


class TestFleetLiveness:
    def test_beat_publishes_and_carries_info(self, tmp_path):
        b = FleetBeat(str(tmp_path), "d1", interval_s=5.0,
                      info_fn=lambda: {"concurrency": 3, "queued": 2})
        b.beat()
        peers = read_peers(str(tmp_path))
        rec = peers["d1"]
        assert rec["id"] == "d1" and rec["pid"] == os.getpid()
        assert rec["interval_s"] == 5.0 and rec["seq"] == 0
        assert rec["concurrency"] == 3 and rec["queued"] == 2
        assert not rec["exiting"]
        b.beat()
        assert read_peers(str(tmp_path))["d1"]["seq"] == 1

    def test_three_valued_liveness(self, tmp_path):
        b = FleetBeat(str(tmp_path), "d1", interval_s=1.0)
        b.beat()
        view = FleetView(str(tmp_path), self_id="me", cache_ttl_s=0.0)
        # fresh beat: provably alive
        assert view.is_dead("d1") is False
        # no beat ever published: UNKNOWN, never "dead" — callers must
        # fall back to the slow lease-staleness rule
        assert view.is_dead("stranger") is None
        # a daemon never declares itself dead, whatever its beat says
        _backdate(beat_path(str(tmp_path), "d1"),
                  STALE_INTERVALS * 1.0 + 5.0)
        assert FleetView(str(tmp_path), self_id="d1").is_dead("d1") is False
        # aged past 3 x its PROMISED cadence: dead
        assert view.is_dead("d1") is True

    def test_exiting_beat_is_immediate_death(self, tmp_path):
        b = FleetBeat(str(tmp_path), "d1", interval_s=30.0)
        b.start()
        view = FleetView(str(tmp_path), self_id="me", cache_ttl_s=0.0)
        assert view.is_dead("d1") is False
        b.stop(final=True)  # terminal ``exiting`` stamp
        # dead within one read, no 3x-cadence ageing required
        assert view.is_dead("d1") is True
        assert "d1" not in view.live()

    def test_torn_beat_degrades_to_mtime_ageing(self, tmp_path):
        """``fleet.write`` chaos: a truncated daemon.<id>.json must not
        crash a reader NOR misdeclare the (fresh) writer dead — it ages
        from file mtime, the torn-lease convention."""
        b = FleetBeat(str(tmp_path), "d1", interval_s=1.0)
        faults.configure("fleet.write:torn:bytes=5;seed=1")
        try:
            b.beat()
        finally:
            faults.reset()
        raw = open(beat_path(str(tmp_path), "d1"), "rb").read()
        assert len(raw) == 5
        with pytest.raises(json.JSONDecodeError):
            json.loads(raw)
        assert read_peers(str(tmp_path))["d1"].get("torn") is True
        view = FleetView(str(tmp_path), self_id="me", cache_ttl_s=0.0)
        # fresh mtime: alive (the promised cadence is unreadable, so the
        # reader falls back to the ambient heartbeat default)
        assert view.is_dead("d1") is False
        past = time.time() - 3600.0
        os.utime(beat_path(str(tmp_path), "d1"), (past, past))
        assert view.is_dead("d1") is True

    def test_scale_advice(self, tmp_path):
        view = FleetView(str(tmp_path), cache_ttl_s=0.0)
        # backlog with no live capacity: spawn
        adv = scale_advice(str(tmp_path),
                           stats={"queued": 4, "running": 0}, view=view)
        assert adv["action"] == "spawn" and adv["capacity"] == 0
        # two idle daemons: drain one
        for i, conc in ((0, 2), (1, 2)):
            FleetBeat(str(tmp_path), f"d{i}", interval_s=5.0,
                      info_fn=lambda c=conc: {"concurrency": c}).beat()
        adv = scale_advice(str(tmp_path),
                           stats={"queued": 0, "running": 0}, view=view)
        assert adv["action"] == "drain" and adv["capacity"] == 4
        # backlog within capacity: hold
        adv = scale_advice(str(tmp_path),
                           stats={"queued": 3, "running": 4}, view=view)
        assert adv["action"] == "hold"
        # advice only — nothing was spawned or killed
        assert set(read_peers(str(tmp_path))) == {"d0", "d1"}


# --------------------------------------------------------------------------
# peer failover at job grain


class TestPeerFailover:
    def test_claim_stamps_daemon_id_at_claim_time(self, tmp_path):
        """The claim-to-first-renewal window: the very first lease write
        (the exclusive link itself) must carry the daemon id — a daemon
        SIGKILLed before its first renewal still leaves an attributable
        lease."""
        q = JobQueue(str(tmp_path / "jobs"), lease_s=30.0, daemon_id="dA")
        q.submit({"workflow": "W", "tenant": "t"})
        claim = q.claim_next()
        lease = json.load(open(claim.lease_path))
        assert lease["daemon"] == "dA" and lease["gen"] == 0

    def test_orphan_lease_expires_at_heartbeat_not_lease_staleness(
        self, tmp_path, traced
    ):
        """The tentpole latency contract: a dead daemon's lease (lease_s
        30 => 90s slow window) is reclaimed as soon as its beat proves it
        gone, and counts as serve.jobs_reclaimed."""
        state = str(tmp_path / "state")
        os.makedirs(state)
        # the ghost daemon beats once (cadence 1s), claims, and dies
        FleetBeat(state, "ghost", interval_s=1.0).beat()
        qg = JobQueue(os.path.join(state, "jobs"), lease_s=30.0,
                      daemon_id="ghost")
        jid = qg.submit({"workflow": "W", "tenant": "t"})
        dead_claim = qg.claim_next()
        assert dead_claim is not None
        # a peer sees a FRESH lease and a fresh beat: nothing to steal
        view = FleetView(state, self_id="peer", cache_ttl_s=0.0)
        qp = JobQueue(os.path.join(state, "jobs"), lease_s=30.0,
                      daemon_id="peer", fleet=view)
        assert qp.claim_next() is None
        # the ghost's beat ages past 3 x its cadence; the lease (aged 2s
        # past the tiny inter-generation backoff) is still DECADES inside
        # its own 90s staleness window
        _backdate(beat_path(state, "ghost"), STALE_INTERVALS * 1.0 + 2.0)
        _backdate(dead_claim.lease_path, 2.0)
        before = obs_metrics.snapshot()["counters"]
        takeover = qp.claim_next()
        assert takeover is not None and takeover.job_id == jid
        assert takeover.gen == 1
        after = obs_metrics.snapshot()["counters"]
        assert after.get("serve.jobs_reclaimed", 0) > before.get(
            "serve.jobs_reclaimed", 0
        )
        assert after.get("serve.leases_requeued", 0) > before.get(
            "serve.leases_requeued", 0
        )
        # the fast path never fires without the view: a fleet-blind peer
        # keeps honoring the lease window
        q_blind = JobQueue(os.path.join(state, "jobs"), lease_s=30.0,
                           daemon_id="blind")
        assert q_blind.claim_next() is None

    def test_no_beat_falls_back_to_slow_rule(self, tmp_path):
        """An owner that never published a beat (pre-fleet daemon) is
        UNKNOWN, not dead: its live lease must not be stolen."""
        state = str(tmp_path / "state")
        q = JobQueue(os.path.join(state, "jobs"), lease_s=30.0,
                     daemon_id="old-daemon")
        q.submit({"workflow": "W", "tenant": "t"})
        assert q.claim_next() is not None
        view = FleetView(state, self_id="peer", cache_ttl_s=0.0)
        qp = JobQueue(os.path.join(state, "jobs"), lease_s=30.0,
                      daemon_id="peer", fleet=view)
        assert qp.claim_next() is None  # fresh lease, unknown owner


# --------------------------------------------------------------------------
# retry budgets + poison-job quarantine


class TestRetryBudget:
    def test_quarantine_after_exactly_max_job_gens(self, tmp_path, traced):
        q = JobQueue(str(tmp_path / "jobs"), lease_s=0.5, daemon_id="d1",
                     max_job_gens=3)
        jid = q.submit({"workflow": "W", "tenant": "acme"})
        # three generations claim it and "die" (their leases go stale)
        for expected_gen in range(3):
            claim = q.claim_next()
            assert claim is not None and claim.gen == expected_gen
            _backdate(claim.lease_path, 3600.0)
        before = obs_metrics.snapshot()["counters"]
        # the would-be gen 3 claim quarantines instead of executing
        assert q.claim_next() is None
        after = obs_metrics.snapshot()["counters"]
        assert after.get("serve.jobs_quarantined", 0) > before.get(
            "serve.jobs_quarantined", 0
        )
        st = q.get(jid)
        assert st["state"] == "failed"
        res = st["result"]
        assert res["quarantined"] is True and res["ok"] is False
        assert res["gen"] == 3 and res["tenant"] == "acme"
        assert "retry budget" in res["error"]
        # the failure log carries EVERY generation's last lease stamp
        assert [e["gen"] for e in res["failure_log"]] == [0, 1, 2]
        assert all(e["daemon"] == "d1" for e in res["failure_log"])
        # quarantine parks the job, it does not take down the queue: a
        # fresh submission still claims and completes normally
        j2 = q.submit({"workflow": "W", "tenant": "acme"})
        c2 = q.claim_next()
        assert c2 is not None and c2.job_id == j2
        assert q.complete(c2, {"ok": True, "seconds": 0.0})
        # first-writer-wins: re-scanning never duplicates the quarantine
        assert q.claim_next() is None
        assert q.get(jid)["result"]["failure_log"] == res["failure_log"]

    def test_max_job_gens_zero_disables_budget(self, tmp_path):
        q = JobQueue(str(tmp_path / "jobs"), lease_s=0.5, daemon_id="d1",
                     max_job_gens=0)
        q.submit({"workflow": "W", "tenant": "t"})
        for expected_gen in range(6):  # far past the default budget
            claim = q.claim_next()
            assert claim is not None and claim.gen == expected_gen
            _backdate(claim.lease_path, 3600.0)

    def test_generation_backoff_gates_takeover(self, tmp_path, monkeypatch):
        """Between generations the queue waits out backoff_delay_s(gen):
        an expired-but-recent lease is in backoff, not claimable — the
        decelerating burn for poison jobs."""
        monkeypatch.setenv("CTT_IO_BACKOFF_BASE_S", "30.0")
        monkeypatch.setenv("CTT_IO_BACKOFF_MAX_S", "120.0")
        q = JobQueue(str(tmp_path / "jobs"), lease_s=0.5, daemon_id="d1")
        jid = q.submit({"workflow": "W", "tenant": "t"})
        claim = q.claim_next()
        assert claim.gen == 0
        # stale (age 5s > 3 x 0.5s) but inside backoff_delay_s(0) = 30s
        _backdate(claim.lease_path, 5.0)
        assert q.claim_next() is None
        assert q.get(jid)["state"] == "queued"  # expired, awaiting backoff
        # past the backoff: claimable at gen 1
        _backdate(claim.lease_path, 3600.0)
        takeover = q.claim_next()
        assert takeover is not None and takeover.gen == 1


# --------------------------------------------------------------------------
# fleet-consistent admission (the k-daemon overshoot regression)


class TestFleetAdmission:
    def _burst(self, clients, payloads):
        """Submit payloads concurrently round-robin over clients;
        returns (accepted job ids, rejection reasons)."""
        accepted, rejected = [], []
        lock = threading.Lock()

        def one(i, payload):
            try:
                jid = clients[i % len(clients)].submit(**_submit_kw(payload))
                with lock:
                    accepted.append(jid)
            except QuotaRejected as e:
                with lock:
                    rejected.append(str(e))

        threads = [
            threading.Thread(target=one, args=(i, p))
            for i, p in enumerate(payloads)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        return accepted, rejected

    def test_k_daemons_cannot_overshoot_queue_depth(
        self, tmp_path, daemon_factory
    ):
        """The regression the shared-dir recount exists for: before the
        two-phase admit, each daemon's private check-then-act let k
        daemons admit up to k x max_queue_depth together."""
        state = tmp_path / "state"
        daemon_factory(state, max_queue_depth=3, tenant_quota=100)
        daemon_factory(state, max_queue_depth=3, tenant_quota=100)
        clients = [ServeClient(state_dir=str(state))]
        # target both daemons explicitly (serve.json is last-writer)
        td = str(tmp_path)
        payloads = [
            _sleep_vol_job(td, f"ov{i}", 3.0, tenant=f"t{i}")
            for i in range(8)
        ]
        peers = read_peers(str(state))
        assert len(peers) == 2, peers
        accepted, rejected = self._burst(clients, payloads)
        assert len(accepted) == 3, (accepted, rejected)
        assert len(rejected) == 5
        assert all("queue full" in r for r in rejected)
        # zero loss on the admitted side: each runs to a real result
        for jid in accepted:
            st = clients[0].wait(jid, timeout_s=180)
            assert st["result"]["ok"]

    def test_tenant_quota_holds_fleet_wide(self, tmp_path, daemon_factory):
        state = tmp_path / "state"
        d1 = daemon_factory(state, max_queue_depth=100, tenant_quota=2)
        d2 = daemon_factory(state, max_queue_depth=100, tenant_quota=2)
        td = str(tmp_path)
        c1 = ServeClient(endpoint=f"http://127.0.0.1:{d1.port}",
                         token=d1.token)
        c2 = ServeClient(endpoint=f"http://127.0.0.1:{d2.port}",
                         token=d2.token)
        payloads = [
            _sleep_vol_job(td, f"tq{i}", 3.0, tenant="noisy")
            for i in range(6)
        ]
        accepted, rejected = self._burst([c1, c2], payloads)
        # 2 daemons x quota 2 would be 4 under per-daemon admission;
        # fleet-wide it is exactly the one quota
        assert len(accepted) == 2, (accepted, rejected)
        assert all("quota" in r for r in rejected)
        for jid in accepted:
            st = c1.wait(jid, timeout_s=180)
            assert st["result"]["ok"]

    def test_healthz_exports_admission_inputs_and_fleet(
        self, tmp_path, daemon_factory
    ):
        state = tmp_path / "state"
        d = daemon_factory(state, max_queue_depth=7, tenant_quota=4,
                           daemon_id="hz-daemon")
        client = ServeClient(state_dir=str(state))
        jid = client.submit(**_submit_kw(
            _sleep_vol_job(str(tmp_path), "hz", 1.5, tenant="acme")))
        hz = client.healthz()
        adm = hz["admission"]
        assert adm["max_queue_depth"] == 7 and adm["tenant_quota"] == 4
        assert adm["in_flight"] == 1 and adm["per_tenant"] == {"acme": 1}
        assert "queued" in adm
        fl = hz["fleet"]
        assert fl["id"] == "hz-daemon" and hz["daemon_id"] == "hz-daemon"
        assert fl["peers"] == 1 and fl["daemons"] == ["hz-daemon"]
        assert fl["scale_advice"]["action"] in ("spawn", "drain", "hold")
        assert d.daemon_id == "hz-daemon"
        client.wait(jid, timeout_s=180)

    def test_late_joining_daemon_drains_backlog(
        self, tmp_path, daemon_factory
    ):
        """The elastic story: one daemon saturates, scale_advice says
        spawn, a late joiner over the same state dir picks up queued
        work with no handshake."""
        state = tmp_path / "state"
        td = str(tmp_path)
        d1 = daemon_factory(state, daemon_id="first", tenant_quota=100)
        client = ServeClient(endpoint=f"http://127.0.0.1:{d1.port}",
                             token=d1.token)
        blocker = client.submit(**_submit_kw(
            _sleep_vol_job(td, "el_block", 3.0)))
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if client.status(blocker)["state"] == "running":
                break
            time.sleep(0.05)
        queued = [
            client.submit(**_submit_kw(
                _sleep_vol_job(td, f"el{i}", 0.3, tenant=f"t{i}")))
            for i in range(4)
        ]
        adv = client.fleet()["scale_advice"]
        assert adv["action"] == "spawn", adv  # backlog 4 > capacity 1
        d2 = daemon_factory(state, daemon_id="late", tenant_quota=100)
        for jid in [blocker] + queued:
            st = client.wait(jid, timeout_s=180)
            assert st["result"]["ok"]
        q = JobQueue(str(state / "jobs"))
        owners = {q.get(j)["result"]["daemon"] for j in queued}
        assert "late" in owners, (
            f"the late joiner never executed anything: {owners}"
        )
        assert d2.daemon_id == "late"
        # drained: the advice stops asking for capacity
        adv = client.fleet()["scale_advice"]
        assert adv["action"] in ("drain", "hold"), adv


# --------------------------------------------------------------------------
# cross-host work stealing: WorkQueue over an object store


class TestWorkQueueObjectStore:
    def test_publish_once_is_create_only_put(self, tmp_path):
        with StubObjectStore(str(tmp_path / "root")) as srv:
            key = f"{srv.url}/q/lease.0.g0.json"
            assert publish_once(key, b"first") is True
            assert publish_once(key, b"second") is False  # 412, lost race
            from cluster_tools_tpu.utils.store_backend import backend_for
            assert backend_for(key).read_bytes(key) == b"first"

    def test_exactly_once_over_object_store_with_chaos(self, tmp_path):
        """Two WorkQueue handles over ONE remote queue dir, seeded 503s
        on the store AND injected sched.claim stalls widening the
        selection->PUT window: conditional-PUT exclusivity must hand
        every item to exactly one owner."""
        with StubObjectStore(str(tmp_path / "root"), fail_rate=0.05,
                             seed=7) as srv:
            qdir = f"{srv.url}/jobdir_queue"
            q = WorkQueue.create(qdir, "t", list(range(12)), 2, 5.0,
                                 duplicate=False)
            assert q.task == "t"
            workers = [WorkQueue(qdir), WorkQueue(qdir)]
            owned = {0: [], 1: []}
            faults.configure("sched.claim:stall:p=0.4,s=0.01;seed=3")
            try:
                def drain_one(w):
                    wq = workers[w]
                    while True:
                        claim = wq.claim(job_id=w)
                        if claim is None:
                            break
                        owned[w].append(claim.item)
                        wq.complete(claim, claim.block_ids, [], {}, 0.001,
                                    job_id=w)

                threads = [
                    threading.Thread(target=drain_one, args=(w,))
                    for w in (0, 1)
                ]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(timeout=120)
            finally:
                faults.reset()
            assert not (set(owned[0]) & set(owned[1]))  # exclusive claims
            assert sorted(owned[0] + owned[1]) == list(range(len(q.items)))
            done, failed, errors, _ = q.aggregate()
            assert failed == [] and errors == {}
            assert sorted(done) == sorted(
                b for item in q.items for b in item
            )
            # every lease is gen 0: nothing was lost OR doubly executed
            names = workers[0]._backend.listdir(qdir)
            leases = [n for n in names if n.startswith("lease.")]
            assert len(leases) == len(q.items)
            assert all(n.endswith(".g0.json") for n in leases)

    def test_steal_queue_url_routes_queue_to_store(self, tmp_path):
        """The config seam cluster_executor rides: steal_queue_url puts
        the queue dir on the object store, named after the job dir."""
        from cluster_tools_tpu.runtime.cluster_executor import (
            ClusterExecutor,
        )

        with StubObjectStore(str(tmp_path / "root")) as srv:
            job_dir = str(tmp_path / "tmp_x" / "myjob")
            os.makedirs(job_dir)

            class _Task:
                identifier = "t"

            conf = {"steal_queue_url": srv.url}
            # _create_queue never touches self — exercise the seam
            # without standing up a scheduler
            q = ClusterExecutor._create_queue(
                None, _Task(), job_dir, list(range(4)), conf, 2)
            assert q.dir == f"{srv.url}/myjob_queue"
            assert q.claim(job_id=0) is not None
            # stale re-create rebuilds the remote dir (fresh leases)
            q2 = ClusterExecutor._create_queue(
                None, _Task(), job_dir, list(range(4)), conf, 2)
            assert q2.claim(job_id=0) is not None


# --------------------------------------------------------------------------
# chaos gate: SIGKILL a daemon mid-run, zero loss, fast recovery


def _spawn_daemon(state_dir, daemon_id, extra_env=None, args=()):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "CTT_HEARTBEAT_S": "0.2"}
    env.pop("CTT_TRACE_DIR", None)
    env.pop("CTT_RUN_ID", None)
    if extra_env:
        env.update(extra_env)
    proc = subprocess.Popen(
        [sys.executable, "-m", "cluster_tools_tpu.serve",
         "--state-dir", str(state_dir), "--lease-s", "5",
         "--daemon-id", daemon_id, *args],
        env=env, cwd=REPO,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    # line 1 is the listening banner, line 2 the endpoint JSON — per-
    # daemon discovery (serve.json in a shared state dir is last-writer)
    proc.stdout.readline()
    ep_line = proc.stdout.readline()
    if not ep_line:
        raise AssertionError(
            f"daemon {daemon_id} died at startup:\n{proc.stderr.read()}"
        )
    ep = json.loads(ep_line)
    client = ServeClient(endpoint=f"http://{ep['host']}:{ep['port']}",
                         token=ep["token"])
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        try:
            client.healthz()
            return proc, client, ep
        except Exception:
            if proc.poll() is not None:
                raise AssertionError(
                    f"daemon {daemon_id} died:\n{proc.stderr.read()}"
                ) from None
            time.sleep(0.1)
    proc.kill()
    raise AssertionError(f"daemon {daemon_id} never became healthy")


def _read_beat(state_dir, daemon_id):
    try:
        return json.load(open(beat_path(str(state_dir), daemon_id)))
    except (OSError, json.JSONDecodeError):
        return {}


@pytest.mark.timeout(300)
class TestFleetChaos:
    def test_sigkill_mid_run_zero_loss_byte_identical(self, tmp_path):
        """The acceptance gate: two real daemons, a 6-job burst, SIGKILL
        one mid-job.  Every job publishes an ok result, the recovered
        job re-executes byte-identically, and recovery latency is
        bounded by the heartbeat rule (3 x 0.2s cadence) — NOT the
        15s lease-staleness window (--lease-s 5)."""
        state = tmp_path / "state"
        td = str(tmp_path)
        proc_a = proc_b = None
        try:
            proc_a, client_a, _ = _spawn_daemon(state, "dA")
            proc_b, client_b, _ = _spawn_daemon(state, "dB")
            jobs = []
            for i in range(6):
                cl = client_a if i % 2 == 0 else client_b
                jobs.append(cl.submit(**_submit_kw(
                    _sleep_vol_job(td, f"k{i}", 2.0, tenant=f"t{i}"))))
            # wait until dA's own beat reports a job in flight ...
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                if _read_beat(state, "dA").get("running_jobs", 0) >= 1:
                    break
                time.sleep(0.05)
            assert _read_beat(state, "dA").get("running_jobs", 0) >= 1
            # ... and SIGKILL it mid-job: no drain, no exiting beat
            proc_a.kill()
            proc_a.wait(timeout=30)
            t_kill = time.time()
            # zero loss: every job reaches an ok result via the survivor
            for jid in jobs:
                st = client_b.wait(jid, timeout_s=180)
                assert st["result"]["ok"], st
            q = JobQueue(str(state / "jobs"), lease_s=5.0)
            results = [q.get(j)["result"] for j in jobs]
            requeued = [r for r in results if r["gen"] > 0]
            assert requeued, "the killed daemon's job never requeued"
            for r in requeued:
                assert r["daemon"] == "dB"
                # heartbeat-bounded recovery: detect at ~0.6s, re-execute
                # 2s — far inside the 15s the lease rule alone would take
                assert r["finished_wall"] - t_kill < 12.0, r
            # byte-identical recovery: all 6 outputs (same input) match,
            # including the re-executed one
            digests = {
                _digest(os.path.join(td, f"k{i}.n5", "y"))
                for i in range(6)
            }
            assert len(digests) == 1, digests
            # the survivor's ledger shows the fast-path reclaim
            text = client_b.metrics_text()
            vals = {
                ln.split(" ")[0]: float(ln.split(" ")[1])
                for ln in text.splitlines()
                if ln and not ln.startswith("#") and " " in ln
            }
            assert vals.get("ctt_serve_jobs_reclaimed_total", 0) >= 1
            assert vals.get("ctt_serve_jobs_quarantined_total", 0) == 0
        finally:
            for proc in (proc_a, proc_b):
                if proc is not None and proc.poll() is None:
                    proc.kill()
                    proc.wait(timeout=30)

    @pytest.mark.slow
    @pytest.mark.timeout(600)
    def test_poison_job_quarantined_across_respawns(self, tmp_path):
        """A job that kills every daemon that executes it (CTT_FAULTS
        executor kill) burns exactly max_job_gens generations across
        respawned daemons, then parks as quarantined — and the next
        (healthy) daemon keeps serving other work."""
        state = tmp_path / "state"
        td = str(tmp_path)
        poison_env = {"CTT_FAULTS": "executor.block:kill:once;seed=1"}
        gens_args = ("--max-job-gens", "2")
        proc = None
        try:
            proc, client, _ = _spawn_daemon(
                state, "p0", extra_env=poison_env, args=gens_args)
            jid = client.submit(**_submit_kw(
                _sleep_vol_job(td, "poison", 0.01)))
            proc.wait(timeout=120)  # gen 0 kills the daemon
            proc, client, _ = _spawn_daemon(
                state, "p1", extra_env=poison_env, args=gens_args)
            proc.wait(timeout=120)  # gen 1 kills its successor too
            # budget burned: a healthy daemon quarantines instead of dying
            proc, client, _ = _spawn_daemon(state, "p2", args=gens_args)
            deadline = time.monotonic() + 120
            res = None
            while time.monotonic() < deadline:
                st = client.status(jid)
                if st["state"] == "failed":
                    res = st["result"]
                    break
                time.sleep(0.2)
            assert res is not None, "poison job never quarantined"
            assert res["quarantined"] is True
            assert [e["gen"] for e in res["failure_log"]] == [0, 1]
            assert {e["daemon"] for e in res["failure_log"]} == {"p0", "p1"}
            # the daemon that quarantined is alive and still serves
            st = client.submit(**_submit_kw(
                _sleep_vol_job(td, "healthy", 0.01)))
            assert client.wait(st, timeout_s=180)["result"]["ok"]
            text = client.metrics_text()
            vals = {
                ln.split(" ")[0]: float(ln.split(" ")[1])
                for ln in text.splitlines()
                if ln and not ln.startswith("#") and " " in ln
            }
            assert vals.get("ctt_serve_jobs_quarantined_total", 0) >= 1
        finally:
            if proc is not None and proc.poll() is None:
                proc.kill()
                proc.wait(timeout=30)
