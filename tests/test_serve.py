"""ctt-serve: persistent serving daemon tests.

Covers the submission/execution split end to end:

  * ExecutionContext: process singleton, explicit contexts through
    ``build()``, install() for long-lived hosts;
  * the durable job queue: priority claim order, first-writer-wins
    results, stale-lease requeue at gen+1 (daemon death recovery);
  * admission: queue-depth and per-tenant quota rejections (429 on the
    wire, ``serve.quota_rejections`` counter);
  * byte-identity: a daemon-submitted watershed produces chunk-for-chunk
    identical output to ``build()`` in a fresh process;
  * liveness: mid-job client disconnect survives, /metrics parses as
    OpenMetrics, ``obs watch`` renders the serve health line;
  * SIGTERM drain (subprocess): the in-flight job finishes, queued jobs
    stay durable, the heartbeat carries ``draining``, and a restarted
    daemon over the same state dir completes the leftovers.
"""

import hashlib
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from cluster_tools_tpu.obs import metrics as obs_metrics
from cluster_tools_tpu.obs import trace as obs_trace
from cluster_tools_tpu.runtime import ExecutionContext, build
from cluster_tools_tpu.serve import (
    JobQueue, QuotaRejected, ServeClient, ServeDaemon,
)
from cluster_tools_tpu.serve.client import read_endpoint
from cluster_tools_tpu.serve.admission import AdmissionController
from cluster_tools_tpu.serve.protocol import (
    ProtocolError, job_signature, resolve_workflow, validate_submission,
)
from cluster_tools_tpu.utils import file_reader

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WS_CONFIG = {
    "threshold": 0.5, "sigma_seeds": 1.6, "size_filter": 10,
    "halo": [2, 4, 4],
}


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


def _ws_volume(seed=0, shape=(16, 32, 32)):
    from scipy import ndimage

    rng = np.random.default_rng(seed)
    raw = ndimage.gaussian_filter(rng.random(shape), (1.0, 2.0, 2.0))
    return (
        (raw - raw.min()) / (raw.max() - raw.min())
    ).astype("float32")


def _sleep_vol_job(td, tag, sleep_s, tenant="default", priority=0):
    """A submission payload for a calibrated-cost job (the ctt-steal
    skewed-cost fixture task, resolved by dotted path): one block, every
    block costs ``sleep_s``."""
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


@pytest.fixture
def daemon_factory(tmp_path):
    """In-process daemons with tracing scoped to this test (the daemon
    would otherwise flip the process-global trace switch on for the rest
    of the session)."""
    was_on = obs_trace.enabled()
    if not was_on:
        obs_trace.enable(str(tmp_path / "trace"), "serve_test",
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
                t.join(timeout=30)
    if not was_on:
        obs_trace.disable()


# --------------------------------------------------------------------------
# ExecutionContext


class TestExecutionContext:
    def test_process_context_singleton_idempotent(self):
        a = ExecutionContext.process_context()
        b = ExecutionContext.process_context()
        assert a is b
        assert a.activate() is a
        desc = a.describe()
        assert desc["activated"] and desc["pid"] == os.getpid()
        assert a.local_device_count() >= 1
        assert desc["chunk_cache_budget_bytes"] >= 0

    def test_install_makes_context_process_wide(self):
        prev = ExecutionContext.process_context()
        ctx = ExecutionContext(role="serve")
        try:
            assert ctx.install() is ctx
            assert ExecutionContext.process_context() is ctx
            assert ctx.describe()["role"] == "serve"
        finally:
            prev.install()

    def test_build_threads_explicit_context(self, tmp_path):
        from cluster_tools_tpu.runtime import config as cfg
        from cluster_tools_tpu.workflows import UniqueWorkflow

        path = str(tmp_path / "d.n5")
        rng = np.random.default_rng(0)
        file_reader(path).create_dataset(
            "seg", data=rng.integers(0, 9, (8, 16, 16)).astype(np.uint64),
            chunks=(4, 8, 8),
        )
        config_dir = str(tmp_path / "configs")
        cfg.write_global_config(config_dir, {"block_shape": [4, 8, 8]})
        ctx = ExecutionContext().activate()
        n0 = ctx.builds_executed
        wf = UniqueWorkflow(
            str(tmp_path / "tmp"), config_dir,
            input_path=path, input_key="seg",
            output_path=path, output_key="u",
        )
        assert build([wf], context=ctx)
        assert ctx.builds_executed == n0 + 1
        with file_reader(path, "r") as f:
            assert f["u"][:].size > 0


# --------------------------------------------------------------------------
# protocol


class TestProtocol:
    def test_validate_submission_normalizes_and_rejects(self):
        rec = validate_submission({
            "workflow": " WatershedWorkflow ",
            "kwargs": {"tmp_folder": "/t"},
        })
        assert rec["workflow"] == "WatershedWorkflow"
        assert rec["tenant"] == "default" and rec["priority"] == 0
        for bad in (
            [],                                        # not an object
            {},                                        # no workflow
            {"workflow": "X"},                         # no tmp_folder
            {"workflow": "X", "kwargs": {"tmp_folder": "/t"},
             "priority": "high"},                      # bad priority
            {"workflow": "X", "kwargs": {"tmp_folder": "/t"},
             "configs": {"global": {}}},               # configs, no dir
        ):
            with pytest.raises(ProtocolError):
                validate_submission(bad)

    def test_resolve_workflow_catalog_and_dotted(self):
        from cluster_tools_tpu.workflows import WatershedWorkflow

        assert resolve_workflow("WatershedWorkflow") is WatershedWorkflow
        cls = resolve_workflow("bench_e2e_lib:SkewedCostTask")
        assert cls.task_name == "skewed_cost"
        for bad in ("NoSuchWorkflow", "nope.nope:Missing",
                    "json:JSONDecoder"):
            with pytest.raises(ProtocolError):
                resolve_workflow(bad)

    def test_job_signature_keys_on_workflow_and_block_shape(self):
        a = job_signature({"workflow": "W",
                           "configs": {"global": {"block_shape": [4, 8, 8]}}})
        b = job_signature({"workflow": "W",
                           "configs": {"global": {"block_shape": [4, 8, 8]}}})
        c = job_signature({"workflow": "W",
                           "configs": {"global": {"block_shape": [8, 8, 8]}}})
        assert a == b and a != c


# --------------------------------------------------------------------------
# durable job queue


class TestJobQueue:
    def test_submit_claim_priority_order_and_states(self, tmp_path):
        q = JobQueue(str(tmp_path / "jobs"), lease_s=5.0)
        j1 = q.submit({"workflow": "A", "tenant": "t", "priority": 0})
        j2 = q.submit({"workflow": "B", "tenant": "t", "priority": 5})
        j3 = q.submit({"workflow": "C", "tenant": "t", "priority": 5})
        assert [j1, j2, j3] == ["j000001", "j000002", "j000003"]
        assert q.get(j1)["state"] == "queued"
        # claim order: priority desc, then submission sequence
        c = q.claim_next()
        assert c.job_id == j2 and c.gen == 0
        assert q.get(j2)["state"] == "running"
        assert q.claim_next().job_id == j3
        assert q.claim_next().job_id == j1
        assert q.claim_next() is None
        assert q.complete(c, {"ok": True, "seconds": 0.1})
        # first writer wins: a duplicate completion is a no-op
        assert not q.complete(c, {"ok": False, "seconds": 9.9})
        st = q.get(j2)
        assert st["state"] == "done" and st["result"]["ok"]
        stats = q.stats()
        assert stats["in_flight"] == 2 and stats["per_tenant"] == {"t": 2}

    def test_stale_lease_requeues_at_next_generation(self, tmp_path):
        was_on = obs_trace.enabled()
        if not was_on:
            obs_trace.enable(str(tmp_path / "trace"), "serve_unit",
                             export_env=False)
        try:
            q = JobQueue(str(tmp_path / "jobs"), lease_s=0.2)
            jid = q.submit({"workflow": "A", "tenant": "t", "priority": 0})
            claim = q.claim_next()
            assert claim.gen == 0
            # a second daemon sees a live lease: nothing claimable
            q2 = JobQueue(str(tmp_path / "jobs"), lease_s=0.2)
            assert q2.claim_next() is None
            # the owner dies: its lease stamp ages past 3 x lease_s
            lease = json.load(open(claim.lease_path))
            lease["wall"] -= 3600.0
            with open(claim.lease_path, "w") as f:
                json.dump(lease, f)
            before = obs_metrics.snapshot()["counters"]
            takeover = q2.claim_next()
            assert takeover is not None and takeover.job_id == jid
            assert takeover.gen == 1
            after = obs_metrics.snapshot()["counters"]
            assert after.get("serve.leases_requeued", 0) > before.get(
                "serve.leases_requeued", 0
            )
            assert q2.complete(takeover, {"ok": True, "seconds": 0.1})
            assert q2.get(jid)["state"] == "done"
        finally:
            if not was_on:
                obs_trace.disable()

    def test_renew_restamps_wall(self, tmp_path):
        q = JobQueue(str(tmp_path / "jobs"), lease_s=1.0)
        q.submit({"workflow": "A", "tenant": "t", "priority": 0})
        claim = q.claim_next()
        before = json.load(open(claim.lease_path))
        time.sleep(0.05)
        q.renew(claim)
        after = json.load(open(claim.lease_path))
        assert after["wall"] > before["wall"]
        assert after["claim_wall"] == pytest.approx(before["claim_wall"])


# --------------------------------------------------------------------------
# admission


class TestAdmission:
    def test_queue_depth_and_tenant_quota(self):
        adm = AdmissionController(
            max_queue_depth=3, tenant_quota=2, tenant_quotas={"big": 3}
        )
        ok, _ = adm.admit("a", {"in_flight": 0, "per_tenant": {}})
        assert ok
        ok, reason = adm.admit("a", {"in_flight": 3, "per_tenant": {}})
        assert not ok and "queue full" in reason
        ok, reason = adm.admit(
            "a", {"in_flight": 2, "per_tenant": {"a": 2}}
        )
        assert not ok and "quota" in reason
        # per-tenant override: "big" rides its own ceiling
        ok, _ = adm.admit("big", {"in_flight": 2, "per_tenant": {"big": 2}})
        assert ok
        # disabled gates admit everything
        open_adm = AdmissionController(None, None)
        ok, _ = open_adm.admit("a", {"in_flight": 999,
                                     "per_tenant": {"a": 999}})
        assert ok

    def test_zero_limits_mean_admit_nothing(self):
        """0 is a real ceiling, not a truthy-falsy 'unlimited': only
        None disables a gate."""
        adm = AdmissionController(max_queue_depth=0, tenant_quota=None)
        ok, reason = adm.admit("a", {"in_flight": 0, "per_tenant": {}})
        assert not ok and "queue full" in reason
        adm = AdmissionController(max_queue_depth=None, tenant_quota=0)
        ok, reason = adm.admit("a", {"in_flight": 0, "per_tenant": {}})
        assert not ok and "quota" in reason


# --------------------------------------------------------------------------
# daemon end-to-end (in process)


class TestServeDaemon:
    def test_byte_identical_to_fresh_process_build(
        self, tmp_path, daemon_factory
    ):
        """The acceptance contract: daemon-submitted execution is
        byte-identical (incl. chunk digests) to build() in a fresh
        process — only the setup cost differs."""
        raw = _ws_volume()
        paths = {}
        for tag in ("cold", "serve"):
            p = str(tmp_path / f"{tag}.n5")
            file_reader(p).create_dataset(
                "bnd", data=raw, chunks=(8, 16, 16)
            )
            paths[tag] = p

        # fresh process: the cold path every workflow run paid before
        driver = tmp_path / "cold_driver.py"
        driver.write_text(
            "import os, sys\n"
            "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
            f"sys.path.insert(0, {REPO!r})\n"
            "from cluster_tools_tpu.runtime import build, config as cfg\n"
            "from cluster_tools_tpu.workflows import WatershedWorkflow\n"
            f"td = {str(tmp_path)!r}\n"
            "config_dir = os.path.join(td, 'configs_cold')\n"
            "cfg.write_global_config(config_dir,"
            " {'block_shape': [8, 16, 16]})\n"
            f"cfg.write_config(config_dir, 'watershed', {WS_CONFIG!r})\n"
            "wf = WatershedWorkflow(\n"
            "    os.path.join(td, 'tmp_cold'), config_dir,\n"
            f"    input_path={paths['cold']!r}, input_key='bnd',\n"
            f"    output_path={paths['cold']!r}, output_key='ws')\n"
            "assert build([wf])\n"
        )
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        env.pop("CTT_TRACE_DIR", None)
        proc = subprocess.run(
            [sys.executable, str(driver)], capture_output=True, text=True,
            env=env, cwd=REPO,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]

        daemon = daemon_factory(tmp_path / "serve_state")
        client = ServeClient(state_dir=str(tmp_path / "serve_state"))
        state = client.submit_and_wait(
            "WatershedWorkflow",
            {
                "tmp_folder": str(tmp_path / "tmp_serve"),
                "config_dir": str(tmp_path / "configs_serve"),
                "input_path": paths["serve"], "input_key": "bnd",
                "output_path": paths["serve"], "output_key": "ws",
            },
            configs={"global": {"block_shape": [8, 16, 16]},
                     "watershed": dict(WS_CONFIG)},
            timeout_s=300,
        )
        assert state["state"] == "done" and state["result"]["ok"]

        with file_reader(paths["cold"], "r") as fc, \
                file_reader(paths["serve"], "r") as fs:
            np.testing.assert_array_equal(fs["ws"][:], fc["ws"][:])
        assert _digest(os.path.join(paths["serve"], "ws")) == _digest(
            os.path.join(paths["cold"], "ws")
        ), "daemon output chunks not byte-identical to the fresh process"
        assert daemon.healthz()["context"]["builds_executed"] >= 1

    def test_warm_cold_accounting_and_metrics(
        self, tmp_path, daemon_factory
    ):
        daemon = daemon_factory(tmp_path / "state")
        client = ServeClient(state_dir=str(tmp_path / "state"))
        td = str(tmp_path)
        s1 = client.submit_and_wait(**_submit_kw(
            _sleep_vol_job(td, "w1", 0.01)), timeout_s=120)
        s2 = client.submit_and_wait(**_submit_kw(
            _sleep_vol_job(td, "w2", 0.01)), timeout_s=120)
        assert not s1["result"]["warm"], "first signature must be cold"
        assert s2["result"]["warm"], "repeat signature must be warm"
        text = client.metrics_text()
        assert text.rstrip().endswith("# EOF")
        lines = {
            l.split(" ")[0]: float(l.split(" ")[1])
            for l in text.splitlines()
            if l and not l.startswith("#") and " " in l
        }
        assert lines.get("ctt_serve_jobs_done_total", 0) >= 2
        assert lines.get("ctt_serve_warm_compile_jobs_total", 0) >= 1
        assert lines.get("ctt_serve_cold_compile_jobs_total", 0) >= 1
        try:
            from prometheus_client.openmetrics.parser import (
                text_string_to_metric_families,
            )
            assert list(text_string_to_metric_families(text))
        except ImportError:
            pass

    def test_quota_rejection_and_requeue_after_finish(
        self, tmp_path, daemon_factory
    ):
        daemon_factory(
            tmp_path / "state", tenant_quota=1, max_queue_depth=2
        )
        client = ServeClient(state_dir=str(tmp_path / "state"))
        td = str(tmp_path)
        blocker = client.submit(**_submit_kw(
            _sleep_vol_job(td, "q1", 1.5, tenant="t1")))
        _wait_state(client, blocker, "running")
        # tenant t1 is at quota while its job runs
        with pytest.raises(QuotaRejected) as exc:
            client.submit(**_submit_kw(
                _sleep_vol_job(td, "q2", 0.01, tenant="t1")))
        assert "quota" in str(exc.value)
        # another tenant still fits (queue depth 2: 1 running + 1 queued)
        other = client.submit(**_submit_kw(
            _sleep_vol_job(td, "q3", 0.01, tenant="t2")))
        # ... and now the queue itself is full for everyone
        with pytest.raises(QuotaRejected) as exc:
            client.submit(**_submit_kw(
                _sleep_vol_job(td, "q4", 0.01, tenant="t3")))
        assert "queue full" in str(exc.value)
        client.wait(blocker, timeout_s=120)
        client.wait(other, timeout_s=120)
        # capacity freed: the rejected tenant resubmits successfully
        done = client.submit_and_wait(**_submit_kw(
            _sleep_vol_job(td, "q5", 0.01, tenant="t1")), timeout_s=120)
        assert done["result"]["ok"]

    def test_priority_orders_claims(self, tmp_path, daemon_factory):
        daemon_factory(tmp_path / "state")  # concurrency 1 (default)
        client = ServeClient(state_dir=str(tmp_path / "state"))
        td = str(tmp_path)
        blocker = client.submit(**_submit_kw(
            _sleep_vol_job(td, "p0", 1.5)))
        _wait_state(client, blocker, "running")
        low = client.submit(**_submit_kw(
            _sleep_vol_job(td, "p_low", 0.01, priority=0)))
        high = client.submit(**_submit_kw(
            _sleep_vol_job(td, "p_high", 0.01, priority=10)))
        client.wait(blocker, timeout_s=120)
        s_low = client.wait(low, timeout_s=120)
        s_high = client.wait(high, timeout_s=120)
        assert (
            s_high["result"]["finished_wall"]
            < s_low["result"]["finished_wall"]
        ), "higher priority must claim (and finish) first"

    def test_mid_job_client_disconnect_survives(
        self, tmp_path, daemon_factory
    ):
        daemon = daemon_factory(tmp_path / "state")
        client = ServeClient(state_dir=str(tmp_path / "state"))
        td = str(tmp_path)
        job = client.submit(**_submit_kw(_sleep_vol_job(td, "d1", 1.0)))
        _wait_state(client, job, "running")
        # a client tears its connection mid-request while the job runs
        for payload in (b"", b"POST /api/v1/jobs HTTP/1.1\r\nContent-"):
            s = socket.create_connection(("127.0.0.1", daemon.port), 5)
            if payload:
                s.sendall(payload)
            s.close()
        # the daemon neither died nor lost the job
        assert client.healthz()["ok"]
        state = client.wait(job, timeout_s=120)
        assert state["result"]["ok"]

    def test_requests_require_daemon_token(self, tmp_path, daemon_factory):
        """The auth gate: serve.json is 0600 and carries the token; a
        tokenless caller gets 401 everywhere but /healthz — never
        reaching workflow resolution (arbitrary imports) in particular."""
        daemon = daemon_factory(tmp_path / "state")
        state_dir = str(tmp_path / "state")
        ep = read_endpoint(state_dir)
        assert ep["token"] == daemon.token
        mode = os.stat(os.path.join(state_dir, "serve.json")).st_mode
        assert mode & 0o777 == 0o600
        base = f"http://{ep['host']}:{ep['port']}"
        # tokenless liveness probe stays open
        with urllib.request.urlopen(base + "/healthz", timeout=10) as resp:
            assert json.loads(resp.read())["ok"]
        # everything else answers 401 without the token
        for method, path, data in (
            ("GET", "/api/v1/jobs", None),
            ("GET", "/metrics", None),
            ("POST", "/api/v1/jobs",
             json.dumps(_sleep_vol_job(str(tmp_path), "auth", 0.01))
             .encode()),
        ):
            req = urllib.request.Request(
                base + path, data=data, method=method,
                headers={"Content-Type": "application/json"},
            )
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(req, timeout=10)
            assert exc.value.code == 401, (method, path)
        # the file-discovered client carries the token on every call
        client = ServeClient(state_dir=state_dir)
        assert client.token == daemon.token
        assert client.list_jobs() == []
        assert client.metrics_text().rstrip().endswith("# EOF")
        # Bearer form works too (prometheus-style authorization)
        req = urllib.request.Request(
            base + "/api/v1/jobs",
            headers={"Authorization": f"Bearer {daemon.token}"},
        )
        with urllib.request.urlopen(req, timeout=10) as resp:
            assert json.loads(resp.read()) == {"jobs": []}

    def test_lease_renewer_threads_stop_with_jobs(
        self, tmp_path, daemon_factory
    ):
        """Each job's lease renewer must die with the job — a persistent
        daemon otherwise accumulates one immortal thread per job."""
        daemon_factory(tmp_path / "state")
        client = ServeClient(state_dir=str(tmp_path / "state"))
        td = str(tmp_path)
        for i in range(3):
            state = client.submit_and_wait(**_submit_kw(
                _sleep_vol_job(td, f"lr{i}", 0.01)), timeout_s=120)
            assert state["result"]["ok"]
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            alive = [t for t in threading.enumerate()
                     if t.name == "ctt-serve-lease" and t.is_alive()]
            if not alive:
                break
            time.sleep(0.05)
        assert not alive, f"leaked lease renewers: {alive}"

    def test_watch_renders_serve_line(self, tmp_path, daemon_factory):
        from cluster_tools_tpu.obs.live import LiveRun, format_watch

        daemon_factory(tmp_path / "state")
        client = ServeClient(state_dir=str(tmp_path / "state"))
        client.submit_and_wait(**_submit_kw(
            _sleep_vol_job(str(tmp_path), "w", 0.01)), timeout_s=120)
        obs_metrics.flush()
        snap = LiveRun(obs_trace.run_dir()).poll()
        text = format_watch(snap)
        assert "serve:" in text and "done 1" in text


def _submit_kw(payload):
    return {
        "workflow": payload["workflow"],
        "kwargs": payload["kwargs"],
        "configs": payload["configs"],
        "tenant": payload["tenant"],
        "priority": payload["priority"],
    }


def _wait_state(client, job_id, state, timeout_s=60.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if client.status(job_id)["state"] == state:
            return
        time.sleep(0.05)
    raise AssertionError(
        f"job {job_id} never reached {state!r}: "
        f"{client.status(job_id)['state']}"
    )


# --------------------------------------------------------------------------
# SIGTERM drain (real daemon process)


@pytest.mark.timeout(300)
class TestSigtermDrain:
    def _spawn(self, state_dir, extra_env=None):
        env = {**os.environ, "JAX_PLATFORMS": "cpu",
               "CTT_HEARTBEAT_S": "0.2"}
        env.pop("CTT_TRACE_DIR", None)
        env.pop("CTT_RUN_ID", None)
        if extra_env:
            env.update(extra_env)
        proc = subprocess.Popen(
            [sys.executable, "-m", "cluster_tools_tpu.serve",
             "--state-dir", str(state_dir), "--lease-s", "0.5"],
            env=env, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        deadline = time.monotonic() + 60
        ep_path = os.path.join(str(state_dir), "serve.json")
        while time.monotonic() < deadline:
            if os.path.exists(ep_path):
                try:
                    client = ServeClient(state_dir=str(state_dir))
                    client.healthz()
                    return proc, client
                except Exception:
                    pass
            if proc.poll() is not None:
                raise AssertionError(
                    f"daemon died at startup:\n{proc.stderr.read()}"
                )
            time.sleep(0.1)
        proc.kill()
        raise AssertionError("daemon never became healthy")

    def test_drain_finishes_running_keeps_queued_then_resumes(
        self, tmp_path
    ):
        state_dir = tmp_path / "state"
        td = str(tmp_path)
        proc, client = self._spawn(state_dir)
        try:
            running = client.submit(**_submit_kw(
                _sleep_vol_job(td, "r1", 2.0)))
            _wait_state(client, running, "running")
            queued = [
                client.submit(**_submit_kw(
                    _sleep_vol_job(td, f"g{i}", 0.01)))
                for i in range(2)
            ]
            proc.send_signal(signal.SIGTERM)
            # heartbeats keep landing DURING the drain: the SIGTERM
            # flush stops the beat thread, request_drain restarts it —
            # readers must see live draining beats (not `exiting`, not
            # staleness) while the in-flight job finishes
            run_dir = os.path.join(
                str(state_dir), "trace",
                json.load(open(state_dir / "serve.json"))["run_id"],
            )

            def read_hb():
                names = [n for n in os.listdir(run_dir)
                         if n.startswith("hb.p")]
                assert names, os.listdir(run_dir)
                return json.load(open(os.path.join(run_dir, names[0])))

            draining_beats = []
            deadline = time.monotonic() + 1.5
            while time.monotonic() < deadline:
                try:
                    hb = read_hb()
                except (OSError, json.JSONDecodeError):
                    hb = None
                if (
                    hb
                    and hb.get("draining")
                    and not hb.get("exiting")
                    and hb["seq"] not in [b["seq"] for b in draining_beats]
                ):
                    draining_beats.append(hb)
                    if len(draining_beats) >= 2:
                        break
                time.sleep(0.05)
            assert len(draining_beats) >= 2, (
                "heartbeat went silent during the drain: "
                f"{draining_beats}"
            )
            rc = proc.wait(timeout=120)
            assert rc == 0, (proc.stdout.read(), proc.stderr.read())
            # the in-flight job drained to a real result ...
            q = JobQueue(str(state_dir / "jobs"), lease_s=0.5)
            st = q.get(running)
            assert st["state"] == "done" and st["result"]["ok"], st
            # ... the queued jobs were not run and not lost ...
            for jid in queued:
                assert q.get(jid)["state"] == "queued"
            # ... and the heartbeat flagged the drain before exit
            hb = read_hb()
            assert hb["draining"] is True and hb["exiting"] is True
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=30)

        # a successor daemon over the same state dir completes the
        # leftovers — the disk is the queue
        proc2, client2 = self._spawn(state_dir)
        try:
            for jid in queued:
                state = client2.wait(jid, timeout_s=120)
                assert state["result"]["ok"]
        finally:
            proc2.send_signal(signal.SIGTERM)
            try:
                proc2.wait(timeout=60)
            except subprocess.TimeoutExpired:
                proc2.kill()
                proc2.wait(timeout=30)
