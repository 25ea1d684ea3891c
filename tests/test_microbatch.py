"""ctt-microbatch: cross-tenant job aggregation tests.

Covers the PR acceptance contract:

  * aggregation: a mixed-tenant burst of same-signature ``event_batch``
    jobs coalesces into stacked dispatches
    (``serve.microbatch_batches``/``serve.microbatch_jobs_batched``),
    every result carries the ``microbatch`` annotation, and the outputs
    are byte-identical — labels, event tables, chunk digests — to a
    window-0 daemon (exact per-job dispatch);
  * priority: a higher-priority job arriving DURING an open window joins
    the batch ahead of lower-priority queue residents (it gets batch
    index 0);
  * poison isolation (fail): an ``executor.block:fail`` member drops out
    of the batch, re-dispatches individually (``serve.microbatch_splits``),
    and fails ALONE — its batchmates publish ok from the same window;
  * poison isolation (kill, subprocess, slow): an ``executor.block:kill``
    member takes the daemon down mid-batch; across respawns the
    batchmates publish ok at gen 1 while only the culprit burns its
    retry budget and quarantines.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from cluster_tools_tpu import faults
from cluster_tools_tpu.obs import metrics as obs_metrics
from cluster_tools_tpu.obs import trace as obs_trace
from cluster_tools_tpu.serve import JobQueue, ServeClient, ServeDaemon
from cluster_tools_tpu.serve.protocol import microbatch_signature
from cluster_tools_tpu.tasks.events import read_event_tables
from cluster_tools_tpu.utils import file_reader

from test_serve import _digest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

GCONF = {
    "block_shape": [2, 16, 16], "target": "tpu",
    "device_batch_size": 2, "devices": [0], "pipeline_depth": 2,
}
# the poison tests run members on the local executor: its per-block
# ``executor.block`` fault seam fires on BOTH the stacked member pass and
# the solo re-dispatch, so a poisoned member fails (or kills) the same
# way wherever it runs
GCONF_LOCAL = {"block_shape": [2, 16, 16], "target": "local"}

THRESHOLD = 0.1


@pytest.fixture
def rng():
    return np.random.default_rng(11)


@pytest.fixture
def daemon_factory(tmp_path):
    """In-process daemons with tracing scoped to this test (mirrors
    tests/test_serve.py — the serve counters need the trace switch)."""
    obs_metrics.reset()
    was_on = obs_trace.enabled()
    if not was_on:
        obs_trace.enable(str(tmp_path / "trace"), "microbatch_test",
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
    obs_metrics.reset()


def _frames(rng, n=4, h=16, w=16):
    from scipy import ndimage

    raw = ndimage.gaussian_filter(
        rng.random((n, h, w)), (0.0, 1.0, 1.0)
    ).astype("float32")
    frames = np.where(raw > np.quantile(raw, 0.9), raw, 0.0)
    return frames.astype("float32")


def _write_frames(tmp_path, rng, tag, n=4):
    path = str(tmp_path / f"{tag}.n5")
    file_reader(path).create_dataset(
        "frames", data=_frames(rng, n=n), chunks=(2, 16, 16)
    )
    return path


def _submit_event(client, path, td, tag, gconf=GCONF, **kw):
    return client.event_batch(
        input_path=path, input_key="frames",
        output_path=path, output_key=f"ev_{tag}",
        tmp_folder=os.path.join(td, f"tmp_{tag}"),
        config_dir=os.path.join(td, f"configs_{tag}"),
        threshold=THRESHOLD,
        configs={"global": gconf},
        **kw,
    )


def _counters():
    return dict(obs_metrics.snapshot()["counters"])


def _delta(before, after, name):
    return after.get(name, 0.0) - before.get(name, 0.0)


def _wait_state(client, job_id, state, timeout_s=60.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if client.status(job_id)["state"] == state:
            return
        time.sleep(0.02)
    raise AssertionError(
        f"job {job_id} never reached {state!r}: "
        f"{client.status(job_id)['state']}"
    )


class TestSignature:
    def test_cross_tenant_same_signature(self):
        base = {
            "type": "event_batch", "workflow": "W", "configs": {},
            "kwargs": {"input_path": "/a"},
        }
        a = microbatch_signature({**base, "tenant": "alice"})
        b = microbatch_signature({**base, "tenant": "bob",
                                  "kwargs": {"input_path": "/b"}})
        assert a is not None and a == b, (
            "aggregation must be kwargs- and tenant-blind"
        )
        assert microbatch_signature({**base, "microbatch": False}) is None
        assert microbatch_signature({**base, "type": "ingest"}) is None
        assert (
            microbatch_signature({**base, "configs": {"global": {"x": 1}}})
            != a
        ), "different configs must never stack"


class TestAggregation:
    def test_burst_aggregates_and_stays_byte_identical(
        self, tmp_path, daemon_factory, rng
    ):
        """The tentpole gate: a 4-job mixed-tenant burst coalesces into
        stacked dispatches, and output bytes (labels, event tables,
        chunk digests) match a window-0 daemon exactly."""
        path = _write_frames(tmp_path, rng, "burst")
        td = str(tmp_path)
        n_blocks = 4 // GCONF["block_shape"][0]

        daemon_factory(tmp_path / "state_mb",
                       microbatch_window_s=2.0, microbatch_max_jobs=4)
        client = ServeClient(state_dir=str(tmp_path / "state_mb"))
        before = _counters()
        jobs = [
            _submit_event(client, path, td, f"mb{i}", tenant=f"t{i % 2}")
            for i in range(4)
        ]
        states = [client.wait(j, timeout_s=300) for j in jobs]
        after = _counters()

        annotations = []
        for st in states:
            assert st["result"]["ok"], st
            note = st["result"].get("microbatch")
            assert note is not None, (
                "an aggregated job's result must carry the microbatch "
                f"annotation: {st['result']}"
            )
            annotations.append((note["jobs"], note["index"]))
        assert any(jobs_n >= 2 for jobs_n, _ in annotations), annotations
        assert _delta(before, after, "serve.microbatch_batches") >= 1
        assert _delta(before, after, "serve.microbatch_jobs_batched") >= 2
        # per-member accounting: every member counted toward jobs_done,
        # exactly one burst member paid the cold compile
        assert _delta(before, after, "serve.jobs_done") == 4
        assert _delta(before, after, "serve.cold_compile_jobs") >= 1

        # control: window 0 = exact pre-aggregation behavior
        daemon_factory(tmp_path / "state_solo", microbatch_window_s=0.0)
        solo_client = ServeClient(state_dir=str(tmp_path / "state_solo"))
        b2 = _counters()
        solo_jobs = [
            _submit_event(solo_client, path, td, f"solo{i}",
                          tenant=f"t{i % 2}")
            for i in range(4)
        ]
        for j in solo_jobs:
            st = solo_client.wait(j, timeout_s=300)
            assert st["result"]["ok"]
            assert "microbatch" not in st["result"], (
                "window 0 must not annotate results"
            )
        assert _delta(b2, _counters(), "serve.microbatch_batches") == 0

        f = file_reader(path, "r")
        ref_labels = f["ev_solo0"][:]
        ref_tab = read_event_tables(path, "ev_solo0", n_blocks)
        for i in range(4):
            np.testing.assert_array_equal(f[f"ev_mb{i}"][:], ref_labels)
            np.testing.assert_array_equal(
                read_event_tables(path, f"ev_mb{i}", n_blocks), ref_tab
            )
            assert _digest(os.path.join(path, f"ev_mb{i}")) == _digest(
                os.path.join(path, f"ev_solo{i}")
            ), "stacked dispatch output chunks not byte-identical"

        # observability satellites: the counters ride /metrics and the
        # watch surface renders the batch: line
        text = client.metrics_text()
        vals = {
            ln.split(" ")[0]: float(ln.split(" ")[1])
            for ln in text.splitlines()
            if ln and not ln.startswith("#") and " " in ln
        }
        assert vals.get("ctt_serve_microbatch_batches_total", 0) >= 1
        assert vals.get("ctt_serve_microbatch_jobs_batched_total", 0) >= 2
        from cluster_tools_tpu.obs.live import LiveRun, format_watch

        obs_metrics.flush()
        watch = format_watch(LiveRun(obs_trace.run_dir()).poll())
        assert "serve:" in watch and "batch:" in watch
        assert "jobs/dispatch" in watch

    def test_priority_arrival_joins_window_ahead_of_residents(
        self, tmp_path, daemon_factory, rng
    ):
        """Members are claimed at window CLOSE in (-priority, seq)
        order: a high-priority job submitted while the window is open
        beats the lower-priority jobs already queued — batch index 0."""
        path = _write_frames(tmp_path, rng, "prio")
        td = str(tmp_path)
        # max_jobs 8 keeps early-fill out of reach: the window closes on
        # its deadline, after every submission below has landed
        daemon_factory(tmp_path / "state",
                       microbatch_window_s=2.0, microbatch_max_jobs=8)
        client = ServeClient(state_dir=str(tmp_path / "state"))
        first = _submit_event(client, path, td, "first", priority=0)
        # "running" == claimed == the window is open
        _wait_state(client, first, "running")
        lows = [
            _submit_event(client, path, td, f"low{i}", priority=0)
            for i in range(2)
        ]
        high = _submit_event(client, path, td, "high", priority=10)

        st_high = client.wait(high, timeout_s=300)
        note = st_high["result"].get("microbatch")
        assert note is not None and note["jobs"] == 4, st_high["result"]
        assert note["index"] == 0, (
            "the high-priority window arrival must head the batch: "
            f"{note}"
        )
        st_first = client.wait(first, timeout_s=300)
        assert st_first["result"]["microbatch"]["index"] == 1
        for j in lows:
            assert client.wait(j, timeout_s=300)["result"]["ok"]


class TestPoisonIsolation:
    def test_failed_member_splits_and_fails_alone(
        self, tmp_path, daemon_factory, rng
    ):
        """One member poisoned with ``executor.block:fail`` drops out of
        the batch at its own fault seam, re-dispatches individually
        (``serve.microbatch_splits``), and publishes the ONLY failure —
        both batchmates publish ok from the same window."""
        td = str(tmp_path)
        # culprit: 6 frames = blocks 0..2 (the fault targets id 2);
        # batchmates: 2 frames = block 0 only — the fault cannot touch them
        culprit_path = _write_frames(tmp_path, rng, "culprit", n=6)
        mate_path = _write_frames(tmp_path, rng, "mates", n=2)
        daemon_factory(tmp_path / "state",
                       microbatch_window_s=2.0, microbatch_max_jobs=3)
        client = ServeClient(state_dir=str(tmp_path / "state"))
        faults.configure("executor.block:fail:ids=2")
        try:
            before = _counters()
            culprit = _submit_event(client, culprit_path, td, "culprit",
                                    gconf=GCONF_LOCAL, tenant="bad")
            mates = [
                _submit_event(client, mate_path, td, f"mate{i}",
                              gconf=GCONF_LOCAL, tenant=f"t{i}")
                for i in range(2)
            ]
            st_bad = client.wait(culprit, timeout_s=300,
                                 raise_on_failure=False)
            assert st_bad["state"] == "failed", (
                "the poisoned member must fail its individual re-dispatch"
            )
            note = st_bad["result"].get("microbatch")
            assert note and note.get("split") is True, st_bad["result"]
            assert st_bad["result"]["error"], st_bad["result"]
            for j in mates:
                st = client.wait(j, timeout_s=300)
                assert st["result"]["ok"], (
                    f"batchmate caught the culprit's fault: {st}"
                )
                mate_note = st["result"].get("microbatch")
                assert mate_note and "split" not in mate_note, st["result"]
            after = _counters()
            assert _delta(before, after, "serve.microbatch_splits") >= 1
            assert _delta(before, after, "serve.jobs_failed") == 1
            assert _delta(before, after, "serve.jobs_done") == 2
        finally:
            faults.reset()


# --------------------------------------------------------------------------
# kill-poison quarantine across respawns (real daemon processes)


def _spawn_daemon(state_dir, daemon_id, extra_env=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "CTT_HEARTBEAT_S": "0.2"}
    env.pop("CTT_TRACE_DIR", None)
    env.pop("CTT_RUN_ID", None)
    if extra_env:
        env.update(extra_env)
    proc = subprocess.Popen(
        [sys.executable, "-m", "cluster_tools_tpu.serve",
         "--state-dir", str(state_dir), "--lease-s", "5",
         "--daemon-id", daemon_id, "--max-job-gens", "2",
         "--microbatch-window-s", "2.0", "--microbatch-max-jobs", "3"],
        env=env, cwd=REPO,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    proc.stdout.readline()  # listening banner
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
            return proc, client
        except Exception:
            if proc.poll() is not None:
                raise AssertionError(
                    f"daemon {daemon_id} died:\n{proc.stderr.read()}"
                ) from None
            time.sleep(0.1)
    proc.kill()
    raise AssertionError(f"daemon {daemon_id} never became healthy")


@pytest.mark.slow
@pytest.mark.timeout(600)
class TestKillPoisonQuarantine:
    def test_culprit_quarantines_alone_batchmates_publish_ok(
        self, tmp_path
    ):
        """The acceptance gate: a member that KILLS the daemon mid-batch
        (``executor.block:kill``) burns only its own retry budget.  The
        shared crash costs every member one generation, after which the
        fresh-gen-only rule makes everyone re-run SOLO: both batchmates
        publish ok at gen 1 while the culprit kills its next daemon too
        and quarantines at the budget."""
        state = tmp_path / "state"
        td = str(tmp_path)
        rng = np.random.default_rng(13)
        culprit_path = _write_frames(tmp_path, rng, "kculprit", n=6)
        mate_path = _write_frames(tmp_path, rng, "kmates", n=2)
        poison_env = {"CTT_FAULTS": "executor.block:kill:ids=2"}
        proc = None
        try:
            proc, client = _spawn_daemon(state, "m0", extra_env=poison_env)
            culprit = _submit_event(client, culprit_path, td, "kculprit",
                                    gconf=GCONF_LOCAL, tenant="bad")
            # higher priority: the respawned daemon re-runs the
            # batchmates before the culprit gets the chance to kill it
            mates = [
                _submit_event(client, mate_path, td, f"kmate{i}",
                              gconf=GCONF_LOCAL, tenant=f"t{i}",
                              priority=5)
                for i in range(2)
            ]
            # gen 0: the batch forms, the culprit's fault seam fires
            # mid-batch and takes the whole daemon down (exit 17)
            assert proc.wait(timeout=120) == 17
            # gen 1 (still poisoned): every member is requeued solo —
            # batchmates finish ok, then the culprit kills this one too
            proc, client = _spawn_daemon(state, "m1", extra_env=poison_env)
            assert proc.wait(timeout=120) == 17
            # budget burned: a healthy daemon quarantines the culprit
            # instead of executing it
            proc, client = _spawn_daemon(state, "m2")
            deadline = time.monotonic() + 120
            res = None
            while time.monotonic() < deadline:
                st = client.status(culprit)
                if st["state"] == "failed":
                    res = st["result"]
                    break
                time.sleep(0.2)
            assert res is not None, "poison member never quarantined"
            assert res["quarantined"] is True
            assert [e["gen"] for e in res["failure_log"]] == [0, 1]
            q = JobQueue(str(state / "jobs"), lease_s=5.0)
            for jid in mates:
                st = client.wait(jid, timeout_s=180)
                assert st["result"]["ok"], (
                    f"batchmate lost to the culprit's kill: {st}"
                )
                r = q.get(jid)["result"]
                assert r["gen"] == 1, (
                    "a batchmate burned more than the one shared-crash "
                    f"generation: {r}"
                )
        finally:
            if proc is not None and proc.poll() is None:
                proc.kill()
                proc.wait(timeout=30)
