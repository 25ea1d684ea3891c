"""The driver bench contract must be unlosable.

A run cut by its time budget must still leave a parseable perf number.
The contract:

  * bench.py (driver mode) prints the merged JSON line after EVERY config
    (flushed; last stdout line wins), so a kill mid-run keeps everything
    measured so far;
  * a global wall-clock deadline enforced inside bench.py
    (``CTT_BENCH_DEADLINE_S``) skips configs that no longer fit and still
    exits 0 with a valid final JSON line.

These tests drive bench.py exactly as the driver does (subprocess,
``timeout``-style budget) with a deadline small enough that every config is
forcibly over budget — the contract must survive.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


def _run(env_extra, args=(), timeout=120):
    env = dict(os.environ)
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, BENCH, "--platform", "cpu", "--quick", *args],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO,
    )


def _contract_lines(stdout):
    lines = [ln for ln in stdout.strip().splitlines() if ln.strip()]
    parsed = [json.loads(ln) for ln in lines]
    for p in parsed:
        assert set(p) == {"metric", "value", "unit", "vs_baseline", "extra"}
        assert p["metric"] == "dt_watershed_throughput_per_chip"
        assert p["unit"] == "Mvox/s"
    return parsed


@pytest.mark.timeout(180)
def test_contract_survives_zero_budget():
    """Every config over budget -> still exit 0 with a valid JSON line."""
    out = _run({"CTT_BENCH_DEADLINE_S": "1"})
    assert out.returncode == 0, out.stderr[-2000:]
    parsed = _contract_lines(out.stdout)
    assert parsed, "no JSON contract emitted"
    # every config (e2e_sharded included) must have been skipped by the
    # deadline, not attempted
    assert out.stderr.count("skipped:") == 9, out.stderr[-2000:]


@pytest.mark.timeout(180)
def test_contract_checkpointed_incrementally():
    """The merged line exists from second zero (before any config runs):
    the first stdout line is already a parseable contract."""
    out = _run({"CTT_BENCH_DEADLINE_S": "1"})
    assert out.returncode == 0
    first = _contract_lines(out.stdout)[0]
    assert first["value"] is None  # null contract, but structurally valid
