"""The benchmark's tests run on the CPU at tiny sizes:
``python -m pytest benchmark/tests``."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import pytest  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture
def rehearsal(monkeypatch, tmp_path):
    """``benchmark.run`` on the CPU: the tiny cells of ``data/``, the
    look for a chip skipped, the v5e's peaks for the CPU."""
    from benchmark import run
    from benchmark.harness import peaks

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))
    monkeypatch.setattr(run, "BENCH_FILE",
                        os.path.join(DATA, "BENCHMARK.json"))
    monkeypatch.setattr(run, "check_device", lambda chips: {
        "platform": "cpu", "kind": "cpu", "count": 1})
    real = peaks.peaks
    monkeypatch.setattr(peaks, "peaks", lambda kind: real("TPU v5 lite"))
    return run


def last_json(text):
    import json

    return json.loads(text.strip().splitlines()[-1])
