"""The harness is driven by data, and refuses to run without its chip or
its program."""

import json
import os
import shutil
import subprocess
import sys

from conftest import DATA, ROOT, last_json

from benchmark.harness import cell as cell_mod


def test_a_new_traffic_file_is_picked_up_by_name(rehearsal, capsys,
                                                 monkeypatch, tmp_path):
    """A mix added as one data file, and a cell naming it, run with no
    edit anywhere else."""
    traffic = os.path.join(cell_mod.BENCH, "traffic", "_test_dummy.json")
    with open(os.path.join(cell_mod.BENCH, "traffic", "cc.json")) as f:
        spec = json.load(f)
    spec.update(name="_test_dummy", roi_blocks=[2, 1, 1])
    spec["task_configs"]["block_components"]["threshold"] = 0.3
    with open(os.path.join(DATA, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"] = [{"name": "tiny._test_dummy", "config": "tiny",
                           "traffic": "_test_dummy", "chips": 1,
                           "why": "test"}]
    bench_file = tmp_path / "BENCHMARK.json"
    bench_file.write_text(json.dumps(bench))
    monkeypatch.setattr(rehearsal, "BENCH_FILE", str(bench_file))
    try:
        with open(traffic, "w") as f:
            json.dump(spec, f)
        assert rehearsal.main(["--workload", "tiny._test_dummy", "--seed",
                               "3", "--seconds", "0.05"]) == 0
    finally:
        os.remove(traffic)
    err = capsys.readouterr()
    result = last_json(err.out)
    assert result["correct"] is True
    assert "roi ((0, 0, 0), (24, 48, 48))" in err.err  # two blocks deep


def run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "cremi_a_b50x512.ws", "--seed", "1", "--seconds", "1"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_1_without_a_result():
    out = run_cli(ROOT)
    assert out.returncode == 1
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".build", "__pycache__"))
    out = run_cli(str(tmp_path))
    assert out.returncode == 1
    assert out.stdout.strip() == ""
    assert "program is missing" in out.stderr
