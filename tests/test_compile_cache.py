"""Persistent compilation cache wiring (utils/compile_cache.py) and the
content-keyed native library path (native/__init__.py)."""

import os
import shutil
import subprocess
import sys

import pytest

from cluster_tools_tpu import native
from cluster_tools_tpu.utils import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_disabled_by_env(monkeypatch):
    monkeypatch.setenv("CTT_COMPILE_CACHE", "0")
    monkeypatch.setattr(compile_cache, "_ACTIVE_DIR", None)
    assert compile_cache.enable_compile_cache() is None


@pytest.mark.parametrize("env_dir", ["set", "unset"])
def test_cache_dir_after_build(tmp_path, env_dir):
    """jax reads JAX_COMPILATION_CACHE_DIR when the process starts, so the
    rule is checked in a fresh process, as a deployment would start one:
    the variable wins untouched, else the checkout's fixed .jax_cache."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("CTT_COMPILE_CACHE", compile_cache.ENV_DIR)}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = ROOT
    want = os.path.join(ROOT, ".jax_cache")
    if env_dir == "set":
        want = str(tmp_path / "x")
        env[compile_cache.ENV_DIR] = want
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax\n"
         "from cluster_tools_tpu.runtime import build\n"
         "assert build([])\n"
         "print(jax.config.jax_compilation_cache_dir)\n"],
        env=env, cwd=str(tmp_path), capture_output=True, text=True,
        timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == want


def test_native_library_path_follows_source_content(tmp_path):
    src = tmp_path / "solvers.cpp"
    shutil.copyfile(native._SRC, src)
    assert native.lib_path(str(src)) == native.lib_path()
    with open(src, "a") as f:
        f.write("\n// edited\n")
    edited = native.lib_path(str(src))
    assert edited != native.lib_path()
    assert os.path.dirname(edited) == os.path.dirname(native.lib_path())
