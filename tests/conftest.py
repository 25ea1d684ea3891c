"""Test configuration: force an 8-device virtual CPU mesh BEFORE jax is imported.

The tests run on the CPU; multi-chip sharding is validated on the virtual
devices.  The real-TPU path is exercised by chip_smoke.py, and the Mosaic
kernels are compiled for a described TPU in tests/test_tpu_compile.py.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# jax may already be imported (and have read JAX_PLATFORMS) by the time this
# file runs, so pin the platform through the config as well: no test process
# may claim a chip
import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _hbm_isolated():
    """ctt-hbm: a test that arms the warm device-buffer cache (directly,
    or by starting an in-process serve daemon whose context installs one
    process-wide) must not leak resident entries — or an enabled budget —
    into later tests' store-traffic accounting.  Restore the environment
    resolution (default 0 = disabled) and drop cached device arrays."""
    yield
    from cluster_tools_tpu.runtime.workflow import ExecutionContext

    ctx = ExecutionContext._PROCESS
    if ctx is not None and ctx._device_cache is not None:
        from cluster_tools_tpu.runtime import hbm

        ctx._device_cache.max_bytes = hbm.cache_budget_bytes()
        ctx._device_cache.clear()


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture
def tmp_env(tmp_path):
    """tmp_folder + config_dir pair with a default global config written."""
    from cluster_tools_tpu.runtime import config as cfg

    tmp_folder = str(tmp_path / "tmp")
    config_dir = str(tmp_path / "configs")
    os.makedirs(tmp_folder, exist_ok=True)
    cfg.write_global_config(config_dir, {"block_shape": [16, 32, 32]})
    return tmp_folder, config_dir


def boundary_from_gt(gt, rng, sigma=1.0, noise=0.05):
    """Smoothed gt-edge boundary map + noise — the synthetic boundary
    evidence recipe shared by the learning/quantile tests."""
    from scipy import ndimage

    bnd = np.zeros(gt.shape, dtype=bool)
    for axis in range(gt.ndim):
        a = [slice(None)] * gt.ndim
        b = [slice(None)] * gt.ndim
        a[axis] = slice(1, None)
        b[axis] = slice(None, -1)
        edge = gt[tuple(a)] != gt[tuple(b)]
        bnd[tuple(a)] |= edge
        bnd[tuple(b)] |= edge
    bnd = ndimage.gaussian_filter(bnd.astype("float32"), sigma)
    return bnd + noise * rng.random(gt.shape).astype("float32")
