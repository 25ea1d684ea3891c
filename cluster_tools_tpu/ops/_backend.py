"""Shared backend-mode switches for the log-depth sweep kernels.

The flood (ops/watershed.py), connected-components (ops/cc.py), and EDT line
scans (ops/dt.py) all choose between log-depth formulations
(``lax.associative_scan`` / ``lax.cummax`` — win on dispatch/latency-bound
TPUs) and sequential carry chains (O(n) work — win on work-bound XLA-CPU).
Further opt-in kernel switches route whole pipelines to Pallas
(flood/cc) or to the device MWS formulation.  One registry keeps every
switch on the same contract:

  * default: by env var (``CTT_<KIND>_MODE``), else a backend-tagged pin
    file (``tools/chip_modes.json``, written by tools/chip_session.py from
    on-chip measurements; applied only when the running backend matches the
    one the pins were measured on), else the kind's default rule;
  * the env pin remains the explicit way to deploy a mode and always
    overrides the pin file;
  * ``force_<kind>_mode(mode)`` scopes an override for tests and
    benchmarks, owning both the restore and the jit-cache invalidation
    (traces bake the mode in — all switches are read at TRACE time, so
    already-compiled shapes keep their path until the caches clear).
"""

from __future__ import annotations

import os
from contextlib import contextmanager

# kind -> forced mode (None = fall back to env var / default rule)
_FORCED: dict = {}

_ENV = {
    "sweep": "CTT_SWEEP_MODE",
    "flood": "CTT_FLOOD_MODE",
    "cc": "CTT_CC_MODE",
    "mws": "CTT_MWS_MODE",
}


# measured-pin file: {"backend": "<jax backend>", "modes": {ENVVAR: mode}}
_PINS_CACHE: dict = {}


def _file_pins() -> dict:
    """Mode pins from tools/chip_modes.json, keyed by env-var name.

    Loaded once per backend: pins measured on one backend (e.g. pallas
    kernels validated on TPU) must not leak into runs on another (the CPU
    test mesh), so a backend-tagged file only applies when
    jax.default_backend() matches its tag."""
    import jax

    backend = jax.default_backend()
    if backend in _PINS_CACHE:
        return _PINS_CACHE[backend]
    path = os.environ.get("CTT_MODES_FILE")
    if path is None:
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        path = os.path.join(root, "tools", "chip_modes.json")
    pins: dict = {}
    try:
        import json

        with open(path) as f:
            data = json.load(f)
        if (isinstance(data, dict) and isinstance(data.get("modes"), dict)
                and data.get("backend") == backend):
            pins = dict(data["modes"])
    except (OSError, ValueError):
        pins = {}
    if pins:
        # implicit mode changes must be traceable: a process whose backend
        # happens to match the committed pin file inherits these silently
        import logging

        logging.getLogger(__name__).debug(
            "loaded %s pin(s) for backend %r from %s: %s",
            len(pins), backend, path, pins,
        )
    _PINS_CACHE[backend] = pins
    return pins


def pinned_value(env_name: str):
    """Resolve a measured pin by its env-var name: the env var wins, else
    the backend-tagged pin file entry, else None.  The one precedence
    implementation for every CTT_* value that is not a mode switch
    (e.g. CTT_DEVICE_BATCH in runtime/executor.py)."""
    env = os.environ.get(env_name)
    if env is not None:
        return env
    return _file_pins().get(env_name)


def _mode(kind: str):
    forced = _FORCED.get(kind)
    if forced is not None:
        return forced
    return pinned_value(_ENV[kind])


@contextmanager
def _force(kind: str, mode):
    """Scoped mode override: set, clear jit caches, restore + clear on exit
    even on error — the single implementation behind every force_*_mode."""
    import jax

    prev = _FORCED.get(kind)
    _FORCED[kind] = mode
    jax.clear_caches()
    try:
        yield
    finally:
        _FORCED[kind] = prev
        jax.clear_caches()


def use_assoc() -> bool:
    """Sweep formulation: associative-scan (TPU) vs sequential carry (CPU);
    CTT_SWEEP_MODE=assoc|seq pins it."""
    mode = _mode("sweep")
    if mode in ("assoc", "seq"):
        return mode == "assoc"
    import jax

    return jax.default_backend() != "cpu"


def use_pallas_flood() -> bool:
    """Whether the per-slice flood uses the Pallas kernel
    (ops/pallas_flood.py, CTT_FLOOD_MODE=pallas)."""
    return _mode("flood") == "pallas"


def use_pallas_cc() -> bool:
    """Whether volume CC uses the per-slice Pallas kernel + z-merge
    (ops/pallas_cc.py, CTT_CC_MODE=pallas)."""
    return _mode("cc") == "pallas"


def use_slices_cc() -> bool:
    """Whether volume CC uses the XLA per-slice sweeps + z-merge structure
    (CTT_CC_MODE=slices) instead of whole-volume 3d propagation."""
    return _mode("cc") == "slices"


def use_coarse_cc() -> bool:
    """Whether CC uses the coarse-to-fine tiled kernel (ops/cc.py ctt-cc:
    tile-local fixpoints + compact boundary union-find) instead of the flat
    whole-volume fixpoint.  ``CTT_CC_MODE=coarse|flat`` pins it; the default
    follows the sweep-mode economics (the bench records both paths): on
    TPU the tile-bounded round count + vmapped VMEM-friendly tiles win, on
    the work-bound CPU mesh the seq-sweep flat kernel already converges in
    a handful of rounds and the O(volume·log boundary) relabel gather of
    the merge table costs more than the saved rounds (bench.py
    ``cc_flat_vs_baseline`` / ``cc_coarse_vs_baseline``).  Both paths are
    bit-exact on every input (tests/test_cc_coarse.py)."""
    mode = _mode("cc")
    if mode in ("coarse", "flat"):
        return mode == "coarse"
    import jax

    return jax.default_backend() != "cpu"


def use_mws_device() -> bool:
    """Whether graph-domain MWS solves route to the parallel-greedy device
    kernel (ops/mws_device.py, CTT_MWS_MODE=device) instead of host C++."""
    return _mode("mws") == "device"


def force_sweep_mode(mode):
    """Scoped sweep-mode override ('assoc' | 'seq')."""
    return _force("sweep", mode)


def force_flood_mode(mode):
    """Scoped flood-mode override ('pallas' | 'xla')."""
    return _force("flood", mode)


def force_cc_mode(mode):
    """Scoped CC-mode override ('coarse' | 'flat' | 'pallas' | 'slices')."""
    return _force("cc", mode)


def force_mws_mode(mode):
    """Scoped MWS-mode override ('device' | 'host')."""
    return _force("mws", mode)
