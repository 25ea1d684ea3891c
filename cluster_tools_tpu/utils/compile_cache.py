"""Persistent XLA compilation cache for the whole framework.

The e2e pipelines concentrate their cold wall in a handful of large jit
programs, and jax leaves its on-disk executable cache off unless a
directory is configured — so every fresh process (each bench subprocess,
every production worker) would recompile everything.  The reference's
deployment model spawns many short-lived jobs (cluster_tasks.py job
scripts), where this matters most.

Where the cache lives:

  * ``JAX_COMPILATION_CACHE_DIR`` set: jax reads it itself and this module
    sets no directory — the deployment decides where compiles persist;
  * otherwise ``<checkout>/.jax_cache``, one fixed path for every process
    of this checkout (the path is part of the cache's key, so a directory
    that moves between runs never hits).

``enable_compile_cache()`` is called from ``runtime.build`` and the bench
entry point; ``CTT_COMPILE_CACHE=0`` disables it.  Idempotent; safe on
backends whose executables cannot be serialized (the cache never hits).
"""

from __future__ import annotations

import os

ENV_DIR = "JAX_COMPILATION_CACHE_DIR"

# <checkout>/.jax_cache: three levels up from cluster_tools_tpu/utils/
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)

# the directory jax is actually caching to (None until first enable)
_ACTIVE_DIR: str | None = None


def active_dir() -> str | None:
    """The directory jax is caching executables to, or None when the cache
    was never enabled / is disabled (introspection for the serve daemon's
    /healthz and the ExecutionContext describe())."""
    return _ACTIVE_DIR


def enable_compile_cache() -> str | None:
    """Turn on jax's persistent compilation cache (idempotent).

    Returns the directory jax caches to, or None when disabled via
    ``CTT_COMPILE_CACHE=0`` or when the default directory cannot be
    created (the cache is an optimization; never fail the caller's
    workload for it)."""
    global _ACTIVE_DIR
    if _ACTIVE_DIR is not None:
        return _ACTIVE_DIR
    env = os.environ.get("CTT_COMPILE_CACHE")
    if env is not None and env.strip() in ("0", "false", "off", ""):
        return None
    import jax

    path = os.environ.get(ENV_DIR)
    if not path:
        path = DEFAULT_DIR
        try:
            os.makedirs(path, exist_ok=True)
        except OSError as e:
            print(f"[compile_cache] disabled ({e})", flush=True)
            return None
        jax.config.update("jax_compilation_cache_dir", path)
    _ACTIVE_DIR = path
    # ctt-obs: count cache hits/misses via jax.monitoring (no-op when
    # tracing is off) and record how warm the cache was at enable time
    from ..obs import metrics as obs_metrics

    obs_metrics.install_compile_cache_listener()
    try:
        n_entries = sum(1 for n in os.listdir(path) if not n.startswith("."))
    except OSError:  # directory not created yet: jax makes it on first write
        n_entries = 0
    obs_metrics.set_gauge("compile_cache.entries_at_enable", n_entries)
    return path
