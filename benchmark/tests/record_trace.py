"""Record the small device trace that ``test_trace.py`` reads.

    python3 benchmark/tests/record_trace.py <out.xplane.pb>

Run on a TPU: two named programs with host annotations around them, one
sync marker, traced by ``jax.profiler``; the newest ``.xplane.pb`` is
copied to the path given."""

import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


def main(out):
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    from benchmark.harness import xtrace

    @jax.jit
    def small_a(x):
        return jnp.sin(x) @ x

    @jax.jit
    def small_b(x):
        return jnp.cumsum(x * 2.0, axis=0)

    x = jnp.ones((512, 512), jnp.float32)
    small_a(x).block_until_ready()
    small_b(x).block_until_ready()
    d = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(d)
        print("sync_ns", time.monotonic_ns())
        with jax.profiler.TraceAnnotation(xtrace.SYNC):
            pass
        for _ in range(3):
            with jax.profiler.TraceAnnotation("ctt_bench_job"):
                small_a(x).block_until_ready()
                time.sleep(0.01)
                small_b(x).block_until_ready()
        jax.profiler.stop_trace()
        path = sorted(glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                                recursive=True))[-1]
        shutil.copy(path, out)
        print("copied", path, os.path.getsize(out), "bytes")
    finally:
        shutil.rmtree(d, ignore_errors=True)


if __name__ == "__main__":
    main(sys.argv[1])
