"""Record the small device trace with named scopes that ``test_scopes.py``
reads.

    python3 benchmark/tests/record_scopes.py benchmark/tests/data/scopes.trace.pb

Run on a TPU: one program whose operations run in two named scopes, one
of them around a loop, and outside them; three executions inside job
annotations, after a sync marker, traced by ``jax.profiler``; the newest
``.xplane.pb`` is copied to the path given."""

import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
from jax import lax


def main(out):
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    from benchmark.harness import xtrace

    @jax.jit
    def scoped(x):
        with jax.named_scope("demo.matmul"):
            y = jnp.sin(x) @ x
        with jax.named_scope("demo.loop"):
            _, y = lax.while_loop(
                lambda c: c[0] < 4 + (c[1][0, 0] > 1e30),
                lambda c: (c[0] + 1, jnp.cos(c[1]) * 1.5 + c[1].T),
                (jnp.int32(0), y))
        return jnp.cumsum(y, axis=0)  # outside every scope

    x = jnp.ones((512, 512), jnp.float32)
    scoped(x).block_until_ready()
    d = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(d)
        with jax.profiler.TraceAnnotation(xtrace.SYNC):
            pass
        for _ in range(3):
            with jax.profiler.TraceAnnotation("ctt_bench_job"):
                scoped(x).block_until_ready()
                time.sleep(0.01)
        jax.profiler.stop_trace()
        path = sorted(glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                                recursive=True))[-1]
        shutil.copy(path, out)
        print("copied", path, os.path.getsize(out), "bytes")
    finally:
        shutil.rmtree(d, ignore_errors=True)


if __name__ == "__main__":
    main(sys.argv[1])
