"""The bench timing primitives: host-fetch completion barrier + variants.

`timeit` must end every timed call in a real device→host fetch
(bench._host_sync) — a backend whose `block_until_ready` acks before
execution makes block-only timing read ~0 ms.  These tests pin the
contract on the CPU backend where both paths are observable.
"""

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import _host_sync, fetch_floor_s, timeit  # noqa: E402


def test_host_sync_passes_through_numpy_and_scalars():
    for r in (np.arange(4), 3.5, None, [np.zeros(2), "x"]):
        assert _host_sync(r) is r


def test_host_sync_fetches_device_arrays():
    import jax.numpy as jnp

    r = (jnp.arange(8), jnp.zeros((2, 2)))
    assert _host_sync(r) is r  # completes without error on tuples


def test_timeit_counts_real_work():
    import jax

    @jax.jit
    def f(x):
        for _ in range(20):
            x = jnp_sin(x)
        return x

    import jax.numpy as jnp

    def jnp_sin(x):
        return jnp.sin(x) + 1e-3

    x = jnp.zeros((256, 256))
    t = timeit(lambda: f(x), 3)
    assert t > 0  # a real, positive wall measurement

    # variant scheme: each timed round consumes one distinct input
    calls = []
    variants = [
        (lambda i: lambda: calls.append(i) or f(x + i))(i) for i in range(4)
    ]
    timeit(None, 3, variants=variants)
    assert calls == [0, 1, 2, 3]


def test_fetch_floor_is_small_and_nonnegative():
    floor = fetch_floor_s(repeats=3)
    assert 0.0 <= floor < 1.0  # CPU: microseconds
