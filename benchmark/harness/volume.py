"""The seeded boundary-probability volume, made on the device.

The benchmark's own copy of the repository's synthetic CREMI-like
generator: uniform noise, a gaussian of sigma (1, 4, 4) voxels (truncated
at 4 sigma, mirrored borders), min-max normalized, then scaled so that a
fixed share of the voxels (the membrane, 12%) lies above 0.5 and clipped
to [0, 1].  Noise is drawn per z-slice from the seed, so the volume does
not depend on how it is cut into slabs; smoothing runs on the device in
float32 with plain multiply-adds, one jitted call per slab.
"""

from __future__ import annotations

import numpy as np

SLAB = 25  # z-slices per device call


def _kernel(sigma: float) -> np.ndarray:
    radius = max(int(4.0 * sigma + 0.5), 1)
    x = np.arange(-radius, radius + 1, dtype=np.float64)
    k = np.exp(-0.5 * (x / sigma) ** 2)
    return (k / k.sum()).astype(np.float32)


def _mirror(i: np.ndarray, n: int) -> np.ndarray:
    """Half-sample symmetric index (…, 1, 0 | 0, 1, …, n-1 | n-1, n-2, …)."""
    period = 2 * n
    i = np.mod(i, period)
    return np.where(i < n, i, period - 1 - i)


def _slab_fn(shape, sigma):
    import jax
    import jax.numpy as jnp

    nz, ny, nx = shape
    kz, ky, kx = (_kernel(s) for s in sigma)
    rz, ry, rx = (len(k) // 2 for k in (kz, ky, kx))

    def smooth(a, k, axis, radius, n):
        idx = _mirror(np.arange(-radius, n + radius), n)
        a = jnp.take(a, jnp.asarray(idx), axis=axis)
        out = 0.0
        for t, w in enumerate(k):
            out = out + float(w) * lax_slice(a, axis, t, n)
        return out

    def lax_slice(a, axis, start, n):
        return jax.lax.slice_in_dim(a, start, start + n, axis=axis)

    @jax.jit
    def slab(key, z_index):
        # z_index: (SLAB + 2 rz,) mirrored global slice numbers
        noise = jax.vmap(
            lambda z: jax.random.uniform(
                jax.random.fold_in(key, z), (ny, nx), jnp.float32)
        )(z_index)
        out = 0.0
        for t, w in enumerate(kz):
            out = out + float(w) * jax.lax.slice_in_dim(noise, t, t + SLAB, axis=0)
        out = smooth(out, ky, 1, ry, ny)
        return smooth(out, kx, 2, rx, nx)

    return slab, rz


def device_key(seed: int):
    """A JAX key from any non-negative whole number (wider than 32 bits)."""
    import jax

    words = np.random.SeedSequence(int(seed)).generate_state(1)
    return jax.random.key(int(words[0] >> 1))


def synthesize(shape, seed: int, sigma=(1.0, 4.0, 4.0),
               boundary_frac: float = 0.12) -> np.ndarray:
    """The float32 volume of ``shape`` for ``seed``."""
    import jax

    nz = int(shape[0])
    slab, rz = _slab_fn(tuple(int(s) for s in shape), tuple(sigma))
    key = device_key(seed)
    out = np.empty(tuple(shape), np.float32)
    for z0 in range(0, nz, SLAB):
        idx = _mirror(np.arange(z0 - rz, z0 + SLAB + rz), nz)
        part = np.asarray(jax.device_get(slab(key, idx.astype(np.int32))))
        n = min(SLAB, nz - z0)
        out[z0:z0 + n] = part[:n]
    lo, hi = float(out.min()), float(out.max())
    out -= lo
    out *= np.float32(1.0 / (hi - lo))
    k = int(round((1.0 - boundary_frac) * (out.size - 1)))
    q = float(np.partition(out.ravel(), k)[k])
    out *= np.float32(0.5 / q)
    np.clip(out, 0.0, 1.0, out=out)
    return out
