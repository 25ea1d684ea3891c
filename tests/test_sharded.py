"""Sharded whole-volume kernels on the 8-virtual-device mesh.

The collective path (ppermute halo exchange + psum convergence inside one
jit) must reproduce the single-device oracles exactly — the same program
runs on a real ICI mesh.
"""

import jax
import numpy as np
import pytest
from scipy import ndimage

from cluster_tools_tpu.ops.cc import connected_components_raw
from cluster_tools_tpu.parallel.mesh import get_mesh
from cluster_tools_tpu.parallel.sharded import (
    halo_exchange,
    sharded_connected_components,
    sharded_seeded_watershed,
)


def _cc_partition_equal(raw_labels, ref):
    """Sharded-CC output (root ids, -1 = background) vs an oracle labeling:
    shift to the same_partition convention (background 0, ids >= 1)."""
    from cluster_tools_tpu.ops.evaluation import same_partition

    shifted = np.where(raw_labels < 0, 0, raw_labels.astype(np.int64) + 1)
    return same_partition(shifted, ref)


@pytest.mark.parametrize("connectivity", [1, 3])
def test_sharded_cc_matches_oracle(rng, connectivity):
    mesh = get_mesh()
    n = mesh.shape["data"]
    assert n == 8
    mask = rng.random((24, 16, 16)) < 0.4

    got = np.asarray(
        sharded_connected_components(mask, mesh=mesh, connectivity=connectivity)
    )
    structure = ndimage.generate_binary_structure(3, connectivity)
    ref, _ = ndimage.label(mask, structure=structure)

    assert (got[~mask] == -1).all()
    assert _cc_partition_equal(got, ref)


def test_sharded_cc_root_ids_match_single_device(rng):
    # root = min global flat index, identical to connected_components_raw
    mask = rng.random((16, 8, 8)) < 0.5
    got = np.asarray(sharded_connected_components(mask))
    ref = np.asarray(connected_components_raw(mask, connectivity=1))
    np.testing.assert_array_equal(got, ref)


def test_sharded_cc_cross_all_shards(rng):
    # a snake spanning every shard: label info must cross 7 boundaries
    mask = np.zeros((24, 8, 8), dtype=bool)
    mask[:, 4, 4] = True  # one column through the whole volume
    mask[0, 4, :] = True
    got = np.asarray(sharded_connected_components(mask))
    ids = np.unique(got[mask])
    assert ids.size == 1  # single component across all 8 shards


def test_halo_exchange_roundtrip(rng):
    from functools import partial

    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = get_mesh()
    x = np.arange(24 * 4 * 4, dtype=np.float32).reshape(24, 4, 4)
    xd = jax.device_put(jnp.asarray(x), NamedSharding(mesh, P("data")))

    fn = jax.shard_map(
        partial(halo_exchange, halo=1, axis_name="data", fill=-1.0),
        mesh=mesh, in_specs=P("data"), out_specs=P("data"),
    )
    out = np.asarray(jax.jit(fn)(xd))  # (24 + 8*2, 4, 4) re-stacked
    out = out.reshape(8, 5, 4, 4)  # per-shard extended blocks (3+2 planes)
    for s in range(8):
        lo = out[s, 0]
        core = out[s, 1:4]
        hi = out[s, 4]
        np.testing.assert_array_equal(core, x[3 * s : 3 * s + 3])
        if s == 0:
            assert (lo == -1.0).all()
        else:
            np.testing.assert_array_equal(lo, x[3 * s - 1])
        if s == 7:
            assert (hi == -1.0).all()
        else:
            np.testing.assert_array_equal(hi, x[3 * s + 3])


def test_sharded_cc_single_plane_shards(rng):
    # z extent == mesh size: every shard holds ONE plane, which is both of
    # its boundary planes (regression: carry-shape crash in boundary_merge)
    mask = rng.random((8, 8, 8)) < 0.5
    got = np.asarray(sharded_connected_components(mask))
    ref = np.asarray(connected_components_raw(mask, connectivity=1))
    np.testing.assert_array_equal(got, ref)


def test_halo_exchange_multi_hop():
    # halo deeper than one shard: planes chain through multiple neighbors
    from functools import partial

    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = get_mesh()
    x = np.arange(16 * 2 * 2, dtype=np.float32).reshape(16, 2, 2)  # Zl = 2
    xd = jax.device_put(jnp.asarray(x), NamedSharding(mesh, P("data")))
    halo = 5  # needs 3 hops at z_local = 2
    fn = jax.shard_map(
        partial(halo_exchange, halo=halo, axis_name="data", fill=-1.0),
        mesh=mesh, in_specs=P("data"), out_specs=P("data"),
    )
    out = np.asarray(jax.jit(fn)(xd)).reshape(8, 2 * halo + 2, 2, 2)
    for s in range(8):
        z0 = 2 * s
        np.testing.assert_array_equal(out[s, halo : halo + 2], x[z0 : z0 + 2])
        for k in range(halo):
            src = z0 - halo + k
            want = x[src] if src >= 0 else np.full((2, 2), -1.0)
            np.testing.assert_array_equal(out[s, k], want)
            src = z0 + 2 + k
            want = x[src] if src < 16 else np.full((2, 2), -1.0)
            np.testing.assert_array_equal(out[s, halo + 2 + k], want)


class TestShardedFlood:
    def _setup(self, rng, shape=(24, 16, 16)):
        import jax.numpy as jnp
        from scipy import ndimage as ndi

        from cluster_tools_tpu.ops.dt import distance_transform
        from cluster_tools_tpu.ops.watershed import dt_seeds

        raw = ndi.gaussian_filter(rng.random(shape), (1.0, 2.0, 2.0))
        raw = ((raw - raw.min()) / (raw.max() - raw.min())).astype("float32")
        fg = raw < 0.6
        dt = distance_transform(jnp.asarray(fg))
        seeds, _ = dt_seeds(dt, sigma=1.0)
        return raw, seeds, fg

    def test_matches_single_device_flood_exactly(self, rng):
        import jax.numpy as jnp

        from cluster_tools_tpu.ops.watershed import seeded_watershed
        from cluster_tools_tpu.parallel.sharded import sharded_seeded_watershed

        hmap, seeds, fg = self._setup(rng)
        ref = np.asarray(
            seeded_watershed(jnp.asarray(hmap), seeds, jnp.asarray(fg))
        )
        got = np.asarray(sharded_seeded_watershed(hmap, seeds, mask=fg))
        np.testing.assert_array_equal(got, ref)

    def test_flood_crosses_all_shards(self):
        # single seed at the top, open corridor: the flood must descend
        # through every shard boundary
        hmap = np.full((24, 8, 8), 0.5, dtype=np.float32)
        seeds = np.zeros((24, 8, 8), dtype=np.int32)
        seeds[0, 4, 4] = 7
        got = np.asarray(sharded_seeded_watershed(hmap, seeds))
        assert (got == 7).all()

    def test_single_plane_shards(self, rng):
        import jax.numpy as jnp

        from cluster_tools_tpu.ops.watershed import seeded_watershed
        from cluster_tools_tpu.parallel.sharded import sharded_seeded_watershed

        hmap, seeds, fg = self._setup(rng, shape=(8, 12, 12))
        ref = np.asarray(
            seeded_watershed(jnp.asarray(hmap), seeds, jnp.asarray(fg))
        )
        got = np.asarray(sharded_seeded_watershed(hmap, seeds, mask=fg))
        np.testing.assert_array_equal(got, ref)
