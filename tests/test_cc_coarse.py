"""ctt-cc: coarse-to-fine CC + hierarchical flood contracts.

Three invariants, each BIT-exact (not just partition-equal):

  * every CC path — flat, coarse (any tile), sharded collective, tiled
    Pallas (interpret) — produces byte-identical labels to the
    ``connected_components_np`` scipy oracle, including the adversarial
    serpentine/spiral corridors, all connectivities × ``per_slice``, empty
    and all-foreground volumes, and non-tile-dividing shapes;
  * the coarse kernel's fixpoint rounds are tile-bounded: strictly fewer
    than the flat kernel's on the serpentine worst case (the tools/ci_check
    smoke repeats this against a fresh process);
  * the tile-warm-started flood reaches the exact ``seeded_watershed``
    fixpoint with no more global rounds than the flat flood.
"""

import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from cluster_tools_tpu.ops import _backend
from cluster_tools_tpu.ops import cc as C


def _oracle(mask, connectivity=1, per_slice=False):
    """Scipy labels with the kernel's numbering (scan-order == ascending
    min flat index); per_slice labels each z-slice independently with ids
    continuing across slices (the kernel's 2d-mode contract)."""
    if not per_slice:
        return C.connected_components_np(mask, connectivity)
    out = np.zeros(mask.shape, np.int32)
    n = 0
    for z in range(mask.shape[0]):
        lab, k = C.connected_components_np(mask[z], connectivity)
        out[z] = np.where(lab > 0, lab + n, 0)
        n += k
    return out, n


def spiral_mask(shape):
    """Rectangular inward spiral corridor: Θ(min(H, W)) nested bends, the
    2d counterpart of ``serpentine_mask``'s banded worst case."""
    h, w = int(shape[-2]), int(shape[-1])
    m2 = np.zeros((h, w), dtype=bool)
    top, bot, left, right = 0, h - 1, 0, w - 1
    while top <= bot and left <= right:
        m2[top, left:right + 1] = True
        m2[top:bot + 1, right] = True
        m2[bot, left:right + 1] = True
        m2[bot:top:-1, left] = True
        top += 2
        bot -= 2
        left += 2
        right -= 2
    if len(shape) == 2:
        return m2
    return np.broadcast_to(m2, tuple(shape)).copy()


def _assert_all_paths_exact(mask, connectivity=1, per_slice=False,
                            tiles=((4, 8, 8),)):
    ref, n_ref = _oracle(mask, connectivity, per_slice)
    with _backend.force_cc_mode("flat"):
        flat, n_flat = C.connected_components(
            jnp.asarray(mask), connectivity, per_slice=per_slice
        )
    np.testing.assert_array_equal(np.asarray(flat), ref)
    assert int(n_flat) == n_ref
    for tile in tiles:
        tile = tile[-mask.ndim:]
        got, n = C.connected_components(
            jnp.asarray(mask), connectivity, per_slice=per_slice,
            coarse_tile=tile,
        )
        np.testing.assert_array_equal(np.asarray(got), ref)
        assert int(n) == n_ref


class TestCoarseParity:
    @pytest.mark.parametrize("connectivity", [1, 2, 3])
    @pytest.mark.parametrize("per_slice", [False, True])
    def test_random_all_modes(self, rng, connectivity, per_slice):
        mask = rng.random((12, 20, 18)) < 0.5
        _assert_all_paths_exact(
            mask, connectivity, per_slice, tiles=((4, 8, 8), (5, 7, 9))
        )

    def test_non_dividing_shape(self, rng):
        # tiles never divide the volume: the padding path must stay exact
        mask = rng.random((13, 17, 11)) < 0.5
        _assert_all_paths_exact(mask, tiles=((8, 8, 8),))

    def test_2d(self, rng):
        mask = rng.random((40, 33)) < 0.5
        _assert_all_paths_exact(mask, tiles=((8, 8), (16, 5)))

    def test_empty_and_full(self):
        for mask in (np.zeros((8, 16, 16), bool), np.ones((8, 16, 16), bool)):
            _assert_all_paths_exact(mask, tiles=((4, 8, 8),))

    def test_serpentine_and_spiral(self):
        for mask in (
            C.serpentine_mask((4, 40, 36)),
            C.serpentine_mask((48, 40)),
            spiral_mask((4, 41, 41)),
            spiral_mask((41, 41)),
        ):
            _assert_all_paths_exact(mask, tiles=((4, 8, 8), (8, 16, 16)))

    def test_partition_mode(self, rng):
        # CC within existing labels: coarse must match flat bit-exactly
        seg = (rng.random((10, 16, 14)) * 3).astype(np.int32)
        with _backend.force_cc_mode("flat"):
            want, n_want = C.connected_components_labels(jnp.asarray(seg))
        got, n_got = C.connected_components_labels(
            jnp.asarray(seg), coarse_tile=(4, 8, 8)
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert int(n_got) == int(n_want)

    def test_single_voxel_tiles(self, rng):
        # degenerate tile (1, 1, 1): every voxel is a tile, everything is
        # boundary merge — the pure union-find limit stays exact
        mask = rng.random((4, 6, 5)) < 0.6
        _assert_all_paths_exact(mask, tiles=((1, 1, 1),))

    @pytest.mark.parametrize("connectivity", [1, 2, 3])
    @pytest.mark.parametrize("per_slice", [False, True])
    def test_vmapped_batch_matches_flat(self, rng, connectivity, per_slice):
        # the block programs vmap the CC over a device batch; a shape the
        # tile does not divide, so edge tiles are padded
        masks = jnp.asarray(rng.random((2, 9, 13, 11)) < 0.5)

        def run(tile):
            return jax.vmap(lambda m: C.connected_components(
                m, connectivity, per_slice=per_slice, coarse_tile=tile))(masks)

        with _backend.force_cc_mode("flat"):
            want, n_want = run(None)
        got, n_got = run((4, 8, 8))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(n_got), np.asarray(n_want))

    def test_vmapped_partition_matches_flat(self, rng):
        segs = jnp.asarray((rng.random((2, 10, 13, 9)) * 3).astype(np.int32))

        def run(tile):
            return jax.vmap(lambda s: C.connected_components_labels(
                s, connectivity=2, coarse_tile=tile))(segs)

        with _backend.force_cc_mode("flat"):
            want, n_want = run(None)
        got, n_got = run((4, 8, 8))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(n_got), np.asarray(n_want))


class TestIterationContract:
    def test_serpentine_tile_bounded_rounds(self):
        mask = jnp.asarray(C.serpentine_mask((4, 64, 64)))
        _, it_flat = C.connected_components_raw_with_iters(mask)
        _, stats = C.connected_components_coarse_raw(
            mask, 1, None, False, (4, 16, 16)
        )
        assert int(stats["fixpoint_iters"]) < int(it_flat)

    def test_live_mask_drops_background_tiles(self):
        # one busy corner in an otherwise empty volume: Σ live tiles per
        # round must be far below (rounds × tiles) — empty tiles drop out
        # after round one
        mask = np.zeros((16, 32, 32), bool)
        mask[:4, :8, :8] = C.serpentine_mask((4, 8, 8))[0]
        _, stats = C.connected_components_coarse_raw(
            jnp.asarray(mask), 1, None, False, (4, 8, 8)
        )
        n_tiles = 4 * 4 * 4
        rounds = int(stats["fixpoint_iters"])
        assert rounds >= 2
        assert int(stats["live_tile_rounds"]) < rounds * n_tiles


class TestValueTable:
    def test_merge_value_table_min_semantics(self):
        from cluster_tools_tpu.ops.unionfind import (
            apply_value_roots,
            merge_value_table,
        )

        # sparse ids: {3,7}, {12,41,100}, self-loop padding at 999
        a = jnp.asarray(np.array([7, 41, 100, 999, 999], np.int32))
        b = jnp.asarray(np.array([3, 12, 41, 999, 999], np.int32))
        vals, roots = merge_value_table(a, b)
        # resolution goes through apply_value_roots (searchsorted side='left'
        # → the canonical leftmost slot of duplicated values)
        x = jnp.asarray(np.array([1, 3, 7, 12, 41, 100, 55, 999], np.int32))
        out = np.asarray(apply_value_roots(x, vals, roots))
        # {3,7} → 3, {12,41,100} → 12, self-loop 999 → itself, absent
        # values (1, 55) pass through untouched
        np.testing.assert_array_equal(out, [1, 3, 3, 12, 12, 12, 55, 999])

    @pytest.mark.parametrize("id_offset", [0, 1000, 40000])
    @pytest.mark.parametrize("seed", [0, 1])
    def test_dense_merge_matches_value_table(self, id_offset, seed):
        # the flat-index merge of the coarse CC gives what the sorted value
        # table gives, for pairs with duplicates, chains, sentinel
        # self-loops and ids offset as a shard's are
        from cluster_tools_tpu.ops.unionfind import (
            apply_value_roots,
            merge_dense_values,
            merge_value_table,
        )

        rng = np.random.default_rng(seed)
        size = 300
        sentinel = 50000
        m = 400
        a = rng.integers(0, size, m) + id_offset
        b = rng.integers(0, size, m) + id_offset
        b[:40] = a[:40]  # self-loops on real values
        a[40:80] = b[40:80] = sentinel  # padding slots
        a[80:120] = a[120:160]  # duplicated values
        a, b = a.astype(np.int32), b.astype(np.int32)
        x = np.concatenate(
            [np.arange(size) + id_offset, [sentinel]]
        ).astype(np.int32)
        rng.shuffle(x)
        want = apply_value_roots(x, *merge_value_table(a, b))
        got = jax.jit(merge_dense_values, static_argnames="size")(
            jnp.asarray(x), jnp.asarray(a), jnp.asarray(b),
            jnp.int32(id_offset), size=size, sentinel=jnp.int32(sentinel),
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        # something merged, and values in no pair kept their own id
        assert (np.asarray(got) != x).any()
        untouched = ~np.isin(x, np.concatenate([a, b]))
        assert untouched.any()
        np.testing.assert_array_equal(np.asarray(got)[untouched], x[untouched])


class TestTileResolution:
    def test_parse_tile_spec(self):
        assert C.parse_tile_spec("8,64,64", 3) == (8, 64, 64)
        assert C.parse_tile_spec("32", 3) == (32, 32, 32)
        assert C.parse_tile_spec("8,64,64", 2) == (64, 64)
        assert C.parse_tile_spec("64", 2) == (64, 64)
        assert C.parse_tile_spec("4,64", 3) == (4, 4, 64)
        assert C.parse_tile_spec("nope", 3) is None
        assert C.parse_tile_spec("0,64,64", 3) is None
        assert C.parse_tile_spec("", 3) is None

    def test_env_pin_and_clip(self, monkeypatch):
        monkeypatch.setenv("CTT_CC_TILE", "4,8,8")
        assert C.resolve_coarse_tile((16, 16, 16)) == (4, 8, 8)
        # clipped to the volume
        assert C.resolve_coarse_tile((2, 4, 4)) == (2, 4, 4)

    def test_invalid_env_warns_and_defaults(self, monkeypatch):
        monkeypatch.setenv("CTT_CC_TILE", "banana")
        with pytest.warns(RuntimeWarning, match="CTT_CC_TILE"):
            tile = C.resolve_coarse_tile((64, 256, 256))
        assert tile == C.default_coarse_tile(3)

    def test_explicit_beats_env(self, monkeypatch):
        monkeypatch.setenv("CTT_CC_TILE", "4,8,8")
        assert C.resolve_coarse_tile((64, 64, 64), 16) == (16, 16, 16)
        assert C.resolve_coarse_tile((64, 64, 64), (8, 16, 32)) == (8, 16, 32)
        with pytest.raises(ValueError):
            C.resolve_coarse_tile((64, 64, 64), (8, 16))

    def test_mode_switch(self):
        # CPU backend defaults flat; explicit pins flip the default path
        assert not _backend.use_coarse_cc()
        with _backend.force_cc_mode("coarse"):
            assert _backend.use_coarse_cc()
        with _backend.force_cc_mode("flat"):
            assert not _backend.use_coarse_cc()


class TestObsCounters:
    def test_wrapper_emits_registered_counters(self, rng, tmp_path):
        from cluster_tools_tpu.obs import metrics, registry, trace

        for name in ("cc.fixpoint_iters", "cc.live_tiles", "cc.merge_pairs"):
            assert registry.is_known_counter(name)
        trace.enable(str(tmp_path / "trace"), "t_cc", export_env=False)
        try:
            metrics.reset()
            mask = rng.random((8, 16, 16)) < 0.5
            labels, n = C.connected_components_coarse(
                mask, coarse_tile=(4, 8, 8)
            )
            ref, n_ref = _oracle(mask)
            np.testing.assert_array_equal(np.asarray(labels), ref)
            assert int(n) == n_ref
            snap = metrics.snapshot()["counters"]
            assert snap.get("cc.fixpoint_iters", 0) >= 1
            assert snap.get("cc.merge_pairs", 0) >= 1
        finally:
            metrics.reset()
            trace.disable()


class TestShardedCoarse:
    def test_sharded_matches_flat_raw(self, rng):
        # the collective (local fixpoint + one all-gathered boundary table)
        # must keep the exact min-flat-index root contract, under BOTH local
        # labeling algorithms
        from cluster_tools_tpu.parallel.sharded import (
            sharded_connected_components,
        )

        mask = rng.random((16, 8, 8)) < 0.5
        ref = np.asarray(C.connected_components_raw(jnp.asarray(mask)))
        for mode in ("flat", "coarse"):
            with _backend.force_cc_mode(mode):
                got = np.asarray(sharded_connected_components(mask))
            np.testing.assert_array_equal(got, ref)

    def test_sharded_serpentine_spans_shards(self):
        # one corridor threading all 8 shards: the single boundary table
        # must resolve a chain of cross-shard equivalences transitively
        from cluster_tools_tpu.parallel.sharded import (
            sharded_connected_components,
        )

        mask = C.serpentine_mask((16, 16, 16))
        ref = np.asarray(C.connected_components_raw(jnp.asarray(mask)))
        with _backend.force_cc_mode("coarse"):
            got = np.asarray(sharded_connected_components(mask))
        np.testing.assert_array_equal(got, ref)


class TestMergeLowering:
    """Which merge each lowered program holds: the coarse CC inside the two
    block programs indexes the block's flat-index range, with no binary
    search under its phase scopes; the sharded CC's stage 2, whose values
    are all-gathered from every shard, keeps the sorted value table."""

    SHAPE = (16, 96, 96)  # 2x2x2 default tiles, so the tile faces merge

    @staticmethod
    def _cc_paths(lowered):
        from test_program_scopes import op_paths

        paths = [p or "" for _, p in op_paths(
            lowered.as_text(debug_info=True))]
        cc = [p for p in paths if re.search(r"(^|/)cc\.(tiles|merge)/", p)]
        assert any(re.search(r"(^|/)cc\.merge/", p) for p in cc)
        return paths, cc

    def test_components_program_has_no_search(self):
        from cluster_tools_tpu.tasks.thresholded_components import (
            _components_batch,
        )

        fn = jax.jit(lambda b, t: _components_batch(b, t, "greater", 0.0, 1))
        with _backend.force_cc_mode("coarse"):
            low = fn.lower(
                jax.ShapeDtypeStruct((1,) + self.SHAPE, jnp.float32),
                jax.ShapeDtypeStruct((), jnp.float32),
            )
        _, cc = self._cc_paths(low)
        assert not [p for p in cc if "searchsorted" in p]

    def test_seed_cc_has_no_search(self):
        from cluster_tools_tpu.ops.watershed import dt_seeds

        with _backend.force_cc_mode("coarse"):
            low = dt_seeds.lower(
                jax.ShapeDtypeStruct(self.SHAPE, jnp.float32), per_slice=True
            )
        _, cc = self._cc_paths(low)
        assert not [p for p in cc if "searchsorted" in p]

    def test_sharded_stage_two_keeps_the_sorted_table(self):
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from cluster_tools_tpu.parallel.sharded import _sharded_cc

        mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
        mask = jax.ShapeDtypeStruct(
            self.SHAPE, jnp.bool_, sharding=NamedSharding(mesh, P("data"))
        )
        with _backend.force_cc_mode("coarse"):
            low = _sharded_cc.lower(mask, 1, "data", mesh)
        paths, cc = self._cc_paths(low)
        assert not [p for p in cc if "searchsorted" in p]
        assert [p for p in paths if "searchsorted" in p]


class TestPallasTiled:
    def test_tiled_kernel_interpret_parity(self, rng):
        from cluster_tools_tpu.ops.pallas_cc import (
            pallas_connected_components_tiled,
        )

        mask = rng.random((3, 16, 256)) < 0.5
        ref, n_ref = _oracle(np.asarray(mask))
        got, n = pallas_connected_components_tiled(
            jnp.asarray(mask), (8, 128), interpret=True
        )
        np.testing.assert_array_equal(np.asarray(got), ref)
        assert int(n) == n_ref

    def test_tile_chooser(self):
        from cluster_tools_tpu.ops.pallas_cc import pallas_cc_tile

        th, tw = pallas_cc_tile((4, 512, 1024))
        assert th % 8 == 0 and tw % 128 == 0
        assert 512 % th == 0 and 1024 % tw == 0
        assert pallas_cc_tile((4, 512, 100)) is None  # no aligned divisor


class TestHierFlood:
    def _fields(self, rng, shape=(12, 32, 24), n_seeds=30):
        from scipy import ndimage

        h = ndimage.gaussian_filter(
            rng.random(shape).astype(np.float32), 1.5
        ).astype(np.float32)
        seeds = np.zeros(shape, np.int32)
        pts = rng.integers(0, np.array(shape), size=(n_seeds, 3))
        for i, p in enumerate(pts):
            seeds[tuple(p)] = i + 1
        mask = rng.random(shape) < 0.92
        return jnp.asarray(h), jnp.asarray(seeds), jnp.asarray(mask)

    @pytest.mark.parametrize("per_slice", [False, True])
    def test_tiled_flood_exact(self, rng, per_slice):
        from cluster_tools_tpu.ops import watershed as W

        h, seeds, mask = self._fields(rng)
        want = np.asarray(
            W._seeded_watershed_scan(h, seeds, mask, per_slice=per_slice)
        )
        got, _, stats = W.flood_with_stats(
            h, seeds, mask, per_slice=per_slice, tile=(4, 8, 8)
        )
        np.testing.assert_array_equal(np.asarray(got), want)
        _, _, flat_stats = W.flood_with_stats(
            h, seeds, mask, per_slice=per_slice
        )
        # the warm start must never cost extra global rounds
        assert int(stats["flood_alt_iters"]) <= int(
            flat_stats["flood_alt_iters"]
        )
        assert int(stats["flood_assign_iters"]) <= int(
            flat_stats["flood_assign_iters"]
        )
        assert int(stats["flood_tile_iters"]) >= 1

    def test_seeded_watershed_coarse_tile_kwarg(self, rng):
        from cluster_tools_tpu.ops import watershed as W

        h, seeds, mask = self._fields(rng)
        want = np.asarray(W.seeded_watershed(h, seeds, mask))
        got = np.asarray(
            W.seeded_watershed(h, seeds, mask, coarse_tile=(4, 8, 8))
        )
        np.testing.assert_array_equal(got, want)

    def test_flood_tile_env_pin(self, rng, monkeypatch):
        from cluster_tools_tpu.ops import watershed as W

        h, seeds, mask = self._fields(rng, shape=(8, 16, 16))
        want = np.asarray(W.seeded_watershed(h, seeds, mask))
        monkeypatch.setenv("CTT_FLOOD_TILE", "4,8,8")
        jax.clear_caches()  # trace-time switch, like every CTT_* mode
        try:
            assert W.resolve_flood_tile(h.shape) == (4, 8, 8)
            got = np.asarray(W.seeded_watershed(h, seeds, mask))
        finally:
            jax.clear_caches()
        np.testing.assert_array_equal(got, want)
        monkeypatch.setenv("CTT_FLOOD_TILE", "garbage")
        with pytest.warns(RuntimeWarning, match="CTT_FLOOD_TILE"):
            assert W.resolve_flood_tile(h.shape) is None

    def test_hier_api_labels_and_merge_table(self, rng):
        from cluster_tools_tpu.ops import watershed as W

        h, seeds, mask = self._fields(rng)
        want = np.asarray(W.seeded_watershed(h, seeds, mask))
        labels, (a, b, s), stats = W.seeded_watershed_hier(
            h, seeds, mask, coarse_tile=(4, 8, 8)
        )
        np.testing.assert_array_equal(np.asarray(labels), want)
        a, b, s = np.asarray(a), np.asarray(b), np.asarray(s)
        real = a > 0
        assert real.any()
        # merge-table invariants: real slots pair distinct labels that are
        # truly tile-face adjacent, with finite saddle = max of the two
        # heights; padding slots are (0, 0, _BIG)
        assert (a[real] != b[real]).all()
        assert (s[real] < 1e38).all()
        assert (b[~real] == 0).all() and (s[~real] > 1e38).all()

    def test_pallas_flood_warm_interpret(self, rng):
        from cluster_tools_tpu.ops import watershed as W
        from cluster_tools_tpu.ops.pallas_flood import flood_tiles_warm

        shape = (3, 16, 256)
        h, seeds, mask = self._fields(rng, shape=shape, n_seeds=20)
        warm = flood_tiles_warm(h, seeds, mask, (8, 128), interpret=True)
        got = W._flood_scan_impl(
            h, seeds, mask, 0, False, (3, 8, 128), warm=warm
        )[0]
        want = W._seeded_watershed_scan(h, seeds, mask)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
