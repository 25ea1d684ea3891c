"""Pallas per-slice CC + device z-merge vs the XLA CC and scipy.

Mirrors tests/test_pallas_flood.py: the Mosaic lowering is compiled for a
described chip in tests/test_tpu_compile.py; here the kernel runs in the
CPU interpreter, which executes identical kernel logic."""

import numpy as np
import pytest

from cluster_tools_tpu.ops.cc import (
    connected_components,
    connected_components_np,
)
from cluster_tools_tpu.ops.pallas_cc import (
    cc_slices,
    pallas_cc_available,
    pallas_connected_components,
)


def _random_mask(rng, shape, p=0.5):
    return rng.random(shape) < p


class TestPallasCC:
    @pytest.mark.parametrize("p", [0.2, 0.5, 0.8])
    def test_matches_scipy_partition(self, rng, p):
        mask = _random_mask(rng, (6, 16, 128), p)
        labels, n = pallas_connected_components(mask, interpret=True)
        labels = np.asarray(labels)
        want, n_want = connected_components_np(mask, connectivity=1)
        assert int(n) == n_want
        # identical partitions
        fg = mask
        pairs = np.unique(
            np.stack([labels[fg], want[fg]], axis=1), axis=0
        )
        assert len(pairs) == n_want
        assert (labels[~fg] == 0).all()

    def test_matches_xla_cc_exactly(self, rng):
        """Not just the partition: the consecutive numbering (minimal-flat-
        index root order) must be identical, so the paths are drop-in
        interchangeable mid-pipeline."""
        mask = _random_mask(rng, (4, 8, 128), 0.55)
        want, n_want = connected_components(mask, connectivity=1)
        got, n_got = pallas_connected_components(mask, interpret=True)
        assert int(n_got) == int(n_want)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_serpentine_corridor_converges(self):
        """A row-serpentine in one slice plus a z-bridge: full rows joined by
        alternating single-cell connectors."""
        mask = np.zeros((2, 16, 128), dtype=bool)
        for r in range(0, 16, 2):
            mask[0, r, :] = True
        for r in range(1, 16, 2):
            mask[0, r, 0 if (r // 2) % 2 == 0 else 127] = True
        mask[1] = mask[0]  # z-bridge everywhere
        labels, n = pallas_connected_components(mask, interpret=True)
        want, n_want = connected_components_np(mask, connectivity=1)
        assert int(n) == n_want == 1

    def test_banded_serpentine_needs_many_rounds(self):
        """The adversarial case that breaks any H+W-style round cap: bands
        of vertical serpentines chained into ONE component that needs
        Θ(H·W) propagation rounds, plus a separate isolated cell whose
        numbering must not be disturbed."""
        h, w = 16, 128
        mask = np.zeros((1, h, w), dtype=bool)
        # vertical columns, connected alternately at top/bottom: a
        # column-serpentine spanning the whole slice
        for c in range(0, w - 2, 2):
            mask[0, :, c] = True
            mask[0, 0 if (c // 2) % 2 else h - 1, c + 1] = True
        # isolated cell far away in the last column
        mask[0, h // 2, w - 1] = True
        labels, n = pallas_connected_components(mask, interpret=True)
        want, n_want = connected_components_np(mask[0], connectivity=1)
        assert int(n) == n_want == 2
        labels = np.asarray(labels)[0]
        fg = mask[0]
        pairs = np.unique(np.stack([labels[fg], want[fg]], axis=1), axis=0)
        assert len(pairs) == 2

    def test_slice_kernel_labels_are_minimal_flat_ids(self, rng):
        mask = _random_mask(rng, (3, 8, 128), 0.5)
        sliced = np.asarray(cc_slices(mask, interpret=True))
        n, h, w = mask.shape
        flat = np.arange(n * h * w, dtype=np.int64).reshape(mask.shape)
        for z in range(n):
            want, n_want = connected_components_np(mask[z], connectivity=1)
            for comp in range(1, n_want + 1):
                sel = want == comp
                ids = np.unique(sliced[z][sel])
                assert ids.size == 1
                assert ids[0] == flat[z][sel].min()
        assert (sliced[~mask] == -1).all()

    def test_availability_gating(self):
        from cluster_tools_tpu.ops import _backend

        shape = (6, 16, 128)
        # off by default
        assert not pallas_cc_available(shape, 1, False)
        with _backend.force_cc_mode("pallas"):
            import jax

            on_tpu = jax.default_backend() == "tpu"
            assert pallas_cc_available(shape, 1, False) == on_tpu
            # never for per-slice / higher connectivity / misaligned
            assert not pallas_cc_available(shape, 1, True)
            assert not pallas_cc_available(shape, 3, False)
            assert not pallas_cc_available((6, 16, 100), 1, False)
            assert not pallas_cc_available((16, 128), 1, False)
            # VMEM bound: oversized slices take the XLA path
            assert not pallas_cc_available((4, 1024, 1024), 1, False)

    def test_empty_and_full(self):
        for mask in (
            np.zeros((2, 8, 128), dtype=bool),
            np.ones((2, 8, 128), dtype=bool),
        ):
            labels, n = pallas_connected_components(mask, interpret=True)
            want, n_want = connected_components_np(mask, connectivity=1)
            assert int(n) == n_want
