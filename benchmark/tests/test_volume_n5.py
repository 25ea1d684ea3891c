import numpy as np

from benchmark.harness import n5, volume


def test_same_seed_same_volume_and_calibrated(monkeypatch):
    a = volume.synthesize((30, 64, 80), 2**33 + 5)
    b = volume.synthesize((30, 64, 80), 2**33 + 5)
    c = volume.synthesize((30, 64, 80), 2**33 + 6)
    assert a.dtype == np.float32 and a.shape == (30, 64, 80)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert 0.0 <= a.min() and a.max() <= 1.0
    assert abs(float((a > 0.5).mean()) - 0.12) < 0.005
    # the volume does not depend on how it is cut into slabs
    monkeypatch.setattr(volume, "SLAB", 7)
    assert np.allclose(volume.synthesize((30, 64, 80), 2**33 + 5), a,
                       atol=1e-6)


def test_n5_round_trip_through_the_program_store(tmp_path):
    from cluster_tools_tpu.utils import file_reader

    path = str(tmp_path / "x.n5")
    a = np.random.default_rng(0).random((13, 40, 50)).astype(np.float32)
    n5.write(path, "raw", a, (5, 16, 32))
    assert np.array_equal(file_reader(path, "r")["raw"][:], a)
    assert np.array_equal(n5.read(path, "raw", (2, 3, 4), (13, 40, 50)),
                          a[2:, 3:, 4:])
    labels = np.random.default_rng(1).integers(0, 99, (13, 40, 50))
    for comp in ("gzip", "blosc", None):
        key = f"lab_{comp}"
        file_reader(path).create_dataset(
            key, data=labels.astype(np.uint64), chunks=(5, 16, 32),
            compression=comp)
        assert np.array_equal(n5.read(path, key, (0, 0, 0), a.shape), labels)


def test_zarr_read(tmp_path):
    from cluster_tools_tpu.utils import file_reader

    path = str(tmp_path / "x.zarr")
    e = np.random.default_rng(2).integers(0, 1000, (77, 2))
    file_reader(path).create_dataset("graph/edges", data=e, chunks=(20, 2))
    assert np.array_equal(n5.read_zarr(path, "graph/edges"), e)
