"""End-to-end ThresholdedComponentsWorkflow test: the first full slice
(SURVEY.md §7 minimum end-to-end slice) with a recompute oracle — the result
must be the same partition scipy.ndimage.label produces on the whole volume."""

import os

import numpy as np
import pytest
from scipy import ndimage

from cluster_tools_tpu.runtime import build, config as cfg
from cluster_tools_tpu.utils import file_reader
from cluster_tools_tpu.workflows import RelabelWorkflow, ThresholdedComponentsWorkflow


def _make_volume(tmp_path, rng, shape=(40, 40, 40)):
    path = str(tmp_path / "data.n5")
    # smooth random field → nontrivial components crossing block borders
    raw = ndimage.gaussian_filter(rng.random(shape), 1.0)
    raw = (raw - raw.min()) / (raw.max() - raw.min())
    f = file_reader(path)
    f.create_dataset("raw", data=raw.astype("float32"), chunks=(16, 16, 16))
    return path, raw


def _assert_same_partition(got, want):
    from cluster_tools_tpu.ops.evaluation import same_partition

    assert same_partition(got, want)


@pytest.mark.parametrize("target", ["local", "tpu"])
def test_thresholded_components_matches_scipy(tmp_path, rng, target):
    path, raw = _make_volume(tmp_path, rng)
    tmp_folder = str(tmp_path / f"tmp_{target}")
    config_dir = str(tmp_path / f"configs_{target}")
    cfg.write_global_config(
        config_dir, {"block_shape": [16, 16, 16], "target": target}
    )
    threshold = 0.55
    cfg.write_config(config_dir, "block_components", {"threshold": threshold})

    wf = ThresholdedComponentsWorkflow(
        tmp_folder,
        config_dir,
        input_path=path,
        input_key="raw",
        output_path=path,
        output_key="components",
    )
    assert build([wf])

    got = file_reader(path, "r")["components"][:]
    want, n_want = ndimage.label(raw > threshold)
    assert n_want > 5  # fixture sanity: nontrivial component structure
    _assert_same_partition(got, want)


def test_sharded_components_workflow_matches_block_pipeline(tmp_path, rng):
    """sharded=True routes through ONE collective task (z-sharded volume +
    ICI boundary exchange) and must produce the block pipeline's partition."""
    path, raw = _make_volume(tmp_path, rng)
    threshold = 0.55
    outs = {}
    for name, sharded in [("blocks", False), ("sharded", True)]:
        tmp_folder = str(tmp_path / f"tmp_{name}")
        config_dir = str(tmp_path / f"configs_{name}")
        cfg.write_global_config(
            config_dir, {"block_shape": [16, 16, 16], "target": "tpu"}
        )
        cfg.write_config(config_dir, "block_components", {"threshold": threshold})
        cfg.write_config(config_dir, "sharded_components", {"threshold": threshold})
        wf = ThresholdedComponentsWorkflow(
            tmp_folder, config_dir,
            input_path=path, input_key="raw",
            output_path=path, output_key=f"components_{name}",
            sharded=sharded,
        )
        assert build([wf])
        outs[name] = file_reader(path, "r")[f"components_{name}"][:]

    want, _ = ndimage.label(raw > threshold)
    _assert_same_partition(outs["sharded"], want)
    _assert_same_partition(outs["sharded"], outs["blocks"])
    # consecutive uint64 ids, background preserved
    ids = np.unique(outs["sharded"])
    assert ids[0] == 0 and (np.diff(ids) == 1).all()


def test_relabel_workflow_makes_consecutive(tmp_path, rng):
    path = str(tmp_path / "data.zarr")
    labels = rng.choice([0, 7, 1000, 123456789], size=(24, 24, 24)).astype("uint64")
    f = file_reader(path)
    f.create_dataset("seg", data=labels, chunks=(12, 12, 12))
    tmp_folder = str(tmp_path / "tmp")
    config_dir = str(tmp_path / "configs")
    cfg.write_global_config(config_dir, {"block_shape": [12, 12, 12]})

    wf = RelabelWorkflow(
        tmp_folder,
        config_dir,
        input_path=path,
        input_key="seg",
        output_path=path,
        output_key="seg_relabeled",
    )
    assert build([wf])
    out = file_reader(path, "r")["seg_relabeled"][:]
    assert set(np.unique(out)) == {0, 1, 2, 3}
    # same partition as input
    for old, new in [(7, None), (1000, None), (123456789, None)]:
        vals = np.unique(out[labels == old])
        assert len(vals) == 1 and vals[0] > 0
    assert (out[labels == 0] == 0).all()


def test_components_workflow_is_resumable(tmp_path, rng):
    path, raw = _make_volume(tmp_path, rng, shape=(32, 32, 32))
    tmp_folder = str(tmp_path / "tmp")
    config_dir = str(tmp_path / "configs")
    cfg.write_global_config(config_dir, {"block_shape": [16, 16, 16]})
    wf = ThresholdedComponentsWorkflow(
        tmp_folder, config_dir,
        input_path=path, input_key="raw",
        output_path=path, output_key="components",
    )
    assert build([wf])
    # completed workflow: a fresh build() call must be a no-op (complete targets)
    assert wf.complete()
    assert build([wf])


def test_sharded_components_streaming_mask_and_nondivisible_z(tmp_path, rng):
    """The sigma=0 streaming path (per-shard store reads + device
    threshold) with a store-backed mask and a z extent the 8-device mesh
    does not divide must match scipy exactly."""
    from scipy import ndimage

    from cluster_tools_tpu.tasks.thresholded_components import (
        ShardedComponentsTask,
    )

    shape = (13, 16, 16)  # 13 % 8 != 0 → internal pad slab
    raw = rng.random(shape).astype("float32")
    m = rng.random(shape) < 0.8
    path = str(tmp_path / "s.n5")
    f = file_reader(path)
    f.create_dataset("raw", data=raw, chunks=(8, 16, 16))
    f.create_dataset("m", data=m.astype("uint8"), chunks=(8, 16, 16))
    config_dir = str(tmp_path / "configs")
    cfg.write_global_config(config_dir, {"block_shape": [8, 16, 16]})
    cfg.write_config(
        config_dir, "sharded_components",
        {"threshold": 0.5, "threshold_mode": "less"},
    )
    task = ShardedComponentsTask(
        str(tmp_path / "tmp"), config_dir,
        input_path=path, input_key="raw",
        output_path=path, output_key="cc",
        mask_path=path, mask_key="m",
    )
    assert build([task])
    got = file_reader(path, "r")["cc"][:]
    want, n_want = ndimage.label((raw < 0.5) & m)
    _assert_same_partition(got, want)
    assert int(file_reader(path, "r")["cc"].attrs["n_labels"]) == n_want


# -- the collective path under a global-config ROI --------------------------

ROI_CASES = {
    # (volume shape, roi begin, roi end); blocks of 8x16x16 throughout
    "aligned": ((32, 48, 48), (8, 16, 0), (24, 48, 32)),
    # z extent 10: the 8- and the 4-device mesh pad it with background
    "z_pad": ((34, 48, 48), (24, 0, 16), (34, 32, 48)),
    # a U whose arms lie in the ROI and whose base lies outside it
    "cut": ((32, 48, 48), (0, 0, 0), (16, 32, 32)),
}
SMOOTH_SIGMA = 1.5


def _roi_volume(tmp_path, rng, case):
    shape, begin, end = ROI_CASES[case]
    raw = ndimage.gaussian_filter(rng.random(shape), 1.0)
    raw = (0.5 * (raw - raw.min()) / (raw.max() - raw.min())).astype(
        "float32")
    raw[raw > 0.3] += 0.45  # components up to [0.75, 0.95]; rest <= 0.3
    if case == "cut":
        raw = np.minimum(raw, np.float32(0.3))
        raw[2:20, 20, 20] = 0.9  # two arms, in the ROI from z 2 to 15
        raw[2:20, 20, 26] = 0.9
        raw[19, 20, 20:27] = 0.9  # joined at z 19, outside the ROI
    path = str(tmp_path / "roi.n5")
    file_reader(path).create_dataset("raw", data=raw, chunks=(8, 16, 16))
    return path, raw, begin, end


def _run_components(tmp_path, path, name, begin, end, sharded, task_conf,
                    devices=None):
    config_dir = str(tmp_path / f"configs_{name}")
    gconf = {"block_shape": [8, 16, 16], "target": "tpu",
             "roi_begin": list(begin), "roi_end": list(end)}
    if devices:
        gconf["devices"] = devices
    cfg.write_global_config(config_dir, gconf)
    cfg.write_config(config_dir, "block_components", {"threshold": 0.5})
    cfg.write_config(config_dir, "sharded_components", task_conf)
    wf = ThresholdedComponentsWorkflow(
        str(tmp_path / f"tmp_{name}"), config_dir,
        input_path=path, input_key="raw",
        output_path=path, output_key=name, sharded=sharded,
    )
    assert build([wf])
    return file_reader(path, "r")[name]


@pytest.mark.parametrize("n_devices", [8, 4])
@pytest.mark.parametrize("case,ingest", [
    ("aligned", "host"), ("aligned", "device"), ("z_pad", "host"),
    ("z_pad", "device"), ("cut", "host"), ("cut", "device"),
    ("aligned", "smooth"),
])
def test_sharded_components_label_exactly_the_roi(tmp_path, rng, case,
                                                  ingest, n_devices):
    """Under a global-config ROI the collective task labels that ROI alone:
    the partition of ``scipy.ndimage.label(raw[roi] > 0.5)`` (of the whole
    volume smoothed, then cut to the ROI, with ``sigma``), and that of the
    block path over the same ROI; consecutive ids within the job, the
    volume's shape, and no chunk written outside the ROI."""
    from cluster_tools_tpu.tasks.thresholded_components import _np_smooth

    path, raw, begin, end = _roi_volume(tmp_path, rng, case)
    roi = tuple(slice(b, e) for b, e in zip(begin, end))
    conf = {"threshold": 0.5, "device_threshold": ingest == "device",
            "sigma": SMOOTH_SIGMA if ingest == "smooth" else 0.0}
    ds = _run_components(tmp_path, path, "sharded", begin, end, True, conf,
                         devices=list(range(n_devices)))
    got = ds[:]
    assert ds.shape == raw.shape

    src = _np_smooth(raw, SMOOTH_SIGMA) if ingest == "smooth" else raw
    want, n_want = ndimage.label(src[roi] > 0.5)
    assert n_want > (1 if case == "cut" else 5)
    _assert_same_partition(got[roi], want)
    ids = np.unique(got[roi])
    assert ids[0] == 0 and (np.diff(ids) == 1).all() and ids[-1] == n_want
    assert int(ds.attrs["n_labels"]) == n_want
    if case == "cut":  # the arms meet only outside the ROI
        assert got[5, 20, 20] != got[5, 20, 26]

    inside = {tuple(p) for p in np.ndindex(*[
        -(-e // c) for e, c in zip(end, ds.chunks)]) if all(
        q >= b // c for q, b, c in zip(p, begin, ds.chunks))}
    for pos in np.ndindex(*[-(-s // c) for s, c in zip(ds.shape,
                                                       ds.chunks)]):
        assert (ds.read_chunk(pos) is not None) == (pos in inside), pos

    if ingest != "smooth":  # the block path smooths block by block
        blocks = _run_components(tmp_path, path, "blocks", begin, end,
                                 False, conf)
        _assert_same_partition(got[roi], blocks[roi])
