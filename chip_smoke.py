#!/usr/bin/env python
"""Bring-up smoke: the block-wise segmentation workflows on a TPU.

    python chip_smoke.py             # one chip: main path + Pallas kernels
    python chip_smoke.py --chips 4   # four chips: the collective path only

Drives the system through its user entry point, ``runtime.build()`` with
``target: "tpu"``, on a seeded CREMI-calibrated boundary volume at the
in-plane size of CREMI sample A (1250x1250; z cut, see below) with the
production block shape [50, 512, 512], and checks every output against
an exact host oracle (scipy / numpy).  Every run is traced
(``CTT_TRACE_DIR``), and a run that went through a fallback (collective
to local kernel, fused chain to task by task, batch to block by block)
fails.

One chip: ThresholdedComponentsWorkflow, WatershedWorkflow and
MulticutSegmentationWorkflow(skip_ws=True), then each Pallas kernel once,
compared bit for bit with the XLA path.  Four chips: block batches of
ThresholdedComponentsWorkflow, one block per chip; the sharded components
path; the sharded seeded flood against the single-device flood; the fused
sharded watershed + problem multicut.

Refuses to run without a TPU.  The last stdout line is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``; it is printed
only when every phase passed.  These are bring-up facts, not benchmark
numbers.
"""

import argparse
import glob
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

# CREMI sample A is 125x1250x1250 (z, y, x) at 40x4x4 nm.  Its z extent is
# cut to one block layer so a cold run (compiles included) fits 15 minutes
# on one v5e; the block shape and the in-plane size are never cut.
FULL_SHAPE = (125, 1250, 1250)
SHAPE = (50, 1250, 1250)
BLOCK = (50, 512, 512)  # runtime/config.py default block_shape
# Four chips cost four times as much per second, and the collective
# programs compile cold: the four-chip phase takes the first 16 slices of
# the same volume (4 per chip).
Z_FOUR_CHIPS = 16
# static edge capacity of the collective RAG table for that volume
MAX_EDGES_4 = 1 << 18
SEED = 0
THRESHOLD = 0.5  # ThresholdedComponents' default threshold
HERE = os.path.dirname(os.path.abspath(__file__))


class SmokeError(RuntimeError):
    """A phase produced a wrong result or ran through a fallback."""


def log(msg):
    print(f"[smoke] {msg}", flush=True)


def check(ok, what):
    if not ok:
        raise SmokeError(what)
    log(f"PASS {what}")


# -- set-up -----------------------------------------------------------------


def build_native():
    out = subprocess.run(
        [sys.executable, "-m", "cluster_tools_tpu.native.build"],
        cwd=HERE, capture_output=True, text=True, timeout=600,
    )
    if out.returncode != 0:
        raise SmokeError(f"native solver build failed:\n{out.stdout}{out.stderr}")
    from cluster_tools_tpu import native

    check(native.available(), "native solvers built and loaded")


def device_info(n_chips):
    """The device as JAX reports it; refuses anything but n_chips TPUs."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SmokeError(
            f"no TPU: jax found {len(devs)} {devs[0].platform} device(s); "
            "this smoke runs on the chip only"
        )
    if len(devs) != n_chips:
        raise SmokeError(f"expected {n_chips} TPU chip(s), jax found {len(devs)}")
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def print_versions(dev):
    import importlib.metadata as md

    import jax
    import jaxlib

    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = "not installed"
    log(f"device: platform={dev['platform']} kind={dev['kind']} "
        f"count={dev['count']}")
    log(f"versions: jax {jax.__version__}, jaxlib {jaxlib.__version__}, "
        f"libtpu {libtpu}")


def write_volume(root, z):
    """The seeded volume at SHAPE, its first ``z`` slices written as n5."""
    from cluster_tools_tpu.utils import file_reader
    from cluster_tools_tpu.utils.synthetic import make_volume

    t0 = time.perf_counter()
    raw = make_volume(SHAPE, seed=SEED)[:z]
    path = os.path.join(root, "data.n5")
    file_reader(path).create_dataset("raw", data=raw, chunks=BLOCK)
    if raw.shape != FULL_SHAPE:
        log(f"cut: z extent {FULL_SHAPE[0]} -> {z} (CREMI sample A is "
            f"{FULL_SHAPE}); block shape and in-plane size uncut")
    log(f"volume {raw.shape} seed {SEED}: {(raw > THRESHOLD).mean():.4f} "
        f"boundary fraction, written as n5 with chunks {BLOCK} "
        f"({time.perf_counter() - t0:.1f} s)")
    return path, raw


def run_workflow(name, wf, tmp_folder):
    from bench_e2e_lib import task_breakdown
    from cluster_tools_tpu.runtime import build

    t0 = time.perf_counter()
    ok = build([wf])
    wall = time.perf_counter() - t0
    if not ok:
        raise SmokeError(f"{name}: build() failed (see {tmp_folder}/logs)")
    walls = " ".join(
        f"{k}={v}" for k, v in sorted(task_breakdown(tmp_folder).items())
    )
    log(f"{name}: wall {wall:.1f} s; task busy seconds: {walls}")
    check_fallback_counters(name)


# -- oracles ----------------------------------------------------------------


def check_components(got, raw, what):
    from scipy import ndimage

    from cluster_tools_tpu.ops.evaluation import same_partition

    want, n = ndimage.label(raw > THRESHOLD)
    log(f"{what}: {n} components (scipy)")
    check(same_partition(got, want),
          f"{what} == scipy.ndimage.label(raw > {THRESHOLD}) partition")


def host_edges(ws):
    """``ops.rag.block_edges`` over the whole volume, computed slab by slab
    (each slab shares one plane with the next, so every z-face is seen)."""
    import numpy as np

    from cluster_tools_tpu.ops.rag import block_edges

    step = 16
    parts = [
        block_edges(ws[z: z + step + 1])
        for z in range(0, ws.shape[0] - 1, step)
    ]
    return np.unique(np.concatenate(parts, axis=0), axis=0)


def check_problem(tmp_folder, ws, what):
    import numpy as np

    from cluster_tools_tpu.utils import file_reader

    store = file_reader(os.path.join(tmp_folder, "data.zarr"), "r")
    nodes = store["graph/nodes"][:]
    edges = store["graph/edges"][:]
    got = np.sort(nodes[edges].astype(np.uint64), axis=1)
    got = np.unique(got, axis=0)
    want = host_edges(ws.astype(np.uint64))
    log(f"{what}: {len(np.unique(ws[ws > 0]))} fragments, {len(got)} graph "
        f"edges ({len(want)} by host recompute)")
    check(got.shape == want.shape and (got == want).all(),
          f"{what} graph edges == ops.rag.block_edges on the written watershed")


def check_coarsening(ws, seg, what):
    import numpy as np

    fg = ws > 0
    wsf, segf = ws[fg].astype(np.uint64), seg[fg].astype(np.uint64)
    if wsf.max() >= 1 << 32 or segf.max() >= 1 << 32:
        raise SmokeError(f"{what}: ids beyond 32 bits")
    # one 64-bit key per (fragment, segment) pair
    pairs = np.unique((wsf << np.uint64(32)) | segf)
    n_frag = len(np.unique(pairs >> np.uint64(32)))
    log(f"{what}: {n_frag} fragments -> {len(np.unique(segf))} segments")
    check(len(pairs) == n_frag, f"{what} segmentation coarsens the fragments")


def check_fallback_counters(what):
    from cluster_tools_tpu.obs import metrics

    counters = metrics.snapshot()["counters"]
    for name in ("sharded.fallback_local", "stream.fallbacks"):
        check(counters.get(name, 0) == 0, f"{what}: counter {name} == 0")
    return counters


def check_no_fallback():
    from cluster_tools_tpu.obs import trace

    trace.flush()
    counters = check_fallback_counters("all phases")
    n_spans = n_fallback = 0
    for path in glob.glob(os.path.join(trace.run_dir(), "spans.*.jsonl")):
        with open(path) as f:
            for line in f:
                rec = json.loads(line)
                if rec.get("type") == "span":
                    n_spans += 1
                    n_fallback += rec.get("name") == "block_fallback"
    check(n_spans > 0, f"trace recorded ({n_spans} spans)")
    check(n_fallback == 0, "no block_fallback span")
    log("compile cache: "
        f"{int(counters.get('compile_cache.cache_hits', 0))} hits, "
        f"{int(counters.get('compile_cache.cache_misses', 0))} misses")


# -- phases -----------------------------------------------------------------


def write_global_config(config_dir, devices=None, **extra):
    from cluster_tools_tpu.runtime import config as cfg

    conf = {"block_shape": list(BLOCK), "target": "tpu", **extra}
    if devices is not None:
        conf["devices"] = devices
    cfg.write_global_config(config_dir, conf)


def one_chip_main_path(root, path, raw):
    import numpy as np

    from cluster_tools_tpu.utils import file_reader
    from cluster_tools_tpu.workflows import (
        MulticutSegmentationWorkflow,
        ThresholdedComponentsWorkflow,
    )
    from cluster_tools_tpu.workflows.watershed import WatershedWorkflow

    config_dir = os.path.join(root, "configs")
    write_global_config(config_dir)
    tmp = lambda name: os.path.join(root, f"tmp_{name}")  # noqa: E731

    run_workflow("ThresholdedComponentsWorkflow", ThresholdedComponentsWorkflow(
        tmp("cc"), config_dir, input_path=path, input_key="raw",
        output_path=path, output_key="components",
    ), tmp("cc"))
    check_components(file_reader(path, "r")["components"][:], raw,
                     "components")

    run_workflow("WatershedWorkflow", WatershedWorkflow(
        tmp("ws"), config_dir, input_path=path, input_key="raw",
        output_path=path, output_key="watershed",
    ), tmp("ws"))
    ws = file_reader(path, "r")["watershed"][:]
    check(ws.shape == SHAPE and ws.max() > 0, "watershed written, non-empty")

    run_workflow("MulticutSegmentationWorkflow", MulticutSegmentationWorkflow(
        tmp("mc"), config_dir, input_path=path, input_key="raw",
        ws_path=path, ws_key="watershed",
        output_path=path, output_key="segmentation", skip_ws=True,
    ), tmp("mc"))
    check_problem(tmp("mc"), ws, "multicut problem")
    seg = file_reader(path, "r")["segmentation"][:]
    check_coarsening(ws, seg.astype(np.uint64), "multicut")


def compile_once(fn, *args):
    """``fn`` jitted, lowered and compiled for ``args``, and its result:
    the compiled program's text is what ran."""
    import jax
    import numpy as np

    compiled = jax.jit(fn).lower(*args).compile()
    out = jax.tree_util.tree_map(np.asarray, compiled(*args))
    return compiled.as_text(), out


def one_chip_pallas(raw):
    """Each Pallas kernel once through its entry point under
    ``force_*_mode("pallas")``, bit for bit against the XLA path, with the
    kernel's Mosaic custom call asserted in the program that ran."""
    import jax
    import jax.numpy as jnp

    from cluster_tools_tpu.ops import _backend
    from cluster_tools_tpu.ops.cc import connected_components
    from cluster_tools_tpu.ops.dt import distance_transform_2d_stack
    from cluster_tools_tpu.ops.pallas_cc import (
        pallas_cc_tile,
        pallas_connected_components_tiled,
    )
    from cluster_tools_tpu.ops.pallas_flood import _MAX_SLICE_ELEMS
    from cluster_tools_tpu.ops.watershed import (
        dt_seeds,
        seeded_watershed,
    )

    def case(name, mode, want, pallas_fn, *args):
        t0 = time.perf_counter()
        with mode("pallas"):
            text, got = compile_once(pallas_fn, *args)
        check("tpu_custom_call" in text, f"{name}: Mosaic kernel in the program")
        leaves = zip(jax.tree_util.tree_leaves(got),
                     jax.tree_util.tree_leaves(want))
        same = all(g.shape == w.shape and (g == w).all() for g, w in leaves)
        check(same, f"{name} == XLA path, bit for bit "
              f"({time.perf_counter() - t0:.1f} s)")

    # CC: whole-slice kernel at the production block, and the tiled kernel
    mask = jnp.asarray(raw[:50, :512, :512] > THRESHOLD)
    cc = lambda m: connected_components(m, connectivity=1)  # noqa: E731
    _, want = compile_once(cc, mask)
    case(f"cc_slices {tuple(mask.shape)}", _backend.force_cc_mode, want, cc,
         mask)
    tile = pallas_cc_tile(mask.shape)
    case(f"cc_tiles {tuple(mask.shape)} tile {tile}", _backend.force_cc_mode,
         want, lambda m: pallas_connected_components_tiled(m, tile), mask)

    # flood: whole-slice kernel at the largest slice its gate admits, and
    # the tiled warm start at the production block
    w = 512
    h = _MAX_SLICE_ELEMS // w
    for shape, coarse_tile, kernel in [
        ((50, h, w), None, "flood_slices"),
        ((50, 512, 512), (1, 256, 512), "flood_tiles_warm"),
    ]:
        hmap = jnp.asarray(raw[: shape[0], : shape[1], : shape[2]])
        fg = hmap < THRESHOLD
        seeds, _ = dt_seeds(distance_transform_2d_stack(fg), sigma=2.0,
                            per_slice=True)
        flood = lambda hm, sd, m, t=coarse_tile: seeded_watershed(  # noqa: E731
            hm, sd, m, per_slice=True, coarse_tile=t)
        case(f"{kernel} {tuple(hmap.shape)}", _backend.force_flood_mode,
             compile_once(flood, hmap, seeds, fg)[1], flood, hmap, seeds, fg)


def four_chip_collective(root, path, raw):
    """The collective path over four chips, cheapest phase first, each
    checked by the same host oracles as on one chip."""
    import jax.numpy as jnp
    import numpy as np

    from cluster_tools_tpu.ops.watershed import seeded_watershed
    from cluster_tools_tpu.parallel import mesh as mesh_mod
    from cluster_tools_tpu.parallel.sharded import sharded_seeded_watershed
    from cluster_tools_tpu.runtime import config as cfg
    from cluster_tools_tpu.utils import file_reader
    from cluster_tools_tpu.workflows import (
        MulticutSegmentationWorkflow,
        ThresholdedComponentsWorkflow,
    )

    devices = list(range(4))
    tmp = lambda name: os.path.join(root, f"tmp_{name}")  # noqa: E731
    mesh = mesh_mod.get_mesh(mesh_mod.resolve_devices({"devices": devices}))
    check(mesh.devices.size == 4, "collective mesh spans 4 devices")

    # Block batches, data-parallel over the mesh: 2x2 blocks, one per chip.
    # The full 3x3 grid would end on a one-block batch, which put_sharded
    # places on one device and last_batch_sharding then reports.
    roi_end = [raw.shape[0], 2 * BLOCK[1], 2 * BLOCK[2]]
    roi_dir = os.path.join(root, "configs_roi")
    write_global_config(roi_dir, devices,
                        roi_begin=[0, 0, 0], roi_end=roi_end)
    mesh_mod._LAST_BATCH_SHARDING = None
    run_workflow("ThresholdedComponentsWorkflow(block batches, 2x2 blocks)",
                 ThresholdedComponentsWorkflow(
                     tmp("cc_blocks"), roi_dir, input_path=path,
                     input_key="raw", output_path=path,
                     output_key="components_blocks",
                 ), tmp("cc_blocks"))
    batch = mesh_mod.last_batch_sharding()
    check(batch is not None and len(batch.device_set) == 4,
          f"block batch spanned 4 devices ({batch})")
    roi = tuple(slice(0, e) for e in roi_end)
    check_components(file_reader(path, "r")["components_blocks"][roi],
                     raw[roi], "block-batch components (2x2 blocks)")

    config_dir = os.path.join(root, "configs")
    write_global_config(config_dir, devices)
    # the collective edge table is static-shape: size it for this volume
    cfg.write_config(config_dir, "sharded_ws_problem",
                     {"max_edges": MAX_EDGES_4})
    run_workflow("ThresholdedComponentsWorkflow(sharded)",
                 ThresholdedComponentsWorkflow(
                     tmp("cc"), config_dir, input_path=path, input_key="raw",
                     output_path=path, output_key="components", sharded=True,
                 ), tmp("cc"))
    check_components(file_reader(path, "r")["components"][:], raw,
                     "sharded components")

    # sharded flood vs the single-device flood, bit for bit, at the shape
    # the sharded watershed below floods (so it reuses this compile)
    t0 = time.perf_counter()
    rng = np.random.default_rng(SEED)
    sd = np.zeros(raw.shape, np.int32)
    pts = rng.choice(raw.size, 64, replace=False)
    sd.reshape(-1)[pts] = np.arange(1, 65, dtype=np.int32)
    fg = raw < THRESHOLD
    got = sharded_seeded_watershed(raw, sd, fg, mesh=mesh)
    check(len(got.sharding.device_set) == 4,
          "sharded flood output spans 4 devices")
    want = seeded_watershed(jnp.asarray(raw), jnp.asarray(sd),
                            jnp.asarray(fg))
    check((np.asarray(got) == np.asarray(want)).all(),
          f"sharded_seeded_watershed {raw.shape} == seeded_watershed, "
          f"bit for bit ({time.perf_counter() - t0:.1f} s)")

    run_workflow("MulticutSegmentationWorkflow(sharded)",
                 MulticutSegmentationWorkflow(
                     tmp("mc"), config_dir, input_path=path, input_key="raw",
                     ws_path=path, ws_key="watershed",
                     output_path=path, output_key="segmentation",
                     sharded_problem=True, sharded_ws=True,
                 ), tmp("mc"))
    ws = file_reader(path, "r")["watershed"][:]
    check_problem(tmp("mc"), ws, "sharded problem")
    seg = file_reader(path, "r")["segmentation"][:]
    check_coarsening(ws, seg.astype(np.uint64), "sharded multicut")


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: run only the collective path over four chips",
    )
    args = parser.parse_args(argv)
    t_start = time.perf_counter()
    root = tempfile.mkdtemp(prefix="ctt_smoke_")
    # spans and counters of every phase; read back by check_no_fallback
    os.environ["CTT_TRACE_DIR"] = os.path.join(root, "trace")
    try:
        build_native()
        dev = device_info(args.chips)
        print_versions(dev)
        from cluster_tools_tpu.utils.compile_cache import enable_compile_cache

        log(f"compile cache: {enable_compile_cache()}")
        z = Z_FOUR_CHIPS if args.chips == 4 else SHAPE[0]
        path, raw = write_volume(root, z)
        if args.chips == 4:
            four_chip_collective(root, path, raw)
        else:
            one_chip_main_path(root, path, raw)
            one_chip_pallas(raw)
        check_no_fallback()
    except SmokeError as e:
        print(f"[smoke] FAIL: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        shutil.rmtree(root, ignore_errors=True)
    log(f"all phases passed in {time.perf_counter() - t_start:.1f} s")
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
