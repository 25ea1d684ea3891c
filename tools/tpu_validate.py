#!/usr/bin/env python
"""One-shot TPU validation of the backend-dependent kernel choices.

The flood and CC kernels pick between log-depth ``lax.associative_scan``
sweeps and sequential ``lax.scan`` / neighbor propagation by backend
(assoc on TPU, seq on CPU) — equivalence is CPU-tested, but the *perf* of
the assoc path needs real hardware.  Run this when the chip is reachable:

    python tools/tpu_validate.py

It times both sweep modes for the flood and CC, the fused DT-watershed, the
Pallas per-slice flood (Mosaic lowering + exactness + perf vs the XLA flood),
and the device RAG kernel, prints a table, and writes tools/tpu_validate.json.
A chip belongs to one process at a time — run nothing else against the
chip concurrently.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
from scipy import ndimage

# repo root on sys.path; bench.timeit owns the distinct-input timing scheme
# (variant 0 = sacrificial warmup, one fresh variant per timed round — see its
# docstring for the execution-cache rationale)
from bench import (  # noqa: E402
    _rolled,
    fetch_floor_s,
    rolled_pair_variants,
    timeit,
)

REPEATS = 3
SPAN = REPEATS + 1  # warmup + timed rounds — one disjoint span per sweep mode


def main():
    import jax
    import jax.numpy as jnp

    print(f"backend: {jax.default_backend()}, devices: {jax.devices()}")
    results = {"backend": jax.default_backend()}
    out_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tpu_validate.json"
    )

    def save():
        # checkpoint after every section: a run cut mid-way must not
        # lose the measurements already taken (same unlosable-contract
        # rule as bench.py driver mode).  Atomic via temp + os.replace —
        # chip_session's SIGTERM on timeout must never catch a truncating
        # in-place write and destroy the checkpoints it exists to keep
        tmp = out_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(results, f, indent=2)
        os.replace(tmp, out_path)
    # additive per-call floor of the host-fetch completion barrier every
    # timeit round ends in (~0 on a local device) — subtract
    # from sub-10ms entries when comparing kernels
    results["fetch_floor_ms"] = round(fetch_floor_s() * 1e3, 2)
    print(f"fetch floor: {results['fetch_floor_ms']} ms")

    rng = np.random.default_rng(0)
    shape = (32, 256, 256)
    raw = ndimage.gaussian_filter(rng.random(shape), (1.0, 4.0, 4.0))
    raw = ((raw - raw.min()) / (raw.max() - raw.min())).astype(np.float32)
    x = jnp.asarray(raw)
    raws = _rolled(raw, 2 * SPAN)
    xs = [jnp.asarray(v) for v in raws]
    masks = [jnp.asarray(v < 0.5) for v in raws]

    # -- flood + CC: assoc vs seq -------------------------------------------
    from cluster_tools_tpu.ops import _backend
    from cluster_tools_tpu.ops import cc as C
    from cluster_tools_tpu.ops.watershed import dt_watershed

    for i, mode in enumerate(("assoc", "seq")):
      span = slice(i * SPAN, (i + 1) * SPAN)
      with _backend.force_sweep_mode(mode):
        t = timeit(
            None, REPEATS,
            sync=lambda r: r[0].block_until_ready(),
            variants=[
                (lambda v: lambda: dt_watershed(v, threshold=0.5))(v)
                for v in xs[span]
            ],
        )
        results[f"dtws_{mode}_ms"] = round(t * 1e3, 1)
        print(f"dt_watershed[{mode}]: {t*1e3:.1f} ms "
              f"({x.size/t/1e6:.1f} Mvox/s)")
        t = timeit(
            None, REPEATS,
            sync=lambda r: r[0].block_until_ready(),
            variants=[
                (lambda m: lambda: C.connected_components(m))(m)
                for m in masks[span]
            ],
        )
        results[f"cc_{mode}_ms"] = round(t * 1e3, 1)
        print(f"connected_components[{mode}]: {t*1e3:.1f} ms")
        save()

    save()

    # -- XLA slices+z-merge CC mode (CTT_CC_MODE=slices) --------------------
    # structure of the Pallas path in plain XLA; measured 5x SLOWER on the
    # 1-core CPU fallback (both stages are round-bound) — only pinned if
    # the chip's bandwidth flips it.  Baseline pinned to the default XLA
    # path (a live pin file could otherwise make the reference the slices
    # path itself); timing runs on a FRESH disjoint input span.
    with _backend.force_cc_mode("xla"):
        want_l, want_n = C.connected_components(masks[0])
    slices_masks = [
        jnp.asarray(v < 0.5) for v in _rolled(raw, SPAN, start=2 * SPAN)
    ]
    with _backend.force_cc_mode("slices"):
        got_l, got_n = C.connected_components(masks[0])
        slices_agree = bool(jnp.array_equal(got_l, want_l)) and int(
            got_n) == int(want_n)
        results["cc_slices_exact"] = slices_agree
        t = timeit(
            None, REPEATS,
            sync=lambda r: r[0].block_until_ready(),
            variants=[
                (lambda m: lambda: C.connected_components(m))(m)
                for m in slices_masks
            ],
        )
        results["cc_slices_ms"] = round(t * 1e3, 1)
        print(f"connected_components[slices]: {t*1e3:.1f} ms "
              f"(exact={slices_agree})")

    # -- Pallas per-slice flood: Mosaic lowering + perf vs the XLA flood ----
    # (the only place the real-hardware lowering of ops/pallas_flood.py is
    # exercised — the CPU interpreter covers correctness, not Mosaic)
    from cluster_tools_tpu.ops.pallas_flood import flood_slices
    from cluster_tools_tpu.ops.watershed import (
        _seeded_watershed_scan,
        dt_seeds,
    )
    from cluster_tools_tpu.ops.dt import distance_transform_2d_stack

    fg = jnp.asarray(raw < 0.5)
    dt_f = distance_transform_2d_stack(fg)
    seeds_f, _ = dt_seeds(dt_f, sigma=2.0, per_slice=True)
    hmaps = [jnp.asarray(0.8 * v + 0.2) for v in raws]
    try:
        ref_out = _seeded_watershed_scan(hmaps[0], seeds_f, fg, per_slice=True)
        got = flood_slices(hmaps[0], seeds_f, fg)
        agree = bool(jnp.array_equal(got, ref_out))
        results["pallas_flood_exact"] = agree
        t_p = timeit(
            None, REPEATS,
            sync=lambda r: r.block_until_ready(),
            variants=[
                (lambda h: lambda: flood_slices(h, seeds_f, fg))(h)
                for h in hmaps[:SPAN]
            ],
        )
        t_x = timeit(
            None, REPEATS,
            sync=lambda r: r.block_until_ready(),
            variants=[
                (lambda h: lambda: _seeded_watershed_scan(
                    h, seeds_f, fg, per_slice=True))(h)
                for h in hmaps[SPAN : 2 * SPAN]
            ],
        )
        results["pallas_flood_ms"] = round(t_p * 1e3, 1)
        results["xla_flood_ms"] = round(t_x * 1e3, 1)
        results["pallas_flood_wins"] = t_p < t_x
        print(f"pallas flood: {t_p*1e3:.1f} ms (exact={agree}), "
              f"xla flood: {t_x*1e3:.1f} ms")
    except Exception as e:  # Mosaic lowering / runtime failure: record, go on
        results["pallas_flood_error"] = f"{type(e).__name__}: {e}"[:500]
        print(f"pallas flood FAILED to lower/run: {e}")

    save()

    # -- Pallas per-slice CC + z-merge vs the XLA CC ------------------------
    from cluster_tools_tpu.ops.pallas_cc import pallas_connected_components

    try:
        want_l, want_n = C.connected_components(masks[0])
        got_l, got_n = pallas_connected_components(masks[0])
        cc_agree = bool(jnp.array_equal(got_l, want_l)) and int(got_n) == int(
            want_n
        )
        results["pallas_cc_exact"] = cc_agree
        t_p = timeit(
            None, REPEATS,
            sync=lambda r: r[0].block_until_ready(),
            variants=[
                (lambda m: lambda: pallas_connected_components(m))(m)
                for m in masks[:SPAN]
            ],
        )
        results["pallas_cc_ms"] = round(t_p * 1e3, 1)
        results["pallas_cc_wins"] = (
            results["pallas_cc_ms"]
            < min(results["cc_assoc_ms"], results["cc_seq_ms"])
        )
        print(f"pallas cc: {t_p*1e3:.1f} ms (exact={cc_agree})")
    except Exception as e:  # Mosaic lowering / runtime failure: record, go on
        results["pallas_cc_error"] = f"{type(e).__name__}: {e}"[:500]
        print(f"pallas cc FAILED to lower/run: {e}")

    save()

    # -- device RAG kernel vs numpy -----------------------------------------
    from cluster_tools_tpu import native
    from cluster_tools_tpu.ops import rag

    labels, _ = native.dt_watershed_cpu(raw, threshold=0.5)
    # the production wrapper packs the sort key whenever the compact label
    # space fits 15 bits AND compacts valid face rows before the sort —
    # measure the same path (cap maxed over the rolled variants, whose
    # wrap seams add boundary faces)
    packed = int(labels.max()) <= rag.PACK_MAX_ID
    lab32 = labels.astype(np.int32)
    cap = rag.sample_capacity(max(
        rag.count_boundary_samples(np.roll(lab32, 7 * i, axis=1) if i else lab32)
        for i in range(SPAN)
    ))
    t_dev = timeit(
        None, REPEATS,
        sync=lambda r: r[0].block_until_ready(),
        variants=rolled_pair_variants(
            raw, lab32, SPAN,
            lambda l, v: rag.boundary_edge_features_device(
                l, v, max_edges=65536, packed=packed, max_samples=cap),
        ),
    )
    results["rag_packed"] = bool(packed)
    results["rag_sample_cap"] = int(cap)
    t0 = time.perf_counter()
    rag.boundary_edge_features(labels.astype(np.uint64), raw)
    t_host = time.perf_counter() - t0
    results["rag_device_ms"] = round(t_dev * 1e3, 1)
    results["rag_numpy_ms"] = round(t_host * 1e3, 1)
    print(f"rag device: {t_dev*1e3:.1f} ms, numpy: {t_host*1e3:.1f} ms")

    save()

    # -- device MWS vs host C++ (CTT_MWS_MODE pin) --------------------------
    # the graph-domain device kernel on the bench's realistic bimodal
    # affinity problem (doomed-pair discard keeps rounds low since r5);
    # the winner decides whether per-block MWS solves route to the device
    try:
        from scipy import ndimage as _ndi

        from cluster_tools_tpu.ops.mws import _affinity_edge_lists
        from cluster_tools_tpu.ops.mws_device import (
            mutex_watershed_device, mutex_watershed_device_rounds,
        )

        offsets = [[-1, 0, 0], [0, -1, 0], [0, 0, -1],
                   [-2, 0, 0], [0, -4, 0], [0, 0, -4]]
        mws_shape = (8, 32, 32)
        mws_rng = np.random.default_rng(1)
        affs = _ndi.gaussian_filter(
            mws_rng.random((len(offsets),) + mws_shape).astype(np.float32),
            (0, 1, 2, 2),
        )
        n_mws = int(np.prod(mws_shape))
        # one problem per rolled affinity volume: distinct inputs per timed
        # round (execution-result caches), conversions prepared OUTSIDE the
        # timed window, and the pin decided by timeit like every other
        # pin-deciding section — one RTT spike must not flip CTT_MWS_MODE
        problems = []
        for i in range(SPAN):
            a_i = np.roll(affs, 3 * i, axis=2) if i else affs
            us, vs, ws_l, at_l = _affinity_edge_lists(
                a_i, offsets, [1, 2, 2], False, 0.0,
                np.random.default_rng(0), 3,
            )
            uv = np.stack([np.concatenate(us), np.concatenate(vs)], axis=1)
            w = np.concatenate(ws_l).astype(np.float32)
            at = np.concatenate(at_l).astype(bool)
            problems.append(
                (uv, w, at, uv.astype(np.int64), w.astype(np.float64),
                 at.astype(np.uint8))
            )
        results["mws_device_rounds"] = mutex_watershed_device_rounds(
            n_mws, *problems[0][:3]
        )
        t_mws_dev = timeit(
            None, REPEATS,
            variants=[
                (lambda p: lambda: mutex_watershed_device(n_mws, *p[:3]))(p)
                for p in problems
            ],
        )
        t_mws_host = timeit(
            None, REPEATS,
            variants=[
                (lambda p: lambda: native.mutex_watershed(n_mws, *p[3:]))(p)
                for p in problems
            ],
        )
        results["mws_device_ms"] = round(t_mws_dev * 1e3, 1)
        results["mws_host_ms"] = round(t_mws_host * 1e3, 1)
        results["mws_device_wins"] = t_mws_dev < t_mws_host
        print(f"mws device: {t_mws_dev*1e3:.1f} ms "
              f"({results['mws_device_rounds']} rounds), "
              f"host C++: {t_mws_host*1e3:.1f} ms")
    except Exception as e:
        results["mws_device_error"] = f"{type(e).__name__}: {e}"[:500]
        print(f"mws device FAILED: {e}")

    save()

    # -- device batch-size sweep (CTT_DEVICE_BATCH pin) ---------------------
    # per-block voxel rate of the vmapped DT-watershed at several batch
    # sizes: a batch amortizes dispatch latency but vmap can
    # serialize while_loop rounds across the batch (max-over-batch) — only
    # measurement can pick the winner for a backend
    block = raw[:16, :128, :128]
    best_rate, best_bs = -1.0, 1
    for bs in (1, 4, 8, 16):
        fn = jax.jit(jax.vmap(lambda v: dt_watershed(v, threshold=0.5)[0]))
        stacks = [
            jnp.asarray(np.stack([
                np.roll(v, j + 1, axis=1) for j in range(bs)
            ]))
            for v in _rolled(block, SPAN)
        ]
        try:
            t = timeit(
                None, REPEATS,
                sync=lambda r: r.block_until_ready(),
                variants=[(lambda s: lambda: fn(s))(s) for s in stacks],
            )
        except Exception as e:
            results[f"batch{bs}_error"] = f"{type(e).__name__}: {e}"[:200]
            continue
        rate = bs * block.size / t / 1e6
        results[f"batch{bs}_mvox_s"] = round(rate, 1)
        print(f"batch sweep x{bs}: {t*1e3:.1f} ms ({rate:.1f} Mvox/s)")
        if rate > best_rate:
            best_rate, best_bs = rate, bs
    if best_rate > 0:  # never pin from an all-errored sweep
        results["best_device_batch"] = best_bs

    save()

    # -- aggregated dispatch sweep (CTT_HBM_STACK pin, ctt-hbm) -------------
    # k read payloads stacked into ONE (k*B, ...) dispatch vs k separate
    # dispatches of the same vmapped kernel: aggregation amortizes
    # dispatch latency on a compute-light (dispatch-bound) kernel —
    # the threshold shape, the workload hbm_stack targets.  Pinned (by
    # chip_session.derive_modes) only where the measured win is >= 1.1x,
    # so work-bound backends keep the per-batch dispatch shape.
    try:
        thr_block = raw[:8, :64, :64]
        thr_fn = jax.jit(jax.vmap(lambda v: (v > 0.5).astype(jnp.uint8)))
        stack_k, stack_b = 8, 4
        singles = [
            [
                jnp.asarray(np.stack([
                    np.roll(v, 3 * j + k + 1, axis=1)
                    for j in range(stack_b)
                ]))
                for k in range(stack_k)
            ]
            for v in _rolled(thr_block, SPAN)
        ]
        stacks = [
            jnp.concatenate(parts, axis=0) for parts in singles
        ]
        t_single = timeit(
            None, REPEATS,
            sync=lambda r: r[-1].block_until_ready(),
            variants=[
                (lambda parts: lambda: [thr_fn(p) for p in parts])(parts)
                for parts in singles
            ],
        )
        t_stacked = timeit(
            None, REPEATS,
            sync=lambda r: r.block_until_ready(),
            variants=[(lambda s: lambda: thr_fn(s))(s) for s in stacks],
        )
        results["hbm_single_ms"] = round(t_single * 1e3, 2)
        results["hbm_stacked_ms"] = round(t_stacked * 1e3, 2)
        speedup = t_single / max(t_stacked, 1e-9)
        results["hbm_stack_speedup"] = round(speedup, 2)
        results["best_hbm_stack"] = stack_k if speedup >= 1.1 else 1
        print(f"hbm stack x{stack_k}: {t_single*1e3:.2f} ms separate -> "
              f"{t_stacked*1e3:.2f} ms stacked ({speedup:.2f}x)")
    except Exception as e:
        results["hbm_stack_error"] = f"{type(e).__name__}: {e}"[:200]
        print(f"hbm stack sweep FAILED: {e}")

    save()

    # -- verdicts ------------------------------------------------------------
    results["flood_assoc_wins"] = results["dtws_assoc_ms"] < results["dtws_seq_ms"]
    results["cc_assoc_wins"] = results["cc_assoc_ms"] < results["cc_seq_ms"]
    results["rag_device_wins"] = results["rag_device_ms"] < results["rag_numpy_ms"]
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "tpu_validate.json")
    with open(out, "w") as f:
        json.dump(results, f, indent=2)
    print(json.dumps(results))
    print(f"-> {out}")
    if not results["flood_assoc_wins"] or not results["cc_assoc_wins"]:
        print("NOTE: an assoc path lost on this backend — consider flipping "
              "the default in _use_assoc() for it.")


if __name__ == "__main__":
    main()
