#!/usr/bin/env python
"""One-shot TPU session: probe → validate → pin modes → bench.

A chip belongs to one process at a time, so a chip session is a single,
sequential run in which each step is its own process:

    python tools/chip_session.py            # full session
    python tools/chip_session.py --dry      # probe only

Steps:
  1. disposable-subprocess jax probe (600 s) requiring a real TPU device;
  2. tools/tpu_validate.py (assoc-vs-seq, Pallas flood + Pallas CC
     lowering/exactness/perf, device RAG) → tools/tpu_validate.json;
  3. derive the production mode pins (CTT_SWEEP_MODE / CTT_FLOOD_MODE /
     CTT_CC_MODE) from the measurements
     → tools/chip_modes.json;
  4. bench.py (driver mode) with those pins exported → the BENCH JSON line
     on stdout (the last line, as the driver expects).

Both artifacts (tools/tpu_validate.json, tools/chip_modes.json) are MEANT
to be committed: the validate record is the audit trail of what ran on
silicon, and the backend-tagged pin file is how plain `python bench.py` and
production runs inherit the measured mode winners (ops/_backend.py loads
it; env vars override; non-matching backends ignore it).
"""

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def jax_probe(timeout: float = 600.0) -> bool:
    """Disposable-subprocess probe requiring a real TPU device.

    Generous timeout + SIGTERM-first escalation, so a slow device init
    gets every chance to answer and the child releases the chip cleanly."""
    proc = subprocess.Popen(
        [sys.executable, "-c",
         "import sys, jax; jax.devices(); "
         "sys.exit(0 if jax.default_backend() == 'tpu' else 3)"],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    try:
        return proc.wait(timeout=timeout) == 0
    except subprocess.TimeoutExpired:
        proc.terminate()
        try:
            proc.wait(timeout=15)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
        return False


def derive_modes(results: dict) -> dict:
    """Production mode pins from tpu_validate measurements.

    CTT_SWEEP_MODE is one global switch consumed by BOTH the watershed
    sweeps and the CC sweeps — pin it on their combined time and report a
    disagreement rather than letting dtws alone decide."""
    modes = {}
    if all(k in results for k in
           ("dtws_assoc_ms", "dtws_seq_ms", "cc_assoc_ms", "cc_seq_ms")):
        assoc = results["dtws_assoc_ms"] + results["cc_assoc_ms"]
        seq = results["dtws_seq_ms"] + results["cc_seq_ms"]
        modes["CTT_SWEEP_MODE"] = "assoc" if assoc <= seq else "seq"
        dtws_pick = results["dtws_assoc_ms"] <= results["dtws_seq_ms"]
        cc_pick = results["cc_assoc_ms"] <= results["cc_seq_ms"]
        if dtws_pick != cc_pick:
            log("NOTE: dtws and cc prefer different sweep modes "
                f"(dtws→{'assoc' if dtws_pick else 'seq'}, "
                f"cc→{'assoc' if cc_pick else 'seq'}); pinned by total")
    elif "dtws_assoc_ms" in results and "dtws_seq_ms" in results:
        modes["CTT_SWEEP_MODE"] = (
            "assoc" if results["dtws_assoc_ms"] <= results["dtws_seq_ms"]
            else "seq"
        )
    if results.get("pallas_flood_exact") and results.get("pallas_flood_wins"):
        modes["CTT_FLOOD_MODE"] = "pallas"
    if results.get("pallas_cc_exact") and results.get("pallas_cc_wins"):
        modes["CTT_CC_MODE"] = "pallas"
    elif (
        results.get("cc_slices_exact")
        and "cc_slices_ms" in results
        and "cc_assoc_ms" in results
        and "cc_seq_ms" in results
        and results["cc_slices_ms"]
        < min(results["cc_assoc_ms"], results["cc_seq_ms"])
    ):
        modes["CTT_CC_MODE"] = "slices"
    if "best_device_batch" in results:
        modes["CTT_DEVICE_BATCH"] = str(results["best_device_batch"])
    # ctt-hbm aggregated dispatch: pin a measured stack depth only where
    # stacking k payloads into one dispatch won by >= 1.1x on this backend
    # (work-bound backends keep the per-batch dispatch shape); the pin
    # makes aggregation the DEFAULT via runtime/hbm.py::hbm_stack, same
    # precedence as CTT_DEVICE_BATCH (env > pin file > off)
    if (
        results.get("best_hbm_stack", 1) > 1
        and results.get("hbm_stack_speedup", 0.0) >= 1.1
    ):
        modes["CTT_HBM_STACK"] = str(results["best_hbm_stack"])
    # graph-domain MWS: route to the device kernel only when it measurably
    # beats the host C++ on this backend; pin host explicitly otherwise so
    # the measured default is recorded either way (VERDICT r4 item 4)
    if "mws_device_ms" in results and "mws_host_ms" in results:
        modes["CTT_MWS_MODE"] = (
            "device" if results.get("mws_device_wins") else "host"
        )
    return modes


def main():
    log("probing jax (disposable subprocess, 600 s cap)")
    if "--dry" in sys.argv:
        alive = jax_probe()
        log(f"jax probe: {'TPU alive' if alive else 'unreachable'}")
        return 0 if alive else 2
    if not jax_probe():
        log("no TPU device — aborting")
        return 2

    log("== tpu_validate ==")
    # SIGTERM-first timeout, so the child releases the chip cleanly;
    # tpu_validate checkpoints its JSON after every section, so even a
    # timed-out run leaves pins to derive from.  Remove any artifact from
    # a previous round first: deriving pins from a stale file measured
    # against old kernel code would masquerade as a fresh measurement.
    stale = os.path.join(HERE, "tpu_validate.json")
    if os.path.exists(stale):
        os.replace(stale, stale + ".prev")
        log("moved previous tpu_validate.json aside (-> .prev)")
    proc = subprocess.Popen(
        [sys.executable, os.path.join(HERE, "tpu_validate.py")], cwd=ROOT
    )
    try:
        rc = proc.wait(timeout=1800)
    except subprocess.TimeoutExpired:
        log("tpu_validate over its 1800 s budget; terminating (checkpointed "
            "sections survive)")
        proc.terminate()
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
        rc = -1
    modes = {}
    if rc != 0:
        log(f"tpu_validate failed (rc={rc}); deriving pins from whatever "
            "sections checkpointed")
    try:
        with open(os.path.join(HERE, "tpu_validate.json")) as f:
            results = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        log(f"tpu_validate.json unreadable ({e}); bench runs unpinned")
    else:
        modes = derive_modes(results)
        # backend-tagged pin file: ops/_backend.py loads it as the
        # default mode source (env vars still override) ONLY when the
        # running backend matches — so the driver's plain `python
        # bench.py` and production runs get the measured winners
        # without leaking TPU pins into CPU runs.
        with open(os.path.join(HERE, "chip_modes.json"), "w") as f:
            json.dump(
                {"backend": results.get("backend", "tpu"),
                 "modes": modes}, f, indent=2)
        log(f"mode pins: {modes}")

    log("== bench (driver mode) ==")
    env = dict(os.environ, **modes)
    bench = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench.py")], cwd=ROOT, env=env
    )
    return bench.returncode


if __name__ == "__main__":
    sys.exit(main())
