"""tools/chip_session.derive_modes — the pin-derivation rules.

Pure-function tests: these decide the production kernel modes written to
chip_modes.json, so each rule is pinned (combined sweep total, pallas
gates requiring exactness AND a win, the slices-CC fallback, the batch
pin, and the all-errored-sweep guard upstream).
"""

import os
import sys

sys.path.insert(
    0,
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 "tools"),
)

from chip_session import derive_modes  # noqa: E402


def test_sweep_pinned_by_combined_total():
    # dtws prefers assoc, cc prefers seq; total favors assoc
    modes = derive_modes({
        "dtws_assoc_ms": 10.0, "dtws_seq_ms": 100.0,
        "cc_assoc_ms": 30.0, "cc_seq_ms": 20.0,
    })
    assert modes["CTT_SWEEP_MODE"] == "assoc"


def test_pallas_needs_exactness_and_win():
    base = {
        "dtws_assoc_ms": 10.0, "dtws_seq_ms": 12.0,
        "cc_assoc_ms": 10.0, "cc_seq_ms": 12.0,
    }
    assert "CTT_FLOOD_MODE" not in derive_modes(
        {**base, "pallas_flood_exact": True, "pallas_flood_wins": False})
    assert "CTT_FLOOD_MODE" not in derive_modes(
        {**base, "pallas_flood_exact": False, "pallas_flood_wins": True})
    assert derive_modes(
        {**base, "pallas_flood_exact": True, "pallas_flood_wins": True}
    )["CTT_FLOOD_MODE"] == "pallas"


def test_cc_slices_fallback_only_without_pallas():
    base = {
        "dtws_assoc_ms": 10.0, "dtws_seq_ms": 12.0,
        "cc_assoc_ms": 50.0, "cc_seq_ms": 60.0,
        "cc_slices_exact": True, "cc_slices_ms": 20.0,
    }
    assert derive_modes(base)["CTT_CC_MODE"] == "slices"
    # pallas wins take precedence
    won = derive_modes(
        {**base, "pallas_cc_exact": True, "pallas_cc_wins": True})
    assert won["CTT_CC_MODE"] == "pallas"
    # slices slower than the sweeps: no pin
    slow = derive_modes({**base, "cc_slices_ms": 80.0})
    assert "CTT_CC_MODE" not in slow


def test_batch_pin_passthrough():
    modes = derive_modes({
        "dtws_assoc_ms": 1.0, "dtws_seq_ms": 2.0,
        "cc_assoc_ms": 1.0, "cc_seq_ms": 2.0,
        "best_device_batch": 16,
    })
    assert modes["CTT_DEVICE_BATCH"] == "16"


def test_dtws_only_sweep_fallback():
    # without cc timings the sweep pin falls back to dtws alone
    assert derive_modes(
        {"dtws_assoc_ms": 5.0, "dtws_seq_ms": 9.0}
    )["CTT_SWEEP_MODE"] == "assoc"
    assert derive_modes(
        {"dtws_assoc_ms": 9.0, "dtws_seq_ms": 5.0}
    )["CTT_SWEEP_MODE"] == "seq"


def test_missing_measurements_pin_nothing():
    assert derive_modes({}) == {}


def test_hbm_stack_pin_requires_measured_win():
    # ctt-hbm aggregated dispatch: pinned only at >= 1.1x measured speedup
    base = {
        "dtws_assoc_ms": 1.0, "dtws_seq_ms": 2.0,
        "cc_assoc_ms": 1.0, "cc_seq_ms": 2.0,
    }
    won = derive_modes(
        {**base, "best_hbm_stack": 8, "hbm_stack_speedup": 1.35}
    )
    assert won["CTT_HBM_STACK"] == "8"
    # below the 1.1x gate: no pin (the per-batch dispatch shape stays)
    assert "CTT_HBM_STACK" not in derive_modes(
        {**base, "best_hbm_stack": 8, "hbm_stack_speedup": 1.05}
    )
    # tpu_validate records best_hbm_stack=1 when stacking lost outright
    assert "CTT_HBM_STACK" not in derive_modes(
        {**base, "best_hbm_stack": 1, "hbm_stack_speedup": 0.9}
    )
    assert "CTT_HBM_STACK" not in derive_modes(base)
