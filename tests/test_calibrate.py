"""Measured device-routing calibration (utils/calibrate.py).

Thresholds derive from the observed link physics (latency/bandwidth), so
a locally attached chip routes bench-scale work to the device with no
code changes.  These tests drive the derivation math with synthetic
profiles (slow link vs HBM-adjacent), smoke the real probe, and pin that
a failed probe on a TPU backend raises instead of routing to the host.
"""

from __future__ import annotations

import pytest

from hyperspace_tpu.config import HyperspaceConf
from hyperspace_tpu.utils import calibrate
from hyperspace_tpu.utils.calibrate import (
    NEVER_MIN_ROWS,
    STATIC_MIN_ROWS,
    DeviceProfile,
)

HOST_RATES = {"filter": 1.2e9, "join": 3.0e7, "agg": 2.0e7, "build": 2.5e7}

SLOW_LINK = DeviceProfile(platform="tpu", latency_s=0.1,
                       h2d_bytes_per_s=4e6, d2h_bytes_per_s=4e6,
                       host_rows_per_s=HOST_RATES)
LOCAL = DeviceProfile(platform="tpu", latency_s=2e-4,
                      h2d_bytes_per_s=12e9, d2h_bytes_per_s=12e9,
                      host_rows_per_s=HOST_RATES)


def test_slow_link_profile_never_routes_to_device():
    """~4 MB/s transfer: per-row shipping exceeds any host per-row cost,
    so every kind calibrates to the 'never organically' sentinel."""
    for kind in STATIC_MIN_ROWS:
        assert SLOW_LINK.min_rows(kind) == NEVER_MIN_ROWS, kind


def test_local_profile_routes_bench_scale_work_to_device():
    """GB/s link: the 6M-row bench tables clear the calibrated
    join/agg/build thresholds — the chip is used without code changes."""
    for kind in ("join", "agg", "build"):
        assert LOCAL.min_rows(kind) < 6_000_000, (kind, LOCAL.min_rows(kind))
    # Filters are host-friendly (arrow scans ~1e9 rows/s): even a 12 GB/s
    # link cannot repay shipping two columns for one compare, so the
    # honest answer stays "never organically" — filters go to the device
    # through the resident cache, not through cold transfers.
    assert LOCAL.min_rows("filter") == NEVER_MIN_ROWS
    hbm_adjacent = DeviceProfile(platform="tpu", latency_s=5e-5,
                                 h2d_bytes_per_s=2e11,
                                 d2h_bytes_per_s=2e11,
                                 host_rows_per_s=HOST_RATES)
    assert hbm_adjacent.min_rows("filter") < NEVER_MIN_ROWS


def test_threshold_monotone_in_latency_and_bandwidth():
    slower = DeviceProfile(platform="tpu", latency_s=2e-3,
                           h2d_bytes_per_s=12e9, d2h_bytes_per_s=12e9,
                           host_rows_per_s=HOST_RATES)
    assert slower.min_rows("join") >= LOCAL.min_rows("join")
    thinner = DeviceProfile(platform="tpu", latency_s=2e-4,
                            h2d_bytes_per_s=2e8, d2h_bytes_per_s=2e8,
                            host_rows_per_s=HOST_RATES)
    assert thinner.min_rows("join") >= LOCAL.min_rows("join")


def test_explicit_conf_value_always_wins(monkeypatch):
    conf = HyperspaceConf()
    conf.device_join_min_rows = 123
    assert conf.device_min_rows("join") == 123
    conf.set("hyperspace.tpu.deviceJoinMinRows", "77")
    assert conf.device_min_rows("join") == 77
    # "auto" restores calibration.
    conf.set("hyperspace.tpu.deviceJoinMinRows", "auto")
    monkeypatch.setattr(calibrate, "device_profile", lambda refresh=False: LOCAL)
    assert conf.device_min_rows("join") == LOCAL.min_rows("join")


def test_disabled_calibration_falls_back_to_static(monkeypatch):
    monkeypatch.setenv("HS_CALIBRATE", "0")
    conf = HyperspaceConf()
    for kind, want in STATIC_MIN_ROWS.items():
        assert conf.device_min_rows(kind) == want


def test_real_probe_smoke(monkeypatch):
    """The actual probe runs (CPU backend here): finite positive physics,
    valid thresholds, process-cached."""
    monkeypatch.setenv("HS_CALIBRATE", "1")
    profile = calibrate.device_profile(refresh=True)
    assert profile is not None
    assert profile.latency_s > 0
    assert profile.h2d_bytes_per_s > 0
    assert profile.d2h_bytes_per_s > 0
    for kind, rate in profile.host_rows_per_s.items():
        assert rate > 0, kind
        assert 0 < profile.min_rows(kind) <= NEVER_MIN_ROWS
    # Cached: second call returns the same object without re-probing.
    assert calibrate.device_profile() is profile
    summary = calibrate.profile_summary()
    assert summary["calibrated"] is True
    assert set(summary["thresholds"]) == set(STATIC_MIN_ROWS)


def test_profile_summary_uncalibrated(monkeypatch):
    monkeypatch.setenv("HS_CALIBRATE", "0")
    summary = calibrate.profile_summary()
    assert summary == {
        "calibrated": False,
        "thresholds": dict(STATIC_MIN_ROWS),
        "resident_thresholds": dict(calibrate.STATIC_RESIDENT_MIN_ROWS)}


def test_unknown_kind_rejected():
    with pytest.raises(KeyError):
        calibrate.calibrated_min_rows("scan")


def test_cpu_backend_keeps_conservative_constants(monkeypatch):
    """XLA-CPU 'device' kernels lose to the numpy/arrow mirrors — a
    CPU-platform profile must not route work to them."""
    monkeypatch.setenv("HS_CALIBRATE", "1")
    cpu_fast = DeviceProfile(platform="cpu", latency_s=1e-4,
                             h2d_bytes_per_s=1e10, d2h_bytes_per_s=1e10,
                             host_rows_per_s=HOST_RATES)
    monkeypatch.setattr(calibrate, "device_profile",
                        lambda refresh=False: cpu_fast)
    for kind, want in STATIC_MIN_ROWS.items():
        assert calibrate.calibrated_min_rows(kind) == want
    for kind, want in calibrate.STATIC_RESIDENT_MIN_ROWS.items():
        assert calibrate.calibrated_resident_min_rows(kind) == want


def _failing_probe():
    raise OSError("probe failed")


@pytest.fixture
def failing_probe(monkeypatch):
    # Restore the process-wide probe cache after the test.
    monkeypatch.setattr(calibrate, "_PROFILE", calibrate._PROFILE)
    monkeypatch.setattr(calibrate, "_PROFILE_FAILED",
                        calibrate._PROFILE_FAILED)
    monkeypatch.setenv("HS_CALIBRATE", "1")
    monkeypatch.setattr(calibrate, "_probe_transfer", _failing_probe)


def test_failed_probe_on_tpu_backend_raises(failing_probe, monkeypatch):
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="TPU backend"):
        calibrate.device_profile(refresh=True)


def test_failed_probe_off_tpu_uses_fixed_constants(failing_probe):
    assert calibrate.device_profile(refresh=True) is None
    assert calibrate.calibrated_min_rows("join") == STATIC_MIN_ROWS["join"]
