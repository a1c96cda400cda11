"""Measured device-routing thresholds: transfer physics, not constants.

The host-vs-device cost model (SURVEY.md §2.4 "per-core XLA data parallelism
over HBM-resident columnar batches") needs a row threshold per op kind:
below it, shipping columns to the accelerator costs more than a vectorized
host pass.  This module measures the attached device at first use and
derives the thresholds from the observed physics:

    device_time(R) ~ latency + R * bytes_per_row / bandwidth
    host_time(R)   ~ R / host_rows_per_s          (measured per op kind)
    threshold      = smallest R where device_time < host_time
                     (infinite when per-row transfer alone exceeds the
                     host's per-row cost -> capped sentinel)

Device COMPUTE rate is deliberately not probed at session start: the first
invocation of each kernel pays an XLA compile, which is not a calibration
a session can afford.  The model instead assumes device compute is never
the bottleneck (true on the MXU/VPU for these elementwise/sort/segment
kernels) — so the threshold is purely the transfer-amortization point.

Explicit conf values always win (``HyperspaceConf.device_min_rows``); env
``HS_CALIBRATE=0`` disables probing and uses the fixed constants below
(the test suite pins this for determinism).  On a TPU backend a failed
probe raises: silently routing every operator to the host mirrors would
hide the chip.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Dict, Optional

# Fixed conservative thresholds, used when calibration is disabled
# (HS_CALIBRATE=0) and on a CPU backend.
STATIC_MIN_ROWS: Dict[str, int] = {
    "filter": 1 << 26,
    "join": 1 << 26,
    "agg": 1 << 26,
    "join_agg": 1 << 26,
    "build": 1 << 22,
}

# "Device never organically wins" sentinel — finite so conf arithmetic and
# JSON round-trips stay safe, far above any realizable batch.
NEVER_MIN_ROWS = 1 << 40

# Conservative fallbacks for the RESIDENT-data thresholds (inputs already
# in HBM via execution/device_cache.py; only round-trip latency must be
# repaid).  Used when calibration is disabled.
STATIC_RESIDENT_MIN_ROWS: Dict[str, int] = {
    "filter": 1 << 24,
    "join": 1 << 22,
    "agg": 1 << 22,
    # Fused join+aggregate returns O(groups) — not O(rows) — so its
    # resident break-even sits well below the plain join's.
    "join_agg": 1 << 20,
    "build": 1 << 22,
}

# Bytes shipped to the device per row, per op kind (the dominant transfer):
#   filter: two 8-B columns up, 1-B mask down
#   join:   8-B keys both sides up, two 8-B index vectors down
#   agg:    (n,2)-u32 key words + one f64 value column up, results down
#   build:  (n,2)-u32 hash words + (n,2)-u32 order words up, 2x i32 down
_BYTES_PER_ROW: Dict[str, float] = {
    "filter": 17.0,
    "join": 32.0,
    "agg": 24.0,
    # join_agg ships keys for both sides plus ~3 referenced value/group
    # columns cold; results return per GROUP, so the down direction is
    # negligible per row.
    "join_agg": 40.0,
    "build": 24.0,
}


@dataclasses.dataclass(frozen=True)
class DeviceProfile:
    """Measured host–device link physics + host baseline rates."""

    platform: str
    latency_s: float           # fixed host->device->host round-trip
    h2d_bytes_per_s: float     # host->device bandwidth
    d2h_bytes_per_s: float     # device->host bandwidth
    host_rows_per_s: Dict[str, float]  # per op kind

    def _host_rate(self, kind: str) -> float:
        """Per-kind host rate; profiles predating the fused join_agg
        kind (or built by tests) derive it from join + agg — the host
        mirror literally runs both."""
        rate = self.host_rows_per_s.get(kind)
        if rate is None and kind == "join_agg":
            j = self.host_rows_per_s["join"]
            a = self.host_rows_per_s["agg"]
            rate = 1.0 / (1.0 / j + 1.0 / a)
        if rate is None:
            raise KeyError(f"Unknown device op kind: {kind!r}")
        return rate

    def min_rows(self, kind: str) -> int:
        """Break-even row count for ``kind`` under this profile."""
        host_s_per_row = 1.0 / self._host_rate(kind)
        transfer_s_per_row = _BYTES_PER_ROW[kind] / self.h2d_bytes_per_s
        margin = host_s_per_row - transfer_s_per_row
        if margin <= 0:
            # Per-row transfer alone already exceeds the host's per-row
            # cost: the device can never repay the shipping.
            return NEVER_MIN_ROWS
        rows = self.latency_s / margin
        # Round up to a power of two: thresholds are routing knobs, not
        # precision instruments, and pow2 values keep logs legible.
        threshold = 1 << max(0, (int(rows) - 1).bit_length())
        return min(threshold, NEVER_MIN_ROWS)

    def resident_min_rows(self, kind: str) -> int:
        """Break-even row count when the inputs are ALREADY device-resident
        (execution/device_cache.py): no per-row shipping — the kernel only
        has to repay its round-trip latency (x2 margin: the two-phase
        kernels sync a scalar mid-flight), assuming device compute beats
        the host mirror at any size that clears this."""
        # The fused join+aggregate pipeline syncs twice (match count,
        # group count) and pulls only per-group results: three round
        # trips to repay.  The other two-phase kernels sync once
        # mid-flight (x2).
        trips = 3.0 if kind == "join_agg" else 2.0
        rows = trips * self.latency_s * self._host_rate(kind)
        threshold = 1 << max(12, (max(1, int(rows)) - 1).bit_length())
        return min(threshold, NEVER_MIN_ROWS)


_PROFILE: Optional[DeviceProfile] = None
_PROFILE_FAILED = False
# One probe per process: concurrent first queries (interop server threads)
# must not each run the probe — timings measured under mutual load would be
# cached as the permanent routing physics.
import threading

_PROBE_LOCK = threading.Lock()


def calibration_enabled() -> bool:
    return os.environ.get("HS_CALIBRATE", "1").lower() not in ("0", "false")


def _median_time(fn, reps: int = 3) -> float:
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def _probe_host_rates(n: int = 1 << 20) -> Dict[str, float]:
    """Host per-row rates for each op kind's dominant host-mirror cost:
    arrow elementwise compare (filter), numpy argsort (join: the mirror is
    sort+searchsorted), arrow hash aggregation (agg), numpy 3-key lexsort
    (build)."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.compute as pc

    rng = np.random.default_rng(0)
    ints = rng.integers(0, n, n)
    arr = pa.array(ints)
    tbl = pa.table({"k": ints % 1024, "v": rng.random(n)})

    t_filter = _median_time(lambda: pc.greater(arr, n // 2))
    t_join = _median_time(lambda: np.argsort(ints, kind="stable"))
    t_agg = _median_time(
        lambda: tbl.group_by("k").aggregate([("v", "sum")]))
    u32 = (ints % (1 << 31)).astype(np.uint32)
    t_build = _median_time(lambda: np.lexsort((u32, u32, u32 % 16)))
    return {
        "filter": n / max(t_filter, 1e-9),
        "join": n / max(t_join, 1e-9),
        "agg": n / max(t_agg, 1e-9),
        # The fused pipeline's host mirror does BOTH: join then hash-agg.
        "join_agg": n / max(t_join + t_agg, 1e-9),
        "build": n / max(t_build, 1e-9),
    }


def _probe_transfer() -> "tuple[str, float, float, float]":
    """(platform, latency_s, h2d_Bps, d2h_Bps) via jit-free transfers
    (device_put / np.asarray compile nothing, so the probe never pays an
    XLA compile)."""
    import jax
    import numpy as np

    dev = jax.devices()[0]
    small = np.zeros(8, dtype=np.float32)
    # Warm the dispatch path once before timing.
    np.asarray(jax.device_put(small, dev))
    latency = _median_time(lambda: np.asarray(jax.device_put(small, dev)))

    big = np.zeros(1 << 16, dtype=np.float32)  # 256 KiB
    nbytes = big.nbytes

    def h2d():
        jax.device_put(big, dev).block_until_ready()

    h2d()  # warm
    t_h2d = max(_median_time(h2d) - latency / 2, 1e-9)
    # d2h: jax arrays CACHE their fetched host copy, so each timed pull
    # must read a DISTINCT resident array or the probe measures a cache
    # hit.
    residents = [jax.device_put(big + np.float32(i), dev).block_until_ready()
                 for i in range(3)]
    times = []
    for r in residents:
        t0 = time.perf_counter()
        np.asarray(r)
        times.append(time.perf_counter() - t0)
    times.sort()
    t_d2h = max(times[len(times) // 2] - latency / 2, 1e-9)
    return dev.platform, latency, nbytes / t_h2d, nbytes / t_d2h


def device_profile(refresh: bool = False) -> Optional[DeviceProfile]:
    """The process-wide measured profile (physics don't change mid-process),
    or None when probing is disabled or fails off-TPU.  A failed probe on
    a TPU backend raises."""
    global _PROFILE, _PROFILE_FAILED
    if not calibration_enabled():
        return None
    with _PROBE_LOCK:
        if _PROFILE is not None and not refresh:
            return _PROFILE
        if _PROFILE_FAILED and not refresh:
            return None
        try:
            platform, latency, h2d, d2h = _probe_transfer()
            _PROFILE = DeviceProfile(
                platform=platform,
                latency_s=latency,
                h2d_bytes_per_s=h2d,
                d2h_bytes_per_s=d2h,
                host_rows_per_s=_probe_host_rates(),
            )
            _PROFILE_FAILED = False
            return _PROFILE
        except Exception as e:
            import jax

            if jax.default_backend() == "tpu":
                raise RuntimeError(
                    f"device calibration probe failed on the TPU backend: "
                    f"{e!r}") from e
            _PROFILE_FAILED = True
            return None


def calibrated_min_rows(kind: str) -> int:
    """The derived threshold for ``kind`` — measured when possible, the
    fixed constants otherwise.  A CPU backend keeps
    the conservative constants too: the model's "device compute is never
    the bottleneck" premise holds for the MXU/VPU, not for XLA-CPU
    re-running the very kernels the numpy/arrow mirrors beat."""
    if kind not in STATIC_MIN_ROWS:
        raise KeyError(f"Unknown device op kind: {kind!r}")
    profile = device_profile()
    if profile is None or profile.platform == "cpu":
        return STATIC_MIN_ROWS[kind]
    return profile.min_rows(kind)


def calibrated_resident_min_rows(kind: str) -> int:
    """Threshold for device-RESIDENT inputs — latency-only break-even
    (fixed constants on a CPU backend, as above)."""
    if kind not in STATIC_RESIDENT_MIN_ROWS:
        raise KeyError(f"Unknown device op kind: {kind!r}")
    profile = device_profile()
    if profile is None or profile.platform == "cpu":
        return STATIC_RESIDENT_MIN_ROWS[kind]
    return profile.resident_min_rows(kind)


def profile_summary() -> Dict[str, object]:
    """JSON-ready view for telemetry output."""
    profile = device_profile()
    if profile is None:
        return {"calibrated": False,
                "thresholds": dict(STATIC_MIN_ROWS),
                "resident_thresholds": dict(STATIC_RESIDENT_MIN_ROWS)}
    return {
        "calibrated": True,
        "platform": profile.platform,
        "latency_ms": round(profile.latency_s * 1e3, 3),
        "h2d_mb_per_s": round(profile.h2d_bytes_per_s / 1e6, 2),
        "d2h_mb_per_s": round(profile.d2h_bytes_per_s / 1e6, 2),
        "host_mrows_per_s": {k: round(v / 1e6, 2)
                             for k, v in profile.host_rows_per_s.items()},
        # Via the calibrated_* gates, so a CPU backend reports
        # the conservative constants actually in effect.
        "thresholds": {k: calibrated_min_rows(k) for k in STATIC_MIN_ROWS},
        "resident_thresholds": {k: calibrated_resident_min_rows(k)
                                for k in STATIC_RESIDENT_MIN_ROWS},
    }
