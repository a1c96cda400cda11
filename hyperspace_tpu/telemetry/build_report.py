"""Per-action build reports: where an index build/maintenance run spent
its time, bytes, and memory.

PR 4 made *queries* explain themselves (telemetry/report.py); this is
the same idea for the build/maintenance path — the side BENCH_r04 showed
dominating wall-clock (sf10_li: 5.0 s of read vs 43.2 s + 40.9 s of
spill) with nothing but a flat seconds dict to show for it.  Every
action run through ``actions/base.Action.run()`` owns one
:class:`BuildReport`:

  - **phases**: seconds per named phase (``read`` → ``spill_route``
    → ``kernel`` → ``spill_finish`` → ``write`` → ``sketch``, plus the
    protocol's ``validate``/``commit`` and the pipelined builder's two
    STALL phases ``prefetch``/``finalize`` — consumer time blocked on
    decode, and the exposed finalize tail after routing drains).  Each
    is timed where its work runs by :meth:`BuildReport.phase`, which
    also opens a ``build.phase.<name>`` span on that thread (tracing
    on) and gives the timeline the same interval (timeline on).
    Seconds accumulate across conflict retries and across the
    prefetch/route/finalize pools' worker threads (the report is
    lock-protected and owned by the ACTION, not a contextvar — worker
    threads do not inherit context; overlapped phases are thread
    seconds and may sum past wall clock on a pipelined spill build).
    Phases are classified device vs host (``kernel`` is device compute;
    everything else is host/IO) so ``device_s``/``host_s`` fall out.
  - **bytes**: decoded source bytes in (``bytes_read``), index data
    bytes out (``bytes_written``), and the external build's temporary
    spill-run bytes (``spill_bytes`` — the figure that must match what
    actually landed on disk) with run/file counts.
  - **memory**: peak host RSS plus live device-buffer bytes, sampled at
    action end via :func:`sample_memory` — lightweight gauges, never a
    profiler.

Finish exports the report into the PR 4 metrics registry
(``build.phase.<name>.seconds``, ``build.spill.bytes``,
``build.bytes.written``, ``build.actions``, ``build.peak_rss_mb``
gauge), tags the live ``action.*`` span with the totals, and publishes
the report as ``session.last_build_report_value`` / :func:`last_report`
— surfaced by ``Hyperspace.last_build_report()``.

Cost contract: ``hyperspace.system.buildProfiling.enabled`` (default on)
gates the memory sampling, metric export, span tags, and the perf
ledger append; phase timing itself stays on.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, Optional

from hyperspace_tpu.telemetry.trace import span

# Phase → attribution class.  ``kernel`` is the device hash+sort pass
# (or its bit-identical host mirror — still "compute", and the mirror
# only runs when the cost model says the chip would lose); everything
# else is host-side IO/shuffle.
_DEVICE_PHASES = frozenset({"kernel"})


_MAX_INTERVALS = 8192  # per report: a runaway phase loop stays bounded


class _Phase:
    """One timed phase on the calling thread (:meth:`BuildReport.phase`);
    ``seconds`` holds its length once the block has closed."""

    __slots__ = ("report", "name", "count", "seconds", "_span", "_t0_ns")

    def __init__(self, report: "BuildReport", name: str, count: bool) -> None:
        self.report = report
        self.name = name
        self.count = count
        self.seconds = 0.0

    def __enter__(self) -> "_Phase":
        self._span = span(f"build.phase.{self.name}")
        self._span.__enter__()
        self._t0_ns = time.monotonic_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        # The span closes right after the clock read: its duration and
        # the phase's seconds cover the same stretch.
        end_ns = time.monotonic_ns()
        self._span.__exit__(exc_type, exc, tb)
        self.seconds = (end_ns - self._t0_ns) / 1e9
        if self.count:
            self.report.add_seconds(self.name, self.seconds)
        self.report._record_interval(self.name, self._t0_ns, end_ns)
        return False


class BuildReport:
    """The explain-yourself artifact of one action run."""

    def __init__(self, action: str = "", index: str = "") -> None:
        self.action = action
        self.index = index
        self.started_at = time.time()
        self.wall_s = 0.0
        self.outcome = "ok"  # "ok" | "noop" | "error"
        self.error = ""
        self.conflict_retries = 0
        self.phases: Dict[str, float] = {}
        self.bytes_read = 0
        self.bytes_written = 0
        self.files_written = 0
        self.spill_bytes = 0
        self.spill_runs = 0
        self.peak_rss_mb: Optional[float] = None
        self.device_live_bytes: Optional[int] = None
        # Action-specific annotations (a refresh records its mode and
        # diff counts here — the RefreshSummary surfaced through
        # ``last_build_report()``); flat scalars only.
        self.properties: Dict[str, Any] = {}
        # Per-jax-device attributed kernel milliseconds (mesh-sharded
        # route/kernel passes: the SPMD program occupies every mesh
        # device for its duration).  Lands in the perf-ledger record so
        # ``doctor()``'s ledger-trend check and ``--compare``
        # attribution can see per-device skew across builds.
        self.device_kernel_ms: Dict[int, float] = {}
        # Timeline intervals (telemetry/timeline.py, when enabled): one
        # (lane, start_ns, end_ns) per timed phase — lane = phase
        # name — so the gap/overlap analysis can say "read idle while
        # spill_route busy", which summed seconds cannot.  Memory
        # samples are fed by the background sampler; per-phase
        # high-water marks come from intersecting the two.
        self.intervals: list = []
        self.memory_samples: list = []
        self._lock = threading.Lock()
        self._t0 = time.perf_counter()

    # -- recording (thread-safe: spill route/finish pools call in) ----------
    def phase(self, name: str, count: bool = True) -> _Phase:
        """Time the with-block as phase ``name`` on this thread: a
        ``build.phase.<name>`` span (tracing on), the same interval on
        the timeline (timeline on), and its seconds added to
        ``phases``.  ``count=False`` keeps the span and interval but
        leaves the seconds to the caller (``_PrefetchReader`` counts a
        stall only once the build is known to spill)."""
        return _Phase(self, name, count)

    def add_seconds(self, name: str, seconds: float) -> None:
        """Add seconds timed elsewhere to phase ``name``, with no span:
        a stall held back until it is known to count, or work another
        process did (the multi-host build's claim records)."""
        with self._lock:
            self.phases[name] = self.phases.get(name, 0.0) + float(seconds)

    def _record_interval(self, name: str, start_ns: int, end_ns: int) -> None:
        from hyperspace_tpu.telemetry import timeline

        if not timeline.timeline_enabled():
            return
        with self._lock:
            if len(self.intervals) < _MAX_INTERVALS:
                self.intervals.append((name, start_ns, end_ns))
        timeline.record_interval(name, "build.phase", start_ns, end_ns)

    def add_device_kernel_ms(self, device_id: int, ms: float) -> None:
        """Attribute ``ms`` of kernel time to one jax device (mesh route
        workers call in concurrently)."""
        with self._lock:
            self.device_kernel_ms[int(device_id)] = \
                self.device_kernel_ms.get(int(device_id), 0.0) + float(ms)

    def add_memory_sample(self, ts_ns: int, rss_mb: float,
                          device_bytes: int) -> None:
        """One background-sampler observation (timeline.MemorySampler
        sink contract)."""
        with self._lock:
            if len(self.memory_samples) < 8192:
                self.memory_samples.append(
                    (int(ts_ns), float(rss_mb), int(device_bytes)))

    def add_bytes(self, *, read: int = 0, written: int = 0, files: int = 0,
                  spill: int = 0, spill_runs: int = 0) -> None:
        with self._lock:
            self.bytes_read += int(read)
            self.bytes_written += int(written)
            self.files_written += int(files)
            self.spill_bytes += int(spill)
            self.spill_runs += int(spill_runs)

    def sample_memory(self) -> None:
        """Peak host RSS + live device-buffer bytes — one getrusage call
        and, when jax is already loaded, a live-array walk.  Called at
        action end (never per row/file)."""
        try:
            import resource

            self.peak_rss_mb = round(
                resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
                / 1024.0, 1)
        except Exception:  # noqa: BLE001 — non-POSIX: report without it
            pass
        import sys

        jax = sys.modules.get("jax")
        if jax is None:
            return  # never force the jax import for a metadata-only action
        try:
            self.device_live_bytes = int(sum(
                int(getattr(a, "nbytes", 0)) for a in jax.live_arrays()))
        except Exception:  # noqa: BLE001 — backend without live_arrays
            pass

    # -- derived -------------------------------------------------------------
    def phase_total_s(self) -> float:
        return sum(self.phases.values())

    def lane_report(self) -> Dict[str, Any]:
        """Gap/overlap analysis over this build's recorded intervals
        (``hyperspace.system.timeline.enabled`` must have been on):
        per-lane busy fractions plus the pairwise "X idle while Y busy"
        matrix — ``idle_while_busy["read"]["spill_route"]`` is ROADMAP
        item 2's serialization claim as a measured number."""
        from hyperspace_tpu.telemetry import timeline

        with self._lock:
            intervals = [(lane, s, e) for lane, s, e in self.intervals]
        return timeline.busy_report(intervals)

    def phase_memory_mb(self) -> Dict[str, float]:
        """Per-phase high-water host RSS (MB): the max sampled RSS whose
        timestamp falls inside any of that phase's intervals — what
        "the spill phase peaks at X" means, instead of one end-of-action
        peak that cannot name its phase."""
        with self._lock:
            intervals = list(self.intervals)
            samples = list(self.memory_samples)
        out: Dict[str, float] = {}
        for lane, s, e in intervals:
            for ts, rss_mb, _dev in samples:
                if s <= ts <= e and rss_mb > out.get(lane, 0.0):
                    out[lane] = rss_mb
        return {k: round(v, 1) for k, v in sorted(out.items())}

    @property
    def mesh_devices(self) -> int:
        """How many mesh devices this build's sharded kernels spanned
        (0 = the single-device path ran throughout)."""
        return int(self.properties.get("mesh_devices", 0) or 0)

    @property
    def device_s(self) -> float:
        return sum(v for k, v in self.phases.items() if k in _DEVICE_PHASES)

    @property
    def host_s(self) -> float:
        return sum(v for k, v in self.phases.items()
                   if k not in _DEVICE_PHASES)

    # -- lifecycle (driven by actions/base.Action.run) -----------------------
    def finish(self, outcome: str = "ok", error: str = "") -> None:
        self.wall_s = time.perf_counter() - self._t0
        self.outcome = outcome
        self.error = error

    def export_metrics(self) -> None:
        """One report → the process metrics registry
        (docs/16-observability.md catalog)."""
        from hyperspace_tpu.telemetry import metrics

        metrics.inc("build.actions")
        metrics.observe("build.wall.seconds", self.wall_s * 1000.0)
        for name, s in self.phases.items():
            metrics.inc(f"build.phase.{name}.seconds", s)
        if self.spill_bytes:
            metrics.inc("build.spill.bytes", self.spill_bytes)
        if self.spill_runs:
            metrics.inc("build.spill.runs", self.spill_runs)
        if self.bytes_written:
            metrics.inc("build.bytes.written", self.bytes_written)
        if self.bytes_read:
            metrics.inc("build.bytes.read", self.bytes_read)
        if self.peak_rss_mb is not None:
            metrics.set_gauge("build.peak_rss_mb", self.peak_rss_mb)
        if self.device_live_bytes is not None:
            metrics.set_gauge("build.device.live_bytes",
                              self.device_live_bytes)

    def attach_to_span(self, sp) -> None:
        """Tag the live ``action.*`` span with the report's totals (the
        phases are spans of their own, opened where they ran)."""
        sp.set(build_wall_s=round(self.wall_s, 4),
               build_phase_total_s=round(self.phase_total_s(), 4),
               build_bytes_written=self.bytes_written,
               build_spill_bytes=self.spill_bytes)

    # -- serialization -------------------------------------------------------
    def to_dict(self) -> Dict[str, Any]:
        return {
            "action": self.action,
            "index": self.index,
            "started_at": self.started_at,
            "wall_s": round(self.wall_s, 4),
            "outcome": self.outcome,
            **({"error": self.error} if self.error else {}),
            "conflict_retries": self.conflict_retries,
            "phases_s": {k: round(v, 4)
                         for k, v in sorted(self.phases.items())},
            "device_s": round(self.device_s, 4),
            "host_s": round(self.host_s, 4),
            "bytes_read": self.bytes_read,
            "bytes_written": self.bytes_written,
            "files_written": self.files_written,
            "spill_bytes": self.spill_bytes,
            "spill_runs": self.spill_runs,
            "peak_rss_mb": self.peak_rss_mb,
            "device_live_bytes": self.device_live_bytes,
            **({"properties": dict(sorted(self.properties.items()))}
               if self.properties else {}),
            **({"device_kernel_ms": {
                str(k): round(v, 3)
                for k, v in sorted(self.device_kernel_ms.items())}}
               if self.device_kernel_ms else {}),
            # Timeline extras (present only when the interval recorder
            # was on for this build): the busy-fraction matrix and the
            # per-phase memory high-water marks.
            **({"lanes": self.lane_report()} if self.intervals else {}),
            **({"phase_peak_rss_mb": self.phase_memory_mb()}
               if self.memory_samples and self.intervals else {}),
        }

    def render(self) -> str:
        lines = [f"Build report: {self.action} index={self.index or '?'} "
                 f"outcome={self.outcome} wall={self.wall_s:.3f}s"]
        if self.conflict_retries:
            lines.append(f"  conflicts absorbed: {self.conflict_retries}")
        for name, s in sorted(self.phases.items(),
                              key=lambda kv: -kv[1]):
            side = "device" if name in _DEVICE_PHASES else "host"
            lines.append(f"  phase {name:<14}{s:>10.3f} s  [{side}]")
        lines.append(f"  bytes: read={self.bytes_read} "
                     f"written={self.bytes_written} "
                     f"spill={self.spill_bytes} "
                     f"(runs={self.spill_runs}, "
                     f"files={self.files_written})")
        if self.peak_rss_mb is not None:
            lines.append(f"  peak host RSS: {self.peak_rss_mb:.1f} MB")
        if self.device_live_bytes is not None:
            lines.append(f"  live device buffers: "
                         f"{self.device_live_bytes} bytes")
        return "\n".join(lines)


# Last finished report, process-wide (the session carries its own copy;
# this is the fallback for actions constructed without a session).
_last: Optional[BuildReport] = None
_last_lock = threading.Lock()


def publish(report: BuildReport, session=None) -> None:
    global _last
    with _last_lock:
        _last = report
    if session is not None:
        session.last_build_report_value = report


def last_report() -> Optional[BuildReport]:
    with _last_lock:
        return _last


def profiling_enabled(conf) -> bool:
    return bool(getattr(conf, "build_profiling_enabled", True))
