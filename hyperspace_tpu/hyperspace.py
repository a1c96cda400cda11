"""Top-level user API.

Reference contract: Hyperspace.scala:26-166 — createIndex/deleteIndex/
restoreIndex/vacuumIndex/refreshIndex/optimizeIndex/cancel/indexes/index/
explain, each delegating to the IndexCollectionManager; ``explain`` renders
the with/without-index plan comparison (PlanAnalyzer).
"""

from __future__ import annotations


import pyarrow as pa

from hyperspace_tpu.dataset import Dataset
from hyperspace_tpu.index.index_config import IndexConfig
from hyperspace_tpu.session import HyperspaceSession


class Hyperspace:
    def __init__(self, session: HyperspaceSession) -> None:
        self.session = session
        self.index_manager = session.index_collection_manager

    def create_index(self, dataset: Dataset, config: IndexConfig) -> None:
        self.index_manager.create(dataset, config)

    def delete_index(self, name: str) -> None:
        self.index_manager.delete(name)

    def restore_index(self, name: str) -> None:
        self.index_manager.restore(name)

    def vacuum_index(self, name: str) -> None:
        self.index_manager.vacuum(name)

    def refresh_index(self, name: str, mode: str = "full"):
        """Modes: ``full`` (rebuild), ``incremental``, ``quick``
        (metadata-only), and ``repair`` — rebuild only the buckets whose
        files are quarantined, then clear their quarantine records
        (docs/15-integrity.md).  Returns a
        :class:`~hyperspace_tpu.actions.refresh.RefreshSummary`:
        appended/deleted file counts the diff saw, the mode that ran,
        the committed log version — or ``outcome="noop"`` when the
        source was unchanged (a benign no-op, not an exception)."""
        return self.index_manager.refresh(name, mode)

    def verify_index(self, name: str, mode: str = "quick") -> pa.Table:
        """Scrub ``name``'s index data files against its log entry and
        return the per-file report (columns: file, status, detail,
        quarantined).  ``quick`` checks existence/size/mtime; ``full``
        additionally re-reads every file and re-hashes it against the
        content digest recorded at write time.  Damaged files are
        QUARANTINED: later queries keep using the index with only the
        damaged buckets read from source, and
        ``refresh_index(name, mode="repair")`` rebuilds them."""
        return self.index_manager.verify(name, mode)

    def optimize_index(self, name: str, mode: str = "quick"):
        """Compact small index files bucket-wise (``quick`` merges only
        files below ``hyperspace.index.optimize.fileSizeThreshold``,
        ``full`` rewrites every bucket).  Returns an
        :class:`~hyperspace_tpu.actions.optimize.OptimizeSummary`:
        files/buckets compacted, files written, the committed log
        version — or ``outcome="noop"`` when no bucket held mergeable
        files (a benign no-op, not an exception)."""
        return self.index_manager.optimize(name, mode)

    def cancel(self, name: str) -> None:
        self.index_manager.cancel(name)

    def indexes(self) -> pa.Table:
        return self.index_manager.indexes()

    def index(self, name: str) -> pa.Table:
        from hyperspace_tpu.index.statistics import index_statistics_table

        entry = self.index_manager.get_index(name)
        return index_statistics_table([entry] if entry else [], extended=True,
                                      path_resolver=self.index_manager
                                      .path_resolver)

    def explain(self, dataset: Dataset, verbose: bool = False) -> str:
        from hyperspace_tpu.plananalysis.explain import explain_string

        return explain_string(dataset, self.session, verbose=verbose)

    # -- the index advisor (docs/17-advisor.md) -----------------------------
    def whatif(self, dataset: Dataset, candidates):
        """Plan ``dataset`` as if ``candidates`` (IndexConfig specs or
        hypothetical entries) were built — the real optimizer's plan
        diff plus an estimated bytes-scanned delta, with zero files
        written and nothing executed.  Returns a
        :class:`~hyperspace_tpu.advisor.hypothetical.WhatIfReport`."""
        from hyperspace_tpu.advisor.hypothetical import whatif

        return whatif(self.session, dataset, candidates)

    def captured_workload(self) -> pa.Table:
        """The captured query-fingerprint workload
        (``hyperspace.advisor.capture.enabled``) as one row per distinct
        query shape: hit count, the filter/join/group/projected columns,
        measured bytes scanned."""
        from hyperspace_tpu.advisor.workload import workload_table

        return workload_table(self.session.conf)

    def clear_captured_workload(self) -> None:
        from hyperspace_tpu.advisor.workload import clear

        clear(self.session.conf)

    def recommend_indexes(self, top_k: int = 5) -> pa.Table:
        """Rank candidate covering indexes for the CAPTURED workload:
        columns ``candidate``, ``relation``, ``indexedColumns``,
        ``includedColumns``, ``supportingQueries``, ``supportingHits``,
        ``estBenefitBytes``, ``estBuildCostBytes``, ``score`` — benefit
        is workload-weighted measured-minus-estimated bytes, cost is one
        covered-column pass over the source (the model in
        advisor/candidates.py; docs/17-advisor.md)."""
        from hyperspace_tpu.advisor.recommend import recommend_indexes

        return recommend_indexes(self.session, top_k)

    def apply_recommendations(self, top_k: int = 1) -> list:
        """Build the top ``top_k`` recommendations through the normal
        ``create_index`` path (same validation/log protocol/build);
        returns the index names built.  Candidates an existing ACTIVE
        index already covers are skipped."""
        from hyperspace_tpu.advisor.recommend import apply_recommendations

        return apply_recommendations(self.session, top_k)

    def last_build_report(self):
        """The :class:`~hyperspace_tpu.telemetry.build_report.BuildReport`
        of the most recent action run through this session (create /
        refresh / repair / optimize / ...): per-phase wall seconds
        (read → route → sort → spill → finalize), device-compute vs host
        split, bytes moved, spill run/file counts, and peak host RSS /
        live device-buffer bytes.  None before the first action.  See
        docs/16-observability.md."""
        report = self.session.last_build_report_value
        if report is not None:
            return report
        from hyperspace_tpu.telemetry.build_report import last_report

        return last_report()

    def perf_history(self, index: str = None, limit: int = None) -> pa.Table:
        """The persistent perf ledger (telemetry/perf_ledger.py) as an
        arrow table — one row per recorded action run under
        ``<systemPath>/_hyperspace_perf``, oldest first, readable over
        both LogStore backends.  Columns: key, kind, name, ts,
        wallSeconds, outcome, phasesJson, bytesWritten, spillBytes,
        recordJson (the full record).

        Filters (also on the interop ``perf_history`` verb): ``index``
        keeps action records for that index, ``limit`` keeps the most
        recent N after filtering."""
        from hyperspace_tpu.telemetry.perf_ledger import history_table

        return history_table(self.session.conf, index=index, limit=limit)

    # -- timeline profiler + health doctor (docs/16-observability.md) -------
    def export_timeline(self, path: str, trace_id: str = None,
                        ledger_key: str = None) -> str:
        """Write a Perfetto/Chrome trace-event JSON file to ``path``
        (load it in ui.perfetto.dev or chrome://tracing).

        Default: the live timeline ring — build-phase / executor /
        device-kernel lanes plus the memory counter track
        (``hyperspace.system.timeline.enabled`` must be on to have
        recorded anything) and the most recent query's span tree when
        one is attached.  ``trace_id`` instead reconstructs from that
        flight-recorder retained record's span tree; ``ledger_key``
        reconstructs from that perf-ledger record's phase seconds —
        both work after the fact, without the ring."""
        from hyperspace_tpu.telemetry import timeline

        if trace_id is not None:
            from hyperspace_tpu.telemetry import flight_recorder

            rec = flight_recorder.recorder().find(trace_id.lower())
            if rec is None:
                raise ValueError(
                    f"no retained flight record for trace id {trace_id!r}")
            timeline.export_chrome_trace(
                path, intervals=(), memory_samples=(),
                span_roots=[rec["spans"]] if rec.get("spans") else ())
            return path
        if ledger_key is not None:
            import json as _json

            from hyperspace_tpu.telemetry import perf_ledger

            for rec in perf_ledger.records(self.session.conf):
                if rec.get("key") == ledger_key:
                    events = timeline.ledger_to_trace_events(rec)
                    from hyperspace_tpu.telemetry.trace import span

                    with span("timeline.export", path=path) as sp:
                        # hslint: allow[io-seam] user-chosen export path
                        with open(path, "w", encoding="utf-8") as f:
                            _json.dump({"traceEvents": events,
                                        "displayTimeUnit": "ms"}, f)
                        sp.set(events=len(events))
                    return path
            raise ValueError(f"no perf-ledger record {ledger_key!r}")
        roots = []
        rep = self.session.last_run_report_value
        if rep is not None and rep.root_span is not None:
            roots.append(rep.root_span)
        timeline.export_chrome_trace(path, span_roots=roots)
        return path

    def doctor(self, fleet: bool = False):
        """One aggregated health report over everything the telemetry
        stack knows (telemetry/doctor.py): quarantine/containment state,
        per-index staleness via the lifecycle change detector, daemon
        failure backoffs, the perf-ledger trend, serving shed rate and
        latency-SLO burn, degraded events, per-device kernel-ms skew —
        graded ok/warn/crit, worst check wins, published as the
        ``health.status`` gauge.  Cheap (stat-level listings and
        process counters only), also served by the inline interop
        ``doctor`` verb so it works during overload.

        ``fleet=True`` adds the CLUSTER checks over the published
        heartbeats (telemetry/fleet.py): a stale heartbeat — a dead or
        hung process — is crit, more than one lifecycle daemon warns,
        the aggregate shed-ratio/SLO burn and cross-process/cross-device
        kernel-ms skew grade over the MERGED counters; their worst grade
        is published as ``health.fleet.status``."""
        from hyperspace_tpu.telemetry.doctor import doctor

        return doctor(self.session, fleet=fleet)

    # -- flight recorder / diagnostics (docs/16-observability.md) -----------
    def slow_queries(self, fleet: bool = False) -> pa.Table:
        """The flight recorder's retained ring as an arrow table, oldest
        first: slow (>= ``hyperspace.serving.flightRecorder.slowMs``),
        error, deadline-expired, and shed requests are always kept,
        healthy ones sampled 1-in-N.  Columns: ts, traceId, requestId,
        kind, outcome, latencyMs, queueWaitMs, slow, reason, error,
        recordJson (the full record: span tree + run report).  The same
        table the interop ``slow_queries`` verb serves.

        ``fleet=True`` federates across the fleet (telemetry/fleet.py):
        the union of this process's ring, every published heartbeat's
        interesting tail (live processes), and the persisted diagnostics
        bundles (drained ones), deduplicated, with a ``process`` column
        naming where each request ran."""
        if fleet:
            from hyperspace_tpu.telemetry.fleet import (
                fleet_slow_queries_table,
            )

            return fleet_slow_queries_table(self.session.conf)
        from hyperspace_tpu.telemetry.flight_recorder import (
            slow_queries_table,
        )

        return slow_queries_table(self.session.conf)

    def trace(self, trace_id: str, fleet: bool = False):
        """The full retained flight record (dict) for ``trace_id`` — the
        id every wire response echoes and every ``QueryFailedError``
        carries — or None when no record for it is retained.
        ``fleet=True`` resolves across the fleet too: the local ring
        first, then every published heartbeat's interesting tail, then
        the persisted diagnostics bundles — so a slow query served by
        ANOTHER process is found from here by its echoed id."""
        if fleet:
            from hyperspace_tpu.telemetry.fleet import find_trace

            return find_trace(self.session.conf, trace_id)
        from hyperspace_tpu.telemetry import flight_recorder

        return flight_recorder.recorder().find(trace_id.lower())

    # -- fleet telemetry federation (docs/16-observability.md) ---------------
    def fleet_status(self) -> pa.Table:
        """Every published fleet heartbeat as an arrow table
        (telemetry/fleet.py): process identity, host, pid, role
        (``server``/``daemon``/``client``), last published health grade,
        heartbeat age, freshness, and the carried snapshot.  The same
        table the inline interop ``fleet_status`` verb serves — it works
        during overload, exactly when an operator asks "which of my
        servers is sick"."""
        from hyperspace_tpu.telemetry.fleet import fleet_status_table

        return fleet_status_table(self.session.conf)

    def fleet_metrics(self) -> dict:
        """The fleet-merged metrics view over every fresh heartbeat plus
        this process's live registry: counters summed, gauges kept
        per-process (``name -> {process: value}``), fixed-bucket
        histograms merged by bucket-sum with exemplar carry.  Keys:
        ``processes``, ``counters``, ``gauges``, ``histograms``
        (docs/16-observability.md has the merge semantics)."""
        from hyperspace_tpu.telemetry.fleet import fleet_metrics

        return fleet_metrics(self.session.conf)

    def start_fleet_telemetry(self):
        """Start this session's heartbeat publisher thread
        (``hyperspace.fleet.telemetry.enabled`` must be true; it
        publishes every ``hyperspace.fleet.telemetry.publishIntervalS``
        seconds).  Sessions, ``QueryServer``, and the lifecycle daemon
        auto-start it when the conf gate is on — this is the explicit
        handle for conf set after construction.  Returns the
        :class:`~hyperspace_tpu.telemetry.fleet.FleetPublisher`."""
        from hyperspace_tpu.telemetry.fleet import publisher_for

        return publisher_for(self.session).start()

    def stop_fleet_telemetry(self) -> None:
        """Stop the heartbeat publisher thread (idempotent)."""
        from hyperspace_tpu.telemetry.fleet import publisher_for

        publisher_for(self.session).stop()

    # -- SLO alerting (docs/16-observability.md) ----------------------------
    def alerts(self, fleet: bool = False) -> pa.Table:
        """Current SLO alert states (telemetry/alerts.py), one row per
        declared objective — availability, latency, staleness,
        build-claim liveness — with state (pending/firing/resolved),
        severity, the since timestamp, and the incident-bundle key
        captured at the moment of firing.  The same table the inline
        interop ``alerts`` verb serves, so it answers during overload.

        ``fleet=True`` federates: every fresh heartbeat's carried
        active alerts ride along with a ``process`` column attributing
        each row — "which server is paging" in one call."""
        from hyperspace_tpu.telemetry.alerts import alerts_table

        return alerts_table(self.session, fleet=fleet)

    def alert_history(self) -> pa.Table:
        """The persisted alert transition log as an arrow table, oldest
        first — every state change (pending → firing → resolved) the
        engine recorded under ``<systemPath>/_hyperspace_alerts``
        through the LogStore seam, restart-proof across both
        backends."""
        from hyperspace_tpu.telemetry.alerts import history_table

        return history_table(self.session.conf)

    def start_alerting(self):
        """Start the SLO evaluator thread
        (``hyperspace.alerts.enabled`` must be true; evaluation rides
        the fleet-heartbeat cadence unless
        ``hyperspace.alerts.intervalS`` overrides it).  Returns the
        :class:`~hyperspace_tpu.telemetry.alerts.AlertEngine`."""
        from hyperspace_tpu.telemetry.alerts import engine_for

        return engine_for(self.session).start()

    def stop_alerting(self) -> None:
        """Stop the SLO evaluator thread (idempotent; the persisted
        alert state survives for the next engine)."""
        from hyperspace_tpu.telemetry.alerts import engine_for

        engine_for(self.session).stop()

    def diagnostics(self) -> dict:
        """The live diagnostics bundle: the flight recorder's retained
        ring, a metrics snapshot, and the recent perf-ledger tail — the
        exact payload :meth:`dump_diagnostics` persists."""
        from hyperspace_tpu.telemetry.flight_recorder import (
            diagnostics_bundle,
        )

        return diagnostics_bundle(self.session.conf)

    def dump_diagnostics(self):
        """Persist :meth:`diagnostics` as a bundle through the LogStore
        seam under ``<systemPath>/_hyperspace_diagnostics`` (both
        backends, restart-proof, bounded by
        ``hyperspace.serving.flightRecorder.maxBundles``); returns the
        bundle key, or None when disabled/failed.  ``QueryServer``'s
        drain (SIGTERM) does this automatically."""
        from hyperspace_tpu.telemetry.flight_recorder import (
            dump_diagnostics,
        )

        return dump_diagnostics(self.session.conf)

    def diagnostics_bundles(self) -> list:
        """Every persisted diagnostics bundle, oldest first — how "what
        happened yesterday" survives a restart (docs/10-faq.md)."""
        from hyperspace_tpu.telemetry.flight_recorder import bundles

        return bundles(self.session.conf)

    # -- autonomous lifecycle (docs/19-lifecycle.md) ------------------------
    def maintenance_cycle(self) -> list:
        """Run ONE maintenance cycle synchronously — the daemon's
        detect → decide → act → journal step, drivable without the
        daemon thread (tests, serving integration, cron).  Returns the
        journal records written this cycle (one per decision, including
        ``kind=none`` "did nothing" records)."""
        from hyperspace_tpu.lifecycle.daemon import daemon_for

        return daemon_for(self.session).run_once()

    def start_maintenance(self):
        """Start the opt-in maintenance daemon thread
        (``hyperspace.lifecycle.enabled`` must be true; it polls every
        ``hyperspace.lifecycle.intervalS`` seconds).  Returns the
        :class:`~hyperspace_tpu.lifecycle.daemon.MaintenanceDaemon`."""
        from hyperspace_tpu.lifecycle.daemon import daemon_for

        return daemon_for(self.session).start()

    def stop_maintenance(self) -> None:
        """Stop the maintenance daemon thread (idempotent; the session's
        daemon object survives for later restarts)."""
        from hyperspace_tpu.lifecycle.daemon import daemon_for

        daemon_for(self.session).stop()

    def lifecycle_history(self) -> pa.Table:
        """The lifecycle decision journal as an arrow table, oldest
        first — every daemon/maintenance-cycle decision (refresh mode
        chosen, advisor build/drop, backoff skip, or "did nothing, "
        "here's why"), persisted under
        ``<systemPath>/_hyperspace_lifecycle`` through the LogStore
        seam, restart-proof.  The same table the interop ``lifecycle``
        verb serves (docs/19-lifecycle.md has the schema)."""
        from hyperspace_tpu.lifecycle.journal import history_table

        return history_table(self.session.conf)

    def metrics(self) -> dict:
        """Point-in-time snapshot of the process-wide metrics registry
        (telemetry/metrics.py): counters like ``io.retry.attempts``,
        ``log.cas.conflicts``, ``rule.filter.applied``,
        ``degraded.fallbacks``, ``scrub.files_flagged``, and derived
        ratios like ``cache.device.hit_ratio`` — the operational
        aggregate across every query and action this process ran
        (docs/16-observability.md has the catalog)."""
        from hyperspace_tpu.telemetry import metrics as m

        return m.snapshot()

    def metrics_text(self) -> str:
        """The same registry as a Prometheus-style text exposition —
        scrape it, or drop it in a log line."""
        from hyperspace_tpu.telemetry import metrics as m

        return m.registry().render_prometheus()

    def reset_metrics(self) -> None:
        """Zero every series (tests isolating deltas)."""
        from hyperspace_tpu.telemetry import metrics as m

        m.reset()
