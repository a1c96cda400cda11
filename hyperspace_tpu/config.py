"""Configuration registry: every tunable in one place, with typed accessors.

Mirrors the reference's key/default registry (index/IndexConstants.scala:21-114)
and typed accessor layer (util/HyperspaceConf.scala:26-118), collapsed into a
single dataclass because we own the session object instead of riding Spark's
string-keyed SQLConf.  String-keyed get/set is still supported (``set``/``get``)
so tests and the Python API can flip flags the way Spark conf users do.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Optional

# Canonical string keys (kept spark-compatible in spirit so reference users
# can map their configs 1:1; see docs/_docs/02-ug-configuration.md:9-23).
SYSTEM_PATH = "hyperspace.system.path"
NUM_BUCKETS = "hyperspace.index.numBuckets"
NUM_BUCKETS_LEGACY = "hyperspace.index.num.buckets"  # HyperspaceConf.scala:109-117
LINEAGE_ENABLED = "hyperspace.index.lineage.enabled"
HYBRID_SCAN_ENABLED = "hyperspace.index.hybridscan.enabled"
HYBRID_SCAN_APPENDED_RATIO = "hyperspace.index.hybridscan.maxAppendedRatio"
HYBRID_SCAN_DELETED_RATIO = "hyperspace.index.hybridscan.maxDeletedRatio"
OPTIMIZE_FILE_SIZE_THRESHOLD = "hyperspace.index.optimize.fileSizeThreshold"
INDEX_MAX_ROWS_PER_FILE = "hyperspace.index.maxRowsPerFile"
FILTER_RULE_USE_BUCKET_SPEC = "hyperspace.index.filterRule.useBucketSpec"
CACHE_EXPIRY_SECONDS = "hyperspace.index.cache.expiryDurationInSeconds"
SOURCE_PROVIDERS = "hyperspace.index.sources.fileBasedBuilders"
SIGNATURE_PROVIDER = "hyperspace.index.signatureProvider"
LOG_MANAGER_CLASS = "hyperspace.index.logManagerClass"
LOG_STORE_CLASS = "hyperspace.index.logStoreClass"
CONCURRENCY_MAX_RETRIES = "hyperspace.index.concurrency.maxRetries"
DEGRADED_FALLBACK_TO_SOURCE = "hyperspace.system.degraded.fallbackToSource"
OBJECT_STORE_STALE_LIST_MS = "hyperspace.system.objectStore.staleListMs"
EVENT_LOGGER = "hyperspace.eventLoggerClass"
SUPPORTED_FILE_FORMATS = "hyperspace.index.supportedFileFormats"
DEVICE_BATCH_ROWS = "hyperspace.tpu.deviceBatchRows"
DEVICE_FILTER_MIN_ROWS = "hyperspace.tpu.deviceFilterMinRows"
MESH_FILTER_MIN_ROWS = "hyperspace.tpu.meshFilterMinRows"
INDEX_FILE_COMPRESSION = "hyperspace.tpu.indexFileCompression"
DEVICE_JOIN_MIN_ROWS = "hyperspace.tpu.deviceJoinMinRows"
DEVICE_BUILD_MIN_ROWS = "hyperspace.tpu.deviceBuildMinRows"
MESH_JOIN_MIN_ROWS = "hyperspace.tpu.meshJoinMinRows"
DEVICE_AGG_MIN_ROWS = "hyperspace.tpu.deviceAggMinRows"
DEVICE_RESIDENT_MIN_ROWS = "hyperspace.tpu.deviceResidentMinRows"
DEVICE_CACHE_BYTES = "hyperspace.tpu.deviceCacheBytes"
DEVICE_CACHE_POLICY = "hyperspace.tpu.deviceCachePolicy"
PARALLEL_BUILD = "hyperspace.tpu.parallelBuild"
SHUFFLE_CAPACITY_SLACK = "hyperspace.tpu.shuffleCapacitySlack"
MESH_ENABLED = "hyperspace.parallel.mesh.enabled"
MESH_MAX_DEVICES = "hyperspace.parallel.mesh.maxDevices"
MESH_AGG_MIN_ROWS = "hyperspace.tpu.meshAggMinRows"
BUILD_PIPELINE_ENABLED = "hyperspace.index.build.pipeline.enabled"
BUILD_PREFETCH_DEPTH = "hyperspace.index.build.prefetchDepth"
BUILD_FINALIZE_WORKERS = "hyperspace.index.build.finalizeWorkers"
MULTIHOST_BUILD_HOSTS = "hyperspace.index.build.multihost.hosts"
MULTIHOST_BUILD_CLAIM_TTL_S = "hyperspace.index.build.multihost.claimTtlS"
MULTIHOST_BUILD_POLL_S = "hyperspace.index.build.multihost.pollS"
MULTIHOST_BUILD_DEADLINE_S = "hyperspace.index.build.multihost.deadlineS"
GLOBBING_PATTERN = "hyperspace.source.globbingPattern"
DISPLAY_MODE = "hyperspace.explain.displayMode"
HIGHLIGHT_BEGIN_TAG = "hyperspace.explain.displayMode.highlight.beginTag"
HIGHLIGHT_END_TAG = "hyperspace.explain.displayMode.highlight.endTag"
AUTO_RECOVERY_ENABLED = "hyperspace.index.autoRecovery.enabled"
AUTO_REPAIR_ENABLED = "hyperspace.index.autoRepair.enabled"
INTEGRITY_DIGEST_ON_WRITE = "hyperspace.system.integrity.digestOnWrite"
INTEGRITY_QUARANTINE_ON_FAILURE = \
    "hyperspace.system.integrity.quarantineOnReadFailure"
IO_RETRY_MAX_ATTEMPTS = "hyperspace.system.io.retry.maxAttempts"
IO_RETRY_INITIAL_BACKOFF_MS = "hyperspace.system.io.retry.initialBackoffMs"
IO_RETRY_MAX_BACKOFF_MS = "hyperspace.system.io.retry.maxBackoffMs"
TELEMETRY_TRACING_ENABLED = "hyperspace.system.telemetry.tracing.enabled"
TELEMETRY_TRACE_SINK = "hyperspace.system.telemetry.trace.sink"
TELEMETRY_TRACE_MAX_BYTES = "hyperspace.system.telemetry.trace.maxBytes"
DEVICE_GUARD_ENABLED = "hyperspace.system.deviceGuard.enabled"
TIMELINE_ENABLED = "hyperspace.system.timeline.enabled"
TIMELINE_MAX_INTERVALS = "hyperspace.system.timeline.maxIntervals"
TIMELINE_MEMORY_SAMPLE_MS = "hyperspace.system.timeline.memorySampleMs"
DOCTOR_LATENCY_SLO_MS = "hyperspace.doctor.latencySloMs"
DOCTOR_SHED_WARN_RATIO = "hyperspace.doctor.shedWarnRatio"
DOCTOR_DEVICE_SKEW_WARN = "hyperspace.doctor.deviceSkewWarn"
FLEET_TELEMETRY_ENABLED = "hyperspace.fleet.telemetry.enabled"
FLEET_PUBLISH_INTERVAL_S = "hyperspace.fleet.telemetry.publishIntervalS"
FLEET_STALE_AFTER_S = "hyperspace.fleet.telemetry.staleAfterS"
FLEET_PRUNE_AFTER_S = "hyperspace.fleet.telemetry.pruneAfterS"
BUILD_PROFILING_ENABLED = "hyperspace.system.buildProfiling.enabled"
PERF_LEDGER_ENABLED = "hyperspace.system.perf.ledger.enabled"
PERF_LEDGER_MAX_ENTRIES = "hyperspace.system.perf.ledger.maxEntries"
ADVISOR_CAPTURE_ENABLED = "hyperspace.advisor.capture.enabled"
ADVISOR_CAPTURE_MAX_ENTRIES = "hyperspace.advisor.capture.maxEntries"
ADVISOR_MAX_CANDIDATES = "hyperspace.advisor.maxCandidates"
SERVING_WORKERS = "hyperspace.serving.workers"
SERVING_QUEUE_DEPTH = "hyperspace.serving.queueDepth"
SERVING_MAX_CONNECTIONS = "hyperspace.serving.maxConnections"
SERVING_DEFAULT_DEADLINE_MS = "hyperspace.serving.defaultDeadlineMs"
SERVING_REQUEST_TIMEOUT_S = "hyperspace.serving.requestTimeoutS"
SERVING_SEND_TIMEOUT_S = "hyperspace.serving.sendTimeoutS"
SERVING_DRAIN_GRACE_S = "hyperspace.serving.drainGraceS"
SERVING_SHED_RSS_MB = "hyperspace.serving.shed.rssWatermarkMb"
SERVING_SHED_QUEUE_WAIT_MS = "hyperspace.serving.shed.queueWaitWatermarkMs"
SERVING_PLAN_CACHE_ENABLED = "hyperspace.serving.planCache.enabled"
SERVING_PLAN_CACHE_BYTES = "hyperspace.serving.planCacheBytes"
SERVING_IO_MODE = "hyperspace.serving.ioMode"
SERVING_TENANT_MAX_QUEUED = "hyperspace.serving.tenant.maxQueued"
FLIGHT_RECORDER_ENABLED = "hyperspace.serving.flightRecorder.enabled"
FLIGHT_RECORDER_MAX_RECORDS = "hyperspace.serving.flightRecorder.maxRecords"
FLIGHT_RECORDER_SLOW_MS = "hyperspace.serving.flightRecorder.slowMs"
FLIGHT_RECORDER_HEALTHY_SAMPLE_N = \
    "hyperspace.serving.flightRecorder.healthySampleN"
FLIGHT_RECORDER_MAX_BUNDLES = "hyperspace.serving.flightRecorder.maxBundles"
LIFECYCLE_ENABLED = "hyperspace.lifecycle.enabled"
LIFECYCLE_INTERVAL_S = "hyperspace.lifecycle.intervalS"
LIFECYCLE_BYTE_BUDGET = "hyperspace.lifecycle.byteBudget"
LIFECYCLE_QUICK_APPEND_RATIO = "hyperspace.lifecycle.quickAppendRatio"
LIFECYCLE_FULL_CHURN_RATIO = "hyperspace.lifecycle.fullChurnRatio"
LIFECYCLE_JOURNAL_MAX_ENTRIES = "hyperspace.lifecycle.journal.maxEntries"
LIFECYCLE_BACKOFF_INITIAL_S = "hyperspace.lifecycle.backoff.initialS"
LIFECYCLE_BACKOFF_MAX_S = "hyperspace.lifecycle.backoff.maxS"
LIFECYCLE_LEASE_ENABLED = "hyperspace.lifecycle.lease.enabled"
LIFECYCLE_LEASE_TTL_S = "hyperspace.lifecycle.lease.ttlS"
LIFECYCLE_CDC_ENABLED = "hyperspace.lifecycle.cdc.enabled"
LIFECYCLE_CDC_MERGE_DEBT_RATIO = "hyperspace.lifecycle.cdc.mergeDebtRatio"
LIFECYCLE_COMPACTION_ENABLED = "hyperspace.lifecycle.compaction.enabled"
LIFECYCLE_COMPACTION_MIN_SMALL_FILES = \
    "hyperspace.lifecycle.compaction.minSmallFiles"
LIFECYCLE_COMPACTION_MODE = "hyperspace.lifecycle.compaction.mode"
WATCH_ENABLED = "hyperspace.system.watch.enabled"
WATCH_MODE = "hyperspace.system.watch.mode"
WATCH_POLL_INTERVAL_S = "hyperspace.system.watch.pollIntervalS"
WATCH_DEBOUNCE_MS = "hyperspace.system.watch.debounceMs"
FAULT_INJECTION_ENABLED = "hyperspace.system.faultInjection.enabled"
FAULT_INJECTION_SITE = "hyperspace.system.faultInjection.site"
FAULT_INJECTION_KIND = "hyperspace.system.faultInjection.kind"
FAULT_INJECTION_AT = "hyperspace.system.faultInjection.at"
FAULT_INJECTION_COUNT = "hyperspace.system.faultInjection.count"
FAULT_INJECTION_LATENCY_MS = "hyperspace.system.faultInjection.latencyMs"
FAULT_INJECTION_HANG_S = "hyperspace.system.faultInjection.hangS"
CLIENT_HEDGE_ENABLED = "hyperspace.client.hedge.enabled"
CLIENT_HEDGE_DELAY_MS = "hyperspace.client.hedge.delayMs"
CLIENT_BREAKER_ENABLED = "hyperspace.client.breaker.enabled"
CLIENT_BREAKER_FAILURES = "hyperspace.client.breaker.failures"
CLIENT_BREAKER_COOLDOWN_MS = "hyperspace.client.breaker.cooldownMs"
ALERTS_ENABLED = "hyperspace.alerts.enabled"
ALERTS_INTERVAL_S = "hyperspace.alerts.intervalS"
ALERTS_AVAILABILITY_TARGET = "hyperspace.alerts.availabilityTarget"
ALERTS_LATENCY_TARGET = "hyperspace.alerts.latencyTarget"
ALERTS_FAST_SHORT_S = "hyperspace.alerts.fastShortS"
ALERTS_FAST_LONG_S = "hyperspace.alerts.fastLongS"
ALERTS_FAST_FACTOR = "hyperspace.alerts.fastFactor"
ALERTS_SLOW_SHORT_S = "hyperspace.alerts.slowShortS"
ALERTS_SLOW_LONG_S = "hyperspace.alerts.slowLongS"
ALERTS_SLOW_FACTOR = "hyperspace.alerts.slowFactor"
ALERTS_PENDING_EVALS = "hyperspace.alerts.pendingEvals"
ALERTS_RESOLVE_EVALS = "hyperspace.alerts.resolveEvals"
ALERTS_STALENESS_WARN_S = "hyperspace.alerts.stalenessWarnS"
ALERTS_MAX_ENTRIES = "hyperspace.alerts.maxEntries"
ALERTS_NOTIFY_COMMAND = "hyperspace.alerts.notify.command"

_DEFAULT_NUM_BUCKETS = 200  # IndexConstants.scala:31-32 (spark.sql.shuffle.partitions default)


def _index_compression_default() -> str:
    from hyperspace_tpu.io.parquet import INDEX_COMPRESSION_DEFAULT

    return INDEX_COMPRESSION_DEFAULT


@dataclasses.dataclass
class HyperspaceConf:
    """Session-scoped configuration.

    Defaults follow index/IndexConstants.scala:
      - num_buckets=200            (:31-32)
      - hybrid scan off, appended<=0.3 / deleted<=0.2 byte ratios (:40-48)
      - filter-rule bucket spec off (:52-53)
      - cache TTL 300 s            (:61-63)
      - optimize threshold 256 MB  (:91-92)
      - lineage off                (:97-99)
    """

    system_path: Optional[str] = None
    num_buckets: int = _DEFAULT_NUM_BUCKETS
    lineage_enabled: bool = False
    hybrid_scan_enabled: bool = False
    hybrid_scan_max_appended_ratio: float = 0.3
    hybrid_scan_max_deleted_ratio: float = 0.2
    optimize_file_size_threshold: int = 256 * 1024 * 1024
    # Split each bucket's sorted run into files of at most this many rows
    # (0 = one file per bucket).  Smaller files = finer per-file min/max
    # pruning granularity (and bounded Parquet sizes at scale).
    index_max_rows_per_file: int = 0
    filter_rule_use_bucket_spec: bool = False
    cache_expiry_seconds: int = 300
    source_providers: str = "default,delta,iceberg"
    signature_provider: str = "IndexSignatureProvider"
    # Operation-log backend, a dotted class path.  The default uses POSIX
    # create-if-absent + atomic rename; object stores without atomic
    # rename (e.g. GCS/S3 generation-/etag-conditional puts) plug in a
    # subclass of IndexLogManager here — the seam SURVEY.md §7 flags as a
    # hard part of the reference's HDFS-rename assumption.
    log_manager_class: str = (
        "hyperspace_tpu.index.log_manager.IndexLogManager")
    # Storage backend for ObjectStoreLogManager (a LogStore subclass,
    # io/log_store.py): conditional-put primitives the rename-less log
    # protocol is built on.  Ignored by the default POSIX manager.
    log_store_class: str = "hyperspace_tpu.io.log_store.EmulatedObjectStore"
    # Optimistic transaction loop (actions/base.py): on a concurrent-write
    # conflict the action re-validates against the new latest log id and
    # retries with jittered backoff, up to this many extra attempts
    # (0 = the reference's abort-on-conflict behavior).
    concurrency_max_retries: int = 3
    # Degraded-mode querying: an index whose log is unreadable, torn past
    # recovery, or whose store is erroring is SKIPPED by the rewrite rules
    # — the query answers from the source scan and telemetry records an
    # IndexDegradedEvent.  Off = such an index raises instead (strict).
    degraded_fallback_to_source: bool = True
    # EmulatedObjectStore listing-visibility window (ms): keys committed
    # within the window are hidden from list operations (point reads stay
    # strong) — the eventual-consistency shape object-store listings have.
    object_store_stale_list_ms: float = 0.0
    event_logger: str = ""
    # Reference default allow-list (HyperspaceConf.scala:97).
    supported_file_formats: str = "avro,csv,json,orc,parquet,text"
    # TPU data-plane tunable: kernel row dimensions are padded up to the
    # next multiple of this, so builds of different datasets share one
    # compiled program per capacity instead of paying a fresh XLA compile
    # per distinct row count.  Env HS_DEVICE_BATCH_ROWS overrides the
    # default (the test suite shrinks it so tiny CPU builds stay tiny).
    device_batch_rows: int = dataclasses.field(
        default_factory=lambda: int(
            os.environ.get("HS_DEVICE_BATCH_ROWS", 1 << 20)))
    # Below this row count a filter evaluates host-side (arrow compute): a
    # device round trip costs fixed transfer latency plus per-row upload,
    # which a vectorized host pass may never repay.  None (the default)
    # derives the threshold from the MEASURED host–device link at first
    # use (utils/calibrate.py).  Set an int to pin it (always wins).
    device_filter_min_rows: Optional[int] = None
    # At or above this row count a device-eligible filter shards its
    # columns over ALL visible devices (1-D mesh) instead of evaluating on
    # one chip: the predicate is elementwise, so XLA partitions it with
    # zero collectives and each device scans 1/N of the rows.
    mesh_filter_min_rows: int = 1 << 24
    # Parquet codec for INDEX data files.  An index is a derived,
    # query-latency-oriented copy: lz4 decodes ~25% faster than snappy at
    # the same size on typical numeric index columns ("none" is fastest
    # still, +16% size).  Source data is never rewritten.  The default
    # literal lives with the writers (io/parquet.INDEX_COMPRESSION_DEFAULT)
    # so a compression-kwarg-less writer call can never drift from it.
    index_file_compression: str = dataclasses.field(
        default_factory=lambda: _index_compression_default())
    # Same cost model for joins: below this (max-side) row count the
    # sorted-merge join runs in numpy on host.  None = calibrate from
    # measured physics.
    device_join_min_rows: Optional[int] = None
    # Same cost model for the BUILD's fused hash+lexsort kernel: below
    # this row count the bit-identical host mirror runs instead (a small
    # build pays more in transfer + compile than it saves).  The layouts
    # are identical either way — only where the permutation is computed
    # changes.  None = calibrate from measured physics.
    device_build_min_rows: Optional[int] = None
    # With >1 visible device, a bucket-aligned INNER join at or above this
    # total row count dispatches its per-bucket joins over the mesh
    # (parallel/join.copartitioned_join_ragged: buckets range-partitioned
    # over the shard axis, zero-collective by co-partitioning); below it,
    # the host thread pool runs the buckets (the single-chip path).
    mesh_join_min_rows: int = 1 << 24
    # Same cost model for GROUP BY: at or above this row count an eligible
    # aggregation (integer/bool keys, null-free numeric inputs,
    # sum/min/max/mean/count) runs as the device segment-reduction kernel
    # (ops/aggregate.py); below it, host arrow hash aggregation.
    # Aggregation ships EVERY input column to the device, so cold small
    # inputs stay on host.  None = calibrate from measured physics.
    device_agg_min_rows: Optional[int] = None
    # HBM-resident index-column cache (execution/device_cache.py): byte
    # budget for post-decode device arrays kept across queries, keyed by
    # file identity.  0 disables.
    device_cache_bytes: int = 1 << 30
    # "auto": populate when the device path runs anyway; "eager": ship
    # eligible scan columns on first use (pay the transfer once, serve
    # repeats from HBM); "off": never cache.
    device_cache_policy: str = "auto"
    # Row threshold when inputs are ALREADY resident (latency-only
    # break-even); applies to every op kind.  None = calibrate.
    device_resident_min_rows: Optional[int] = None
    # Distributed build over the device mesh: "auto" uses it when more than
    # one accelerator is visible; "on"/"off" force it.  The shuffle uses
    # capacity-padded all_to_all; slack is the initial headroom factor over
    # the perfectly-balanced per-destination row count (doubled on overflow).
    parallel_build: str = "auto"
    shuffle_capacity_slack: float = 1.5
    # The engine-wide device mesh (parallel/mesh.py): "auto" activates the
    # mesh-sharded kernel paths (sharded build route, bucket-owned join/
    # aggregate dispatch) whenever more than one local device is visible;
    # "on" insists (still a no-op below 2 devices — there is nothing to
    # shard); "off" pins every kernel to the single-device path, which is
    # bit-equal by construction (the mesh changes WHERE work runs, never
    # the layout or the answer).  maxDevices caps how many local devices
    # the mesh spans (0 = all) — useful to leave a device free for
    # serving while builds shard over the rest.
    mesh_enabled: str = "auto"
    mesh_max_devices: int = 0
    # Same cost model as meshJoinMinRows for GROUP BY: at or above this
    # row count an eligible device aggregation shards its rows over the
    # mesh by group-key bucket ownership (parallel/aggregate.py — each
    # group lives wholly on one device, so no partial-merge pass exists);
    # below it, the single-device segment kernel.
    mesh_agg_min_rows: int = 1 << 24
    # Overlapped build pipeline (actions/create.py; docs/13, docs/16):
    #   - pipeline.enabled: the external (spill) build runs as overlapped
    #     stages — async prefetch of source decode, concurrent chunk
    #     routing, and streaming per-bucket-group finalize — instead of
    #     the forced-serial read → route → finalize loop.  Off is the
    #     bit-equal serial reference (layout NEVER depends on this flag;
    #     tests/test_build_pipeline.py proves it) and the sane setting
    #     for debugging or strictly single-threaded environments.
    #   - prefetchDepth: decoded-but-unconsumed source chunks the
    #     prefetcher may hold (its ONE reader thread decodes file N+1
    #     while file N routes; the bound is the backpressure that keeps
    #     peak RSS at ~depth device batches, not the dataset).
    #   - finalizeWorkers: worker threads merging + parquet-encoding
    #     closed bucket groups, concurrent with routing of remaining
    #     input.  Each in-flight group pins one bucket's rows in memory.
    build_pipeline_enabled: bool = True
    build_prefetch_depth: int = 2
    build_finalize_workers: int = 4
    # Fault-tolerant multi-host build (parallel/multihost_build.py;
    # docs/21):
    #   - multihost.hosts >= 2 runs createIndex as N subprocess hosts
    #     cooperating through crash-recoverable work claims over the
    #     LogStore CAS seam — each host routes claimed chunks, then
    #     finalizes claimed bucket GROUPS into its own staging dir; the
    #     coordinating action CAS-commits the union or nothing.  1 runs
    #     one subprocess host through the same claim pipeline (the
    #     baseline for a scaling ratio; also handy for debugging the
    #     protocol without host interleaving).  0 = the ordinary
    #     single-process build (zero multihost code runs).
    #   - claimTtlS: a work claim expires this long after its last
    #     renew; a SIGKILLed host's claims are reclaimed by survivors
    #     after at most one TTL (epoch fencing keeps the zombie out).
    #   - pollS: claim-table poll interval for hosts waiting on the
    #     route phase to drain and for the coordinator.
    #   - deadlineS: coordinator wall-clock budget; if claims stop
    #     progressing (every host dead) the build fails loudly instead
    #     of hanging.
    multihost_build_hosts: int = 0
    multihost_build_claim_ttl_s: float = 10.0
    multihost_build_poll_s: float = 0.05
    multihost_build_deadline_s: float = 600.0
    # Comma-separated glob pattern(s); when set, createIndex records the
    # pattern as the indexed root paths so later-appearing directories that
    # match are picked up by refresh (IndexConstants.scala:108-114).
    globbing_pattern: str = ""
    # Explain output rendering (IndexConstants.scala:69-80): "plaintext",
    # "html", or "console"; custom highlight tags override the mode default.
    display_mode: str = "plaintext"
    highlight_begin_tag: str = ""
    highlight_end_tag: str = ""
    # When the latest log entry of an index is a TRANSIENT state (a prior
    # action died mid-flight), lifecycle calls through the collection
    # manager first roll it back to the last stable state — an implicit
    # cancel() (actions/CancelAction.scala:25-58).  Off by default: the
    # reference's contract is explicit user recovery, and an in-flight
    # concurrent action looks identical to a crashed one (the rollback is
    # still SAFE either way — the optimistic log write arbitrates — but
    # it would make the racer that started LATER win).
    auto_recovery_enabled: bool = False
    # Integrity subsystem (io/integrity.py, actions/verify.py,
    # index/quarantine.py; docs/15-integrity.md):
    #   - digestOnWrite: hash every index data file as it lands and record
    #     the content digest in its FileInfo (xxh64; ~memory-speed, paid
    #     once per file at build time).  Off = files commit digest-less
    #     and full scrub reports them status="unknown".
    #   - quarantineOnReadFailure: when an index scan dies at execution,
    #     probe that index's files, QUARANTINE the unreadable/mismatched
    #     ones and re-plan with only the damaged buckets read from source
    #     — before PR 2's whole-index fallback (which stays the last
    #     resort).
    #   - autoRepair: after such a containment re-plan answers the query,
    #     rebuild the quarantined buckets in the background of the call
    #     (refresh mode="repair") so the NEXT query runs clean.  Off by
    #     default: repair re-reads source data, which is an operator
    #     decision on metered storage.
    integrity_digest_on_write: bool = True
    integrity_quarantine_on_failure: bool = True
    auto_repair_enabled: bool = False
    # Transient-IO retry for the op-log's file primitives (EIO/ENOSPC/
    # EAGAIN/EINTR): total attempts and exponential-backoff bounds, with
    # uniform jitter so racing writers don't re-collide in lockstep.
    io_retry_max_attempts: int = 3
    io_retry_initial_backoff_ms: float = 10.0
    io_retry_max_backoff_ms: float = 1000.0
    # Observability (telemetry/trace.py; docs/16-observability.md):
    # tracing.enabled turns on per-query span trees (disabled cost: one
    # module-global bool check per instrumented site); trace.sink is a
    # JSONL file path every finished root span is appended to — the
    # machine-readable artifact production runs leave.
    # Run reports and the metrics registry are always on (their cost is
    # a contextvar read / a dict increment at file/action granularity).
    telemetry_tracing_enabled: bool = False
    telemetry_trace_sink: str = ""
    # Size bound for the JSONL trace sink: past it the sink file rotates
    # to <path>.1 (replacing the previous rotation), so a long-lived
    # traced server keeps at most ~2x this on disk.  0 = unbounded.
    telemetry_trace_max_bytes: int = 256 << 20
    # Pipeline timeline profiler (telemetry/timeline.py;
    # docs/16-observability.md): interval-level recording — every
    # BuildReport phase (incl. spill worker threads), executor operator,
    # and block_until_ready-timed device kernel lands as a
    # (lane, kind, start, end) interval in a bounded process ring, plus
    # a background memory sampler during profiled actions.  Off by
    # default; the disabled cost is one module-global bool check (the
    # device-kernel seams never force a sync while off).  maxIntervals
    # bounds the ring (oldest dropped, counted in timeline.dropped);
    # memorySampleMs is the sampler cadence (0 disables the sampler).
    timeline_enabled: bool = False
    # Strict-mode runtime sync guard (execution/sync_guard.py): armed per
    # collect; a device→host conversion outside the attributed
    # sync_guard.pull/scalar seams raises DeviceSyncError and counts
    # guard.sync.violations.  Off (the default) leaves jax untouched.
    device_guard_enabled: bool = False
    timeline_max_intervals: int = 8192
    timeline_memory_sample_ms: float = 25.0
    # Hyperspace.doctor() thresholds (telemetry/doctor.py): the serving
    # check warns past shed/requests >= shedWarnRatio (crit at 5x) and
    # grades latency-SLO burn as the fraction of serve.latency_ms
    # observations above latencySloMs.
    doctor_latency_slo_ms: float = 1000.0
    doctor_shed_warn_ratio: float = 0.05
    # doctor() device-skew grading (single-process ``device_skew`` check
    # and the fleet-level ``fleet.skew`` check): warn when the
    # max/median ratio over attributed per-device (or per-process)
    # kernel milliseconds reaches this; 0 disables the grading.
    doctor_device_skew_warn: float = 4.0
    # Fleet telemetry federation (telemetry/fleet.py;
    # docs/16-observability.md):
    #   - enabled: each process publishes a bounded heartbeat snapshot
    #     (identity/role, typed metrics, health grade, per-device
    #     kernel ms, interesting flight-recorder tail) under
    #     <systemPath>/_hyperspace_fleet through the LogStore seam —
    #     the substrate of fleet_status()/fleet_metrics()/
    #     doctor(fleet=True).  Off by default: publishing writes small
    #     files on a cadence, an operator decision on metered storage.
    #   - publishIntervalS: heartbeat cadence.
    #   - staleAfterS: age past which a heartbeat counts as a
    #     dead/hung process (fleet doctor crit); 0 derives 2x the
    #     publish interval.
    #   - pruneAfterS: age past which publishers garbage-collect a
    #     dead process's heartbeat entirely.
    fleet_telemetry_enabled: bool = False
    fleet_publish_interval_s: float = 5.0
    fleet_stale_after_s: float = 0.0
    fleet_prune_after_s: float = 600.0
    # Build-pipeline profiler (telemetry/build_report.py): every action
    # run records per-phase wall time, bytes moved, spill counts, and
    # memory gauges into a BuildReport (Hyperspace.last_build_report()),
    # exported through the metrics registry (build.phase.*.seconds,
    # build.spill.bytes, ...).  Disabling keeps the phase seconds but
    # skips the memory sampling, metric/span export, and the ledger
    # append.
    build_profiling_enabled: bool = True
    # Persistent perf ledger (telemetry/perf_ledger.py): every completed
    # action appends a compact structured record —
    # phases, bytes, outcome, host/jax/conf fingerprint — through the
    # LogStore seam under <systemPath>/_hyperspace_perf, readable via
    # Hyperspace.perf_history() and the interop ``perf_history`` verb.
    # Appends are fault-quiet and never fail the action; the ledger is
    # bounded (oldest records pruned past maxEntries).
    perf_ledger_enabled: bool = True
    perf_ledger_max_entries: int = 2048
    # Index advisor (hyperspace_tpu/advisor/; docs/17-advisor.md):
    #   - capture.enabled: persist a bounded, deduplicated log of query
    #     FINGERPRINTS (filter/join/group columns + measured bytes
    #     scanned, never data values) under
    #     ``<systemPath>/_hyperspace_workload`` through the LogStore seam.
    #     Off by default: capture writes small files per *distinct* query
    #     shape (repeats fold into a hit counter, flushed at
    #     power-of-two hit counts so the steady-state cost is one dict
    #     update).
    #   - capture.maxEntries: cap on distinct fingerprints; new shapes
    #     beyond it are dropped (counted in advisor.capture.dropped).
    #   - maxCandidates: how many candidate indexes
    #     ``recommend_indexes`` enumerates/scores from the workload.
    advisor_capture_enabled: bool = False
    advisor_capture_max_entries: int = 512
    advisor_max_candidates: int = 20
    # Serving layer (interop/server.py; docs/07-interop.md):
    #   - workers: executor threads per QueryServer — the hard bound on
    #     concurrent query EXECUTION (socket IO threads are separate and
    #     bounded by maxConnections).
    #   - queueDepth: admitted-but-not-yet-running requests; a full queue
    #     sheds new requests with a retryable ERR BUSY.
    #   - maxConnections: concurrent client connections; beyond it the
    #     ACCEPT loop answers ERR BUSY without spawning a handler thread,
    #     so a connection storm cannot grow the thread count.
    #   - defaultDeadlineMs: per-request deadline when the request spec
    #     carries no deadline_ms of its own (0 = none).  The deadline
    #     propagates into dataset.collect via utils/deadline.py and
    #     aborts cleanly at executor phase boundaries (ERR DEADLINE).
    #   - requestTimeoutS / sendTimeoutS: socket read / WRITE timeouts —
    #     a dead client that stops reading mid-Arrow-stream frees its
    #     worker after sendTimeoutS instead of pinning it forever.
    #   - drainGraceS: on drain (SIGTERM), how long in-flight requests
    #     get to finish before the server closes anyway.
    #   - shed.rssWatermarkMb / shed.queueWaitWatermarkMs: overload
    #     watermarks (0 = off) — past either, new requests shed BUSY.
    #   - planCache.*: the optimize-result cache keyed by the advisor's
    #     structural plan fingerprint (execution/plan_cache.py), byte-
    #     budget LRU shared mechanism with the device column cache.
    #   - ioMode: "threaded" (default — one handler thread per
    #     connection) or "async" (one selector thread watches every
    #     socket; workers still execute queries).  Bit-equal wire
    #     behavior either way; async keeps the thread count flat under
    #     thousands of mostly-idle connections.
    #   - tenant.maxQueued: per-tenant cap on queued-or-running requests
    #     (0 = off).  A hot tenant past its cap sheds retryable BUSY
    #     (serve.shed.tenant) without consuming global queue depth, so
    #     it degrades itself, not the fleet.
    serving_workers: int = 4
    serving_queue_depth: int = 16
    serving_max_connections: int = 64
    serving_default_deadline_ms: float = 0.0
    serving_request_timeout_s: float = 30.0
    serving_send_timeout_s: float = 30.0
    serving_drain_grace_s: float = 10.0
    serving_shed_rss_watermark_mb: float = 0.0
    serving_shed_queue_wait_watermark_ms: float = 0.0
    serving_plan_cache_enabled: bool = True
    serving_plan_cache_bytes: int = 64 << 20
    serving_io_mode: str = "threaded"
    serving_tenant_max_queued: int = 0
    # Request flight recorder (telemetry/flight_recorder.py;
    # docs/16-observability.md): a bounded ring of completed request
    # records with tail-based retention — slow (>= slowMs), error,
    # deadline-expired, and shed requests always kept, healthy ones
    # sampled 1-in-healthySampleN (0 = none).  Read by
    # Hyperspace.slow_queries()/diagnostics() and the slow_queries /
    # trace interop verbs; drain()/dump_diagnostics() persist the ring
    # (+ metrics snapshot + perf-ledger tail) as a diagnostics bundle
    # through the LogStore seam, bounded by maxBundles.
    flight_recorder_enabled: bool = True
    flight_recorder_max_records: int = 256
    flight_recorder_slow_ms: float = 1000.0
    flight_recorder_healthy_sample_n: int = 16
    flight_recorder_max_bundles: int = 8
    # Autonomous index lifecycle (hyperspace_tpu/lifecycle/;
    # docs/19-lifecycle.md):
    #   - enabled: the opt-in maintenance daemon thread — detect source
    #     change, pick the cheapest refresh mode, close the advisor loop
    #     under the byte budget, journal every decision.  Off by
    #     default: autonomous builds re-read source data, an operator
    #     decision on metered storage.  ``maintenance_cycle()`` drives
    #     one step at a time regardless of this flag.
    #   - intervalS: seconds between daemon cycles.
    #   - byteBudget: total on-disk index bytes the advisor pass may
    #     grow the fleet to; 0 disables autonomous create/delete
    #     entirely (refresh/repair decisions are unaffected).
    #   - quickAppendRatio: appended-bytes fraction (new + pending
    #     hybrid-scan debt, over recorded source bytes) below which an
    #     append-only change takes the metadata-only quick refresh
    #     (hybrid scan must be on); above it, incremental.
    #   - fullChurnRatio: changed-file fraction of the recorded set at
    #     or past which a full rebuild beats an incremental pass.
    #   - journal.maxEntries: decision-journal bound under
    #     ``<systemPath>/_hyperspace_lifecycle`` (oldest pruned).
    #   - backoff.initialS/.maxS: per-index exponential backoff after a
    #     failed maintenance action (doubles per consecutive failure).
    #   - lease.enabled/.ttlS: cross-process maintenance lease
    #     (lifecycle/lease.py) through the LogStore CAS seam — exactly
    #     one daemon per index tree executes maintenance; losers
    #     idle-poll, a dead holder's lease expires after ttlS and is
    #     taken over with an epoch bump that fences the zombie.
    lifecycle_enabled: bool = False
    lifecycle_interval_s: float = 30.0
    lifecycle_byte_budget: int = 0
    lifecycle_quick_append_ratio: float = 0.1
    lifecycle_full_churn_ratio: float = 0.5
    lifecycle_journal_max_entries: int = 1024
    lifecycle_backoff_initial_s: float = 1.0
    lifecycle_backoff_max_s: float = 300.0
    lifecycle_lease_enabled: bool = False
    lifecycle_lease_ttl_s: float = 30.0
    # Row-level CDC ingest (lifecycle/cdc.py, docs/19-lifecycle.md):
    #   - cdc.enabled: merge-on-read — deletes/mutations with lineage take
    #     the metadata-only quick refresh (the hybrid rule applies the
    #     delete overlay at scan time, bit-equal to a rebuild) while the
    #     accumulated merge debt stays under cdc.mergeDebtRatio of the
    #     recorded source bytes; past it, the real incremental refresh.
    #   - compaction.enabled/.minSmallFiles/.mode: optimizeIndex joins
    #     the policy ladder — when an otherwise-idle index carries at
    #     least minSmallFiles mergeable small files (below
    #     hyperspace.index.optimizeFileSizeThreshold, sharing a bucket),
    #     the daemon schedules an optimize in ``mode`` and journals it
    #     like every other decision.
    lifecycle_cdc_enabled: bool = False
    lifecycle_cdc_merge_debt_ratio: float = 0.2
    lifecycle_compaction_enabled: bool = False
    lifecycle_compaction_min_small_files: int = 8
    lifecycle_compaction_mode: str = "quick"
    # Push-based source change detection (io/watch.py): the maintenance
    # daemon wakes on source events instead of sleeping the full
    # lifecycle interval, so measured staleness is bounded by event
    # latency.  mode: "auto" picks inotify on Linux, else the store
    # notification bus, else stat polling; "inotify"/"store"/"poll"
    # force a backend.  pollIntervalS paces the poll/store watchers;
    # debounceMs coalesces event bursts into one wake.
    watch_enabled: bool = False
    watch_mode: str = "auto"
    watch_poll_interval_s: float = 0.5
    watch_debounce_ms: float = 50.0
    # Deterministic fault injection (io/faults.py): fire ``kind`` at the
    # ``at``-th call of ``site``, ``count`` times.  Test-only machinery;
    # disabled costs one None check per file-level IO op.
    fault_injection_enabled: bool = False
    fault_injection_site: str = ""
    fault_injection_kind: str = ""
    fault_injection_at: int = 1
    fault_injection_count: int = 1
    # Wire-fault shaping (io/faults.py net kinds): added delay for
    # ``slow``, hang duration for ``black-hole``.
    fault_injection_latency_ms: float = 25.0
    fault_injection_hang_s: float = 0.25
    # Front-door resilience features (interop/server.FleetQueryClient).
    # Both default OFF: the plain request path stays byte-for-byte the
    # PR 16 behavior with zero added work beyond a bool check.
    #   - hedge.enabled/.delayMs: fire a second attempt on a different
    #     survivor when the first is slower than the hedge delay
    #     (delayMs 0 = derive from the client's latency EWMA); first
    #     response wins, the loser is discarded by request_id.
    #   - breaker.enabled/.failures/.cooldownMs: per-endpoint circuit
    #     breaker — ``failures`` consecutive errors open it (routing
    #     avoids it), after ``cooldownMs`` one half-open probe may
    #     close it again.
    client_hedge_enabled: bool = False
    client_hedge_delay_ms: float = 0.0
    client_breaker_enabled: bool = False
    client_breaker_failures: int = 5
    client_breaker_cooldown_ms: float = 2000.0
    # The SLO alert engine (telemetry/alerts.py + telemetry/slo.py).
    # Default OFF; when on, an evaluator thread samples the metrics
    # registry every intervalS (0 = ride the fleet-heartbeat cadence)
    # and evaluates multi-window multi-burn-rate rules: the fast pair
    # (fastShortS+fastLongS at fastFactor budgets/window) pages, the
    # slow pair warns.  availabilityTarget/latencyTarget set the error
    # budgets (latency splits serve.latency_ms at
    # hyperspace.doctor.latencySloMs); stalenessWarnS thresholds the
    # staleness objective; pendingEvals/resolveEvals flap-damp the
    # pending -> firing -> resolved machine; maxEntries bounds the
    # persisted transition log; notify.command runs off-thread on
    # firing/resolved with the record as JSON on stdin.
    alerts_enabled: bool = False
    alerts_interval_s: float = 0.0
    alerts_availability_target: float = 0.999
    alerts_latency_target: float = 0.99
    alerts_fast_short_s: float = 300.0
    alerts_fast_long_s: float = 3600.0
    alerts_fast_factor: float = 14.4
    alerts_slow_short_s: float = 21600.0
    alerts_slow_long_s: float = 259200.0
    alerts_slow_factor: float = 1.0
    alerts_pending_evals: int = 2
    alerts_resolve_evals: int = 2
    alerts_staleness_warn_s: float = 600.0
    alerts_max_entries: int = 512
    alerts_notify_command: str = ""
    # Keys explicitly applied through set(); drives canonical-vs-legacy key
    # precedence.
    _set_keys: set = dataclasses.field(default_factory=set, repr=False,
                                       compare=False)

    _FIELD_BY_KEY = {
        SYSTEM_PATH: "system_path",
        NUM_BUCKETS: "num_buckets",
        NUM_BUCKETS_LEGACY: "num_buckets",
        GLOBBING_PATTERN: "globbing_pattern",
        LINEAGE_ENABLED: "lineage_enabled",
        HYBRID_SCAN_ENABLED: "hybrid_scan_enabled",
        HYBRID_SCAN_APPENDED_RATIO: "hybrid_scan_max_appended_ratio",
        HYBRID_SCAN_DELETED_RATIO: "hybrid_scan_max_deleted_ratio",
        OPTIMIZE_FILE_SIZE_THRESHOLD: "optimize_file_size_threshold",
        INDEX_MAX_ROWS_PER_FILE: "index_max_rows_per_file",
        FILTER_RULE_USE_BUCKET_SPEC: "filter_rule_use_bucket_spec",
        CACHE_EXPIRY_SECONDS: "cache_expiry_seconds",
        SOURCE_PROVIDERS: "source_providers",
        SIGNATURE_PROVIDER: "signature_provider",
        LOG_MANAGER_CLASS: "log_manager_class",
        LOG_STORE_CLASS: "log_store_class",
        CONCURRENCY_MAX_RETRIES: "concurrency_max_retries",
        DEGRADED_FALLBACK_TO_SOURCE: "degraded_fallback_to_source",
        OBJECT_STORE_STALE_LIST_MS: "object_store_stale_list_ms",
        EVENT_LOGGER: "event_logger",
        SUPPORTED_FILE_FORMATS: "supported_file_formats",
        DEVICE_BATCH_ROWS: "device_batch_rows",
        DEVICE_FILTER_MIN_ROWS: "device_filter_min_rows",
        MESH_FILTER_MIN_ROWS: "mesh_filter_min_rows",
        INDEX_FILE_COMPRESSION: "index_file_compression",
        DEVICE_JOIN_MIN_ROWS: "device_join_min_rows",
        DEVICE_BUILD_MIN_ROWS: "device_build_min_rows",
        MESH_JOIN_MIN_ROWS: "mesh_join_min_rows",
        DEVICE_AGG_MIN_ROWS: "device_agg_min_rows",
        DEVICE_RESIDENT_MIN_ROWS: "device_resident_min_rows",
        DEVICE_CACHE_BYTES: "device_cache_bytes",
        DEVICE_CACHE_POLICY: "device_cache_policy",
        PARALLEL_BUILD: "parallel_build",
        SHUFFLE_CAPACITY_SLACK: "shuffle_capacity_slack",
        MESH_ENABLED: "mesh_enabled",
        MESH_MAX_DEVICES: "mesh_max_devices",
        MESH_AGG_MIN_ROWS: "mesh_agg_min_rows",
        BUILD_PIPELINE_ENABLED: "build_pipeline_enabled",
        BUILD_PREFETCH_DEPTH: "build_prefetch_depth",
        BUILD_FINALIZE_WORKERS: "build_finalize_workers",
        MULTIHOST_BUILD_HOSTS: "multihost_build_hosts",
        MULTIHOST_BUILD_CLAIM_TTL_S: "multihost_build_claim_ttl_s",
        MULTIHOST_BUILD_POLL_S: "multihost_build_poll_s",
        MULTIHOST_BUILD_DEADLINE_S: "multihost_build_deadline_s",
        DISPLAY_MODE: "display_mode",
        HIGHLIGHT_BEGIN_TAG: "highlight_begin_tag",
        HIGHLIGHT_END_TAG: "highlight_end_tag",
        AUTO_RECOVERY_ENABLED: "auto_recovery_enabled",
        AUTO_REPAIR_ENABLED: "auto_repair_enabled",
        INTEGRITY_DIGEST_ON_WRITE: "integrity_digest_on_write",
        INTEGRITY_QUARANTINE_ON_FAILURE: "integrity_quarantine_on_failure",
        IO_RETRY_MAX_ATTEMPTS: "io_retry_max_attempts",
        IO_RETRY_INITIAL_BACKOFF_MS: "io_retry_initial_backoff_ms",
        IO_RETRY_MAX_BACKOFF_MS: "io_retry_max_backoff_ms",
        TELEMETRY_TRACING_ENABLED: "telemetry_tracing_enabled",
        TELEMETRY_TRACE_SINK: "telemetry_trace_sink",
        TELEMETRY_TRACE_MAX_BYTES: "telemetry_trace_max_bytes",
        TIMELINE_ENABLED: "timeline_enabled",
        DEVICE_GUARD_ENABLED: "device_guard_enabled",
        TIMELINE_MAX_INTERVALS: "timeline_max_intervals",
        TIMELINE_MEMORY_SAMPLE_MS: "timeline_memory_sample_ms",
        DOCTOR_LATENCY_SLO_MS: "doctor_latency_slo_ms",
        DOCTOR_SHED_WARN_RATIO: "doctor_shed_warn_ratio",
        DOCTOR_DEVICE_SKEW_WARN: "doctor_device_skew_warn",
        FLEET_TELEMETRY_ENABLED: "fleet_telemetry_enabled",
        FLEET_PUBLISH_INTERVAL_S: "fleet_publish_interval_s",
        FLEET_STALE_AFTER_S: "fleet_stale_after_s",
        FLEET_PRUNE_AFTER_S: "fleet_prune_after_s",
        BUILD_PROFILING_ENABLED: "build_profiling_enabled",
        PERF_LEDGER_ENABLED: "perf_ledger_enabled",
        PERF_LEDGER_MAX_ENTRIES: "perf_ledger_max_entries",
        ADVISOR_CAPTURE_ENABLED: "advisor_capture_enabled",
        ADVISOR_CAPTURE_MAX_ENTRIES: "advisor_capture_max_entries",
        ADVISOR_MAX_CANDIDATES: "advisor_max_candidates",
        SERVING_WORKERS: "serving_workers",
        SERVING_QUEUE_DEPTH: "serving_queue_depth",
        SERVING_MAX_CONNECTIONS: "serving_max_connections",
        SERVING_DEFAULT_DEADLINE_MS: "serving_default_deadline_ms",
        SERVING_REQUEST_TIMEOUT_S: "serving_request_timeout_s",
        SERVING_SEND_TIMEOUT_S: "serving_send_timeout_s",
        SERVING_DRAIN_GRACE_S: "serving_drain_grace_s",
        SERVING_SHED_RSS_MB: "serving_shed_rss_watermark_mb",
        SERVING_SHED_QUEUE_WAIT_MS: "serving_shed_queue_wait_watermark_ms",
        SERVING_PLAN_CACHE_ENABLED: "serving_plan_cache_enabled",
        SERVING_PLAN_CACHE_BYTES: "serving_plan_cache_bytes",
        SERVING_IO_MODE: "serving_io_mode",
        SERVING_TENANT_MAX_QUEUED: "serving_tenant_max_queued",
        FLIGHT_RECORDER_ENABLED: "flight_recorder_enabled",
        FLIGHT_RECORDER_MAX_RECORDS: "flight_recorder_max_records",
        FLIGHT_RECORDER_SLOW_MS: "flight_recorder_slow_ms",
        FLIGHT_RECORDER_HEALTHY_SAMPLE_N: "flight_recorder_healthy_sample_n",
        FLIGHT_RECORDER_MAX_BUNDLES: "flight_recorder_max_bundles",
        LIFECYCLE_ENABLED: "lifecycle_enabled",
        LIFECYCLE_INTERVAL_S: "lifecycle_interval_s",
        LIFECYCLE_BYTE_BUDGET: "lifecycle_byte_budget",
        LIFECYCLE_QUICK_APPEND_RATIO: "lifecycle_quick_append_ratio",
        LIFECYCLE_FULL_CHURN_RATIO: "lifecycle_full_churn_ratio",
        LIFECYCLE_JOURNAL_MAX_ENTRIES: "lifecycle_journal_max_entries",
        LIFECYCLE_BACKOFF_INITIAL_S: "lifecycle_backoff_initial_s",
        LIFECYCLE_BACKOFF_MAX_S: "lifecycle_backoff_max_s",
        LIFECYCLE_LEASE_ENABLED: "lifecycle_lease_enabled",
        LIFECYCLE_LEASE_TTL_S: "lifecycle_lease_ttl_s",
        LIFECYCLE_CDC_ENABLED: "lifecycle_cdc_enabled",
        LIFECYCLE_CDC_MERGE_DEBT_RATIO: "lifecycle_cdc_merge_debt_ratio",
        LIFECYCLE_COMPACTION_ENABLED: "lifecycle_compaction_enabled",
        LIFECYCLE_COMPACTION_MIN_SMALL_FILES:
            "lifecycle_compaction_min_small_files",
        LIFECYCLE_COMPACTION_MODE: "lifecycle_compaction_mode",
        WATCH_ENABLED: "watch_enabled",
        WATCH_MODE: "watch_mode",
        WATCH_POLL_INTERVAL_S: "watch_poll_interval_s",
        WATCH_DEBOUNCE_MS: "watch_debounce_ms",
        FAULT_INJECTION_ENABLED: "fault_injection_enabled",
        FAULT_INJECTION_SITE: "fault_injection_site",
        FAULT_INJECTION_KIND: "fault_injection_kind",
        FAULT_INJECTION_AT: "fault_injection_at",
        FAULT_INJECTION_COUNT: "fault_injection_count",
        FAULT_INJECTION_LATENCY_MS: "fault_injection_latency_ms",
        FAULT_INJECTION_HANG_S: "fault_injection_hang_s",
        CLIENT_HEDGE_ENABLED: "client_hedge_enabled",
        CLIENT_HEDGE_DELAY_MS: "client_hedge_delay_ms",
        CLIENT_BREAKER_ENABLED: "client_breaker_enabled",
        CLIENT_BREAKER_FAILURES: "client_breaker_failures",
        CLIENT_BREAKER_COOLDOWN_MS: "client_breaker_cooldown_ms",
        ALERTS_ENABLED: "alerts_enabled",
        ALERTS_INTERVAL_S: "alerts_interval_s",
        ALERTS_AVAILABILITY_TARGET: "alerts_availability_target",
        ALERTS_LATENCY_TARGET: "alerts_latency_target",
        ALERTS_FAST_SHORT_S: "alerts_fast_short_s",
        ALERTS_FAST_LONG_S: "alerts_fast_long_s",
        ALERTS_FAST_FACTOR: "alerts_fast_factor",
        ALERTS_SLOW_SHORT_S: "alerts_slow_short_s",
        ALERTS_SLOW_LONG_S: "alerts_slow_long_s",
        ALERTS_SLOW_FACTOR: "alerts_slow_factor",
        ALERTS_PENDING_EVALS: "alerts_pending_evals",
        ALERTS_RESOLVE_EVALS: "alerts_resolve_evals",
        ALERTS_STALENESS_WARN_S: "alerts_staleness_warn_s",
        ALERTS_MAX_ENTRIES: "alerts_max_entries",
        ALERTS_NOTIFY_COMMAND: "alerts_notify_command",
    }

    # Auto-calibrated routing thresholds: None = derive from measured
    # host–device link physics (utils/calibrate.py).
    _AUTO_INT_FIELDS = ("device_filter_min_rows", "device_join_min_rows",
                        "device_agg_min_rows", "device_build_min_rows",
                        "device_resident_min_rows")

    def device_min_rows(self, kind: str) -> int:
        """Effective host-vs-device threshold for ``kind`` (one of
        filter/join/agg/join_agg/build): an explicitly set conf value
        wins; otherwise the calibrated (or conservative-fallback) value.
        The fused join+aggregate has no conf field of its own — an
        explicit join threshold governs it (it IS the join's device
        decision, with the aggregation fused behind it)."""
        field = "join" if kind == "join_agg" else kind
        explicit = getattr(self, f"device_{field}_min_rows")
        if explicit is not None:
            return int(explicit)
        from hyperspace_tpu.utils.calibrate import calibrated_min_rows

        return calibrated_min_rows(kind)

    def resident_min_rows(self, kind: str) -> int:
        """Threshold when the op's inputs are already device-resident
        (only round-trip latency must be repaid)."""
        if self.device_resident_min_rows is not None:
            return int(self.device_resident_min_rows)
        from hyperspace_tpu.utils.calibrate import (
            calibrated_resident_min_rows,
        )

        return calibrated_resident_min_rows(kind)

    def set(self, key: str, value: Any) -> None:
        field = self._FIELD_BY_KEY.get(key)
        if field is None:
            raise KeyError(f"Unknown hyperspace conf key: {key}")
        # Canonical-key precedence (HyperspaceConf.scala:109-117): a value
        # set via the canonical numBuckets key is never overwritten by the
        # legacy key, regardless of apply order.
        if key == NUM_BUCKETS_LEGACY and NUM_BUCKETS in self._set_keys:
            return
        self._set_keys.add(key)
        current = getattr(self, field)
        if field in self._AUTO_INT_FIELDS:
            value = None if value is None or str(value).lower() in (
                "none", "auto") else int(value)
        elif isinstance(current, bool):
            value = value if isinstance(value, bool) else str(value).lower() == "true"
        elif isinstance(current, int):
            value = int(value)
        elif isinstance(current, float):
            value = float(value)
        # Bypass __setattr__: a LEGACY set must not mark the CANONICAL key
        # as explicitly set (later legacy writes still apply).
        object.__setattr__(self, field, value)

    def get(self, key: str) -> Any:
        field = self._FIELD_BY_KEY.get(key)
        if field is None:
            raise KeyError(f"Unknown hyperspace conf key: {key}")
        return getattr(self, field)

    def __setattr__(self, name: str, value: Any) -> None:
        # Direct attribute assignment of num_buckets counts as setting the
        # canonical key for legacy-key precedence.  During __init__ the
        # tracking set doesn't exist yet — defaults are not "explicitly set".
        object.__setattr__(self, name, value)
        if name == "num_buckets":
            tracked = getattr(self, "_set_keys", None)
            if tracked is not None:
                tracked.add(NUM_BUCKETS)

    def copy(self) -> "HyperspaceConf":
        c = dataclasses.replace(self)
        # replace() aliases mutable fields; precedence state must not leak
        # between the copy and the original.
        object.__setattr__(c, "_set_keys", set(self._set_keys))
        return c
