"""User-facing Dataset: a logical plan + session, with DataFrame-style verbs.

The DataFrame analog the reference operates on.  ``collect()`` runs the
optimizer (rules apply only when hyperspace is enabled on the session,
package.scala:47-79) and then the executor.
"""

from __future__ import annotations

from typing import List, Sequence

import numpy as np
import pyarrow as pa

from hyperspace_tpu.plan.expr import Col, Expr, Lit
from hyperspace_tpu.plan.nodes import (
    Aggregate,
    Compute,
    Distinct,
    Filter,
    Join,
    Limit,
    LogicalPlan,
    Project,
    Scan,
    SetOp,
    Sort,
    Union,
    Window,
    WithColumns,
)


def _index_scans_of(plan: LogicalPlan) -> List[str]:
    """Names of indexes the optimized plan reads (Scan relations carrying
    the index marker) — the guard for degraded re-execution: only a plan
    that actually touches index data qualifies for the source fallback."""
    out: List[str] = []
    if isinstance(plan, Scan) and plan.relation.index_scan_of is not None:
        out.append(plan.relation.index_scan_of)
    for child in plan.children:
        out.extend(_index_scans_of(child))
    return sorted(set(out))


def _index_scan_files(plan: LogicalPlan) -> List:
    """(index name, file paths) per index scan in the plan."""
    out: List = []
    if isinstance(plan, Scan) and plan.relation.index_scan_of is not None:
        out.append((plan.relation.index_scan_of,
                    list(plan.relation.file_paths or ())))
    for child in plan.children:
        out.extend(_index_scan_files(child))
    return out


def _quarantine_damaged_index_files(session, plan: LogicalPlan) -> List[str]:
    """Containment probe after an execution failure on a plan that reads
    index data: stat + parquet-footer-check every index file the plan
    touches, then (for files that pass) re-hash against the digest the
    entry records.  Unreadable/mismatched files are QUARANTINED
    (index/quarantine.py) so the re-plan serves their buckets from
    source.  Returns the newly quarantined paths — empty means the
    failure was not attributable to index data and the caller falls
    through to the whole-plan source fallback."""
    import os

    import pyarrow.parquet as pq

    from hyperspace_tpu.io import integrity

    mgr = session.index_collection_manager
    newly: List[str] = []
    for name, paths in _index_scan_files(plan):
        quarantine = mgr.quarantine_manager(name)
        entry = mgr.get_index(name)
        digest_of = {} if entry is None else \
            {f.name: f.digest for f in entry.content.file_infos()}
        for path in paths:
            reason = None
            try:
                os.stat(path)
            except OSError as err:
                reason = f"stat failed: {err}"
            else:
                try:
                    pq.read_metadata(path)
                except Exception as err:  # noqa: BLE001 — any footer
                    # parse failure means the file cannot serve reads
                    reason = f"unreadable: {err}"
                else:
                    digest = digest_of.get(path)
                    if digest is not None and \
                            integrity.verify_file(path, digest) is False:
                        reason = f"content digest mismatch ({digest})"
            if reason is not None and \
                    quarantine.add(path, f"execution-failure probe: {reason}"):
                newly.append(path)
    return newly


class GroupedDataset:
    """``df.group_by(...)`` intermediate; ``agg`` specs are pandas-style
    keyword pairs: ``agg(total=("l_quantity", "sum"))``."""

    def __init__(self, dataset: "Dataset", group_by: Sequence[str]) -> None:
        self._dataset = dataset
        self._group_by = list(group_by)

    def agg(self, **named_specs) -> "Dataset":
        """Specs are ``out=(input, func)`` where ``input`` is a column name
        or an expression: ``agg(revenue=(col("p") * (1 - col("d")), "sum"))``
        — the TPC-H Q3/Q10 shape."""
        aggs = [(func, agg_in, out)
                for out, (agg_in, func) in named_specs.items()]
        return Dataset(Aggregate(self._group_by, aggs, self._dataset.plan),
                       self._dataset.session)

    def count(self, name: str = "count") -> "Dataset":
        """ROW count per group (count(*): null group keys count too)."""
        if not self._group_by:
            raise ValueError(
                "group_by().count() needs group columns; use "
                "Dataset.count() for the total row count")
        return Dataset(Aggregate(self._group_by, [("count_all", "", name)],
                                 self._dataset.plan), self._dataset.session)


class Dataset:
    def __init__(self, plan: LogicalPlan, session) -> None:
        self.plan = plan
        self.session = session

    # -- verbs --------------------------------------------------------------
    def filter(self, condition: Expr) -> "Dataset":
        return Dataset(Filter(condition, self.plan), self.session)

    def select(self, *columns: str, **computed: Expr) -> "Dataset":
        """Project columns, optionally with computed expressions:
        ``select("o_orderkey", revenue=col("p") * (1 - col("d")))``.
        Plain-string-only selects stay a Project (the shape the rewrite
        rules pattern-match); any computed output builds a Compute node."""
        bad = [c for c in columns if not isinstance(c, str)]
        if bad:
            raise ValueError(
                f"select() positional arguments are column names; pass "
                f"expressions as keywords (alias=expr), got {bad[0]!r}")
        if not computed:
            return Dataset(Project(list(columns), self.plan), self.session)
        exprs = [(c, Col(c)) for c in columns]
        for name, e in computed.items():
            if isinstance(e, str):
                # Ambiguous: a rename (col) or a constant (lit)?  Make the
                # caller say which.
                raise ValueError(
                    f"select({name}={e!r}): pass col({e!r}) to project a "
                    f"column under a new name, or lit({e!r}) for a string "
                    f"constant")
            exprs.append((name, e if isinstance(e, Expr) else Lit(e)))
        return Dataset(Compute(exprs, self.plan), self.session)

    def with_column(self, name: str, expr: Expr) -> "Dataset":
        """Append (or replace) one computed column, keeping all others."""
        return Dataset(WithColumns([(name, expr)], self.plan), self.session)

    def with_window(self, name: str, func: str,
                    partition_by: Sequence[str] = (),
                    order_by: Sequence = (),
                    value: str = None, offset: int = 1,
                    frame=None) -> "Dataset":
        """Append one analytic column: ``func(value) OVER (PARTITION BY
        partition_by ORDER BY order_by [ROWS frame])`` — Spark's window
        surface (rank/row_number/dense_rank/ntile/sum/min/max/mean/
        count/lag/lead/first_value/last_value).

            df.with_window("rk", "rank", partition_by=["grp"],
                           order_by=[("revenue", False)])

        ``order_by`` entries are column names or (column, ascending)
        pairs, like ``sort``.  Aggregates with an ORDER BY are running
        (Spark's default RANGE frame: rows tied on the order key share
        one value); without one they reduce the whole partition.
        ``lag``/``lead`` shift ``value`` by ``offset`` rows within the
        partition's order (out-of-partition positions yield null);
        ``ntile`` reads its tile count from ``offset``.  ``frame`` is an
        explicit ROWS frame as an (lo, hi) pair of row offsets relative
        to the current row (negative = preceding, None = unbounded):
        ``frame=(None, 0)`` is ROWS BETWEEN UNBOUNDED PRECEDING AND
        CURRENT ROW, ``frame=(-2, 2)`` a centered 5-row frame."""
        normalized = []
        for k in order_by:
            if isinstance(k, str):
                normalized.append((k, True))
            elif (isinstance(k, (tuple, list)) and len(k) == 2
                    and isinstance(k[0], str)):
                normalized.append((k[0], bool(k[1])))
            else:
                raise ValueError(
                    f"Window order key must be a column name or a "
                    f"(column, ascending) pair, got {k!r}")
        if frame is not None:
            if (not isinstance(frame, (tuple, list)) or len(frame) != 2):
                raise ValueError(
                    f"frame must be an (lo, hi) pair of row offsets "
                    f"(None = unbounded), got {frame!r}")
            frame = (frame[0], frame[1])
        return Dataset(Window(name, func, value, list(partition_by),
                              normalized, self.plan, offset=offset,
                              frame=frame),
                       self.session)

    def join(self, other: "Dataset", condition: Expr, how: str = "inner") -> "Dataset":
        return Dataset(Join(self.plan, other.plan, condition, how), self.session)

    def sort(self, *keys, ascending: bool = True) -> "Dataset":
        """Order by ``keys`` — column names, or (column, ascending)
        pairs; a bare name takes the ``ascending`` default."""
        normalized = []
        for k in keys:
            if isinstance(k, str):
                normalized.append((k, ascending))
            elif (isinstance(k, (tuple, list)) and len(k) == 2
                    and isinstance(k[0], str)
                    and isinstance(k[1], (bool, int, np.bool_,
                                          np.integer))):
                # Bool-like flags only (incl. ints / numpy bools); a string
                # is the ('a', 'b') two-column confusion and None/nested
                # junk means the caller didn't intend a direction — reject.
                normalized.append((k[0], bool(k[1])))
            else:
                raise ValueError(
                    f"Sort key must be a column name or a "
                    f"(column, ascending) pair, got {k!r}")
        return Dataset(Sort(normalized, self.plan), self.session)

    def limit(self, n: int) -> "Dataset":
        return Dataset(Limit(n, self.plan), self.session)

    def distinct(self) -> "Dataset":
        """Unique rows over the full output (SQL DISTINCT)."""
        return Dataset(Distinct(self.plan), self.session)

    def union(self, other: "Dataset") -> "Dataset":
        """UNION ALL with bag semantics, columns resolved BY NAME and
        missing columns null-filled — Spark's
        ``unionByName(allowMissingColumns=True)``, not positional
        ``union``.  Numeric widths widen (int32 ∪ int64 → int64); truly
        incompatible same-named types fail at execution.  Chain
        ``.distinct()`` for SQL UNION."""
        return Dataset(Union([self.plan, other.plan]), self.session)

    def intersect(self, other: "Dataset") -> "Dataset":
        """SQL INTERSECT: distinct rows present in BOTH datasets, rows
        compared positionally and null-safely (Spark's intersect)."""
        return Dataset(SetOp("intersect", self.plan, other.plan),
                       self.session)

    def subtract(self, other: "Dataset") -> "Dataset":
        """SQL EXCEPT: distinct rows of this dataset absent from
        ``other`` (Spark's subtract/except), null-safe comparison."""
        return Dataset(SetOp("except", self.plan, other.plan),
                       self.session)

    def cache(self) -> "Dataset":
        """Materialize this dataset's CURRENT result and return a Dataset
        over the in-memory table (Spark's ``df.cache()`` role, eagerly).
        Later queries over it skip IO and re-optimization of the subtree;
        underlying file changes no longer affect it (like a cached RDD).
        Device-side residency is separate: the HBM column cache
        (execution/device_cache.py) keeps hot INDEX columns on-chip
        keyed by file identity."""
        from hyperspace_tpu.plan.nodes import InMemory

        return Dataset(InMemory(self.collect()), self.session)

    def group_by(self, *columns: str) -> "GroupedDataset":
        return GroupedDataset(self, columns)

    def agg(self, **named_specs) -> "Dataset":
        """Global aggregation (no grouping): ``df.agg(n=("k", "count"))``."""
        return GroupedDataset(self, ()).agg(**named_specs)

    # -- execution ----------------------------------------------------------
    def optimized_plan(self, use_indexes: bool = True) -> LogicalPlan:
        return self.session.optimize(self.plan, use_indexes=use_indexes)

    def collect(self, plan_cache=None) -> pa.Table:
        """Optimize + execute, wrapped in the query-lifecycle trace and a
        :class:`~hyperspace_tpu.telemetry.report.QueryRunReport`: every
        branch this method can take (re-plan, quarantine containment,
        source fallback) is recorded so ``last_run_report()`` can explain
        the query afterwards — docs/16-observability.md.

        ``plan_cache`` is the serving layer's optimize-result cache
        (:class:`~hyperspace_tpu.execution.plan_cache.PlanCache`): on a
        fresh hit the optimizer pass is skipped entirely and the cached
        plan goes straight to the executor; an entry whose plan fails at
        execution is dropped before the degraded/containment machinery
        runs.  Local callers leave it None — caching pays off for the
        repeat-heavy served workload, not one-shot notebook queries."""
        from hyperspace_tpu.telemetry import report as run_report
        from hyperspace_tpu.telemetry import trace

        # Conf set after session construction still wins (same contract as
        # the fault injector / integrity conf re-application).
        trace.configure_from_conf(self.session.conf)
        from hyperspace_tpu.telemetry import timeline

        timeline.configure_from_conf(self.session.conf)
        from hyperspace_tpu.execution import sync_guard

        sync_guard.arm(self.session.conf)
        token = run_report.start()
        query_span = None
        try:
            with trace.span("query.collect") as sp:
                query_span = sp  # the real Span when tracing is enabled
                out = self._collect_traced(plan_cache)
        except Exception:
            rep = run_report.active()
            if rep is not None:
                rep.outcome = "error"
            raise
        finally:
            rep = run_report.finish(token)
            if isinstance(query_span, trace.Span):
                rep.root_span = query_span
            self.session.last_run_report_value = rep
            if trace.current_request_context() is None:
                # A LOCAL query: feed the flight recorder here so
                # slow_queries() works without a server.  Served queries
                # are recorded by their worker/handler (with wire trace
                # context and queue timings), which sets the request
                # scope this checks.  record_local never raises.
                from hyperspace_tpu.telemetry import flight_recorder

                flight_recorder.record_local(self.session.conf, rep)
        if self.session.conf.advisor_capture_enabled:
            # Workload capture (advisor/workload.py): the run report just
            # finished is the feed — fingerprint + measured bytes, folded
            # into the deduplicated workload log.  capture() never raises.
            from hyperspace_tpu.advisor import workload as _workload

            _workload.capture(self.session, self.plan, rep,
                              result_rows=out.num_rows)
        return out

    def explain(self, verbose: bool = False, whatif=None) -> str:
        """The with/without-indexes plan comparison
        (``Hyperspace.explain`` without needing the Hyperspace object).

        ``whatif`` switches to advisor mode: a list of
        :class:`~hyperspace_tpu.index.index_config.IndexConfig` specs (or
        pre-built hypothetical entries) to plan AGAINST AS IF BUILT —
        returns the rendered plan diff plus the estimated bytes-scanned
        delta, touching no data and never executing
        (docs/17-advisor.md)."""
        if whatif is not None:
            from hyperspace_tpu.advisor.hypothetical import whatif as _whatif

            return _whatif(self.session, self, whatif).render()
        from hyperspace_tpu.plananalysis.explain import explain_string

        return explain_string(self, self.session, verbose=verbose)

    def last_run_report(self):
        """The run report of this session's most recent ``collect()`` on
        the calling thread (None before any query), explaining which
        indexes were considered/used/skipped, every degraded/quarantine
        decision, and — when tracing was enabled — where time went."""
        return self.session.last_run_report_value

    def _collect_traced(self, plan_cache=None) -> pa.Table:
        """One query inside a snapshot scope (sources/snapshots.py): each
        versioned table is read at the version latest when it starts,
        and the answer states those versions in its schema metadata."""
        from hyperspace_tpu.sources import snapshots

        with snapshots.query_scope() as versions:
            out = self._collect_in_scope(plan_cache)
        return snapshots.with_source_versions(out, versions)

    def _collect_in_scope(self, plan_cache=None) -> pa.Table:
        from hyperspace_tpu.exceptions import (
            DeadlineExceededError,
            DeviceSyncError,
        )
        from hyperspace_tpu.execution.executor import Executor
        from hyperspace_tpu.telemetry import report as run_report
        from hyperspace_tpu.telemetry import metrics
        from hyperspace_tpu.telemetry.trace import span
        from hyperspace_tpu.utils import deadline as _deadline

        # Deadline phase boundary: a served request that spent its budget
        # queueing aborts here before paying for planning at all.
        _deadline.check("planning")
        executor = Executor(self.session)
        plan = None
        cache_key = None
        try:
            # Versioned tables resolve their version here, once per query:
            # the cache entry must have been optimized against the same.
            versions = self.session.source_provider_manager.source_versions(
                self.plan)
        except Exception:  # noqa: BLE001 — the query itself reports it
            versions = None
        if plan_cache is not None and versions is not None:
            cache_key = plan_cache.key_for(self.session, self.plan)
            if cache_key is not None:
                plan = plan_cache.get(cache_key, versions)
                if plan is not None:
                    # The optimizer pass (whose rules feed indexes_used)
                    # is skipped on a hit: attribute the cached plan's
                    # index scans so "which index answered this query"
                    # survives caching.  The fingerprint rides the
                    # report so the flight record can name the plan.
                    run_report.record("plan_cache", hit=True,
                                      fingerprint=cache_key)
                    for name in _index_scans_of(plan):
                        run_report.record("index.used", index=name,
                                          message="served from plan cache")
        if plan is not None:
            pass  # optimize skipped: the serving layer's repeat fast path
        else:
            try:
                plan = self.optimized_plan()
                if cache_key is not None:
                    plan_cache.put(cache_key, plan, versions)
                    run_report.record("plan_cache", hit=False,
                                      fingerprint=cache_key)
            except Exception as e:  # noqa: BLE001 — InjectedCrash propagates.
                # PLANNING died with index rewrites on (e.g. every file of
                # an index unreadable, so even its schema cannot be
                # fetched).  Degraded mode owns this stage too: re-plan
                # without indexes; a failure of THAT plan is a genuine
                # query error and propagates from a planning pass indexes
                # never touched.  A deadline expiry is NOT a degraded
                # condition: re-planning would spend more time past a
                # deadline that already passed — propagate it.  A strict-
                # mode sync-guard violation likewise: re-planning would
                # just repeat the unattributed sync.
                if isinstance(e, (DeadlineExceededError, DeviceSyncError)):
                    raise
                if not self.session.is_hyperspace_enabled() or \
                        not self.session.conf.degraded_fallback_to_source:
                    raise
                from hyperspace_tpu.telemetry.events import (
                    IndexDegradedEvent,
                    emit_event,
                )

                emit_event(IndexDegradedEvent(
                    reason=f"index-aware planning failed: {e!r}",
                    message="re-planned without index rewrites"))
                run_report.record("replan", mode="source-fallback",
                                  stage="planning")
                with span("optimize.replan", mode="source-fallback"):
                    plan = self.optimized_plan(use_indexes=False)
        try:
            with span("execute"):
                out = executor.execute(plan)
        except Exception as e:  # noqa: BLE001 — InjectedCrash is a
            # BaseException and still dies like a real crash.
            if isinstance(e, (DeadlineExceededError, DeviceSyncError)):
                # Past-deadline work is the one thing the fallback
                # machinery must NOT do more of — propagate immediately.
                # Same for a strict-mode sync-guard violation: the
                # fallback would re-execute the unattributed sync.
                raise
            if cache_key is not None:
                # The cached plan (or the plan just cached) failed at
                # execution: drop it so the containment/fallback outcome
                # below is what the NEXT request re-derives from scratch,
                # not a replay of this failure.
                plan_cache.invalidate(cache_key)
            index_names = _index_scans_of(plan)
            if not index_names or \
                    not self.session.conf.degraded_fallback_to_source:
                raise
            from hyperspace_tpu.telemetry.events import (
                IndexDegradedEvent,
                emit_event,
            )

            # CONTAINMENT first (the integrity loop, docs/15-integrity.md):
            # probe the index files the dead plan read, quarantine the
            # damaged ones, and re-plan WITH indexes — the rewrite rules
            # now serve only the damaged buckets from source.  One rotten
            # bucket costs one bucket's source IO, not the whole index.
            out = None
            if self.session.conf.integrity_quarantine_on_failure:
                with span("containment.probe") as sp:
                    damaged = _quarantine_damaged_index_files(
                        self.session, plan)
                    sp.set(quarantined=len(damaged))
                if damaged:
                    metrics.inc("quarantine.files", len(damaged))
                    run_report.record(
                        "quarantine", index=",".join(index_names),
                        files=damaged)
                    emit_event(IndexDegradedEvent(
                        index_name=",".join(index_names),
                        reason=f"index scan failed at execution: {e!r}; "
                               f"quarantined {len(damaged)} damaged "
                               f"file(s)",
                        message="re-planned with damaged buckets read "
                                "from source"))
                    run_report.record("replan", mode="containment",
                                      stage="execution")
                    try:
                        executor = Executor(self.session)
                        with span("execute.replan", mode="containment"):
                            out = executor.execute(self.optimized_plan())
                    except Exception:  # noqa: BLE001 — containment is
                        # best-effort; the full fallback below still owns
                        # the answer (InjectedCrash stays fatal).
                        out = None
                    if out is not None and \
                            self.session.conf.auto_repair_enabled:
                        # Opt-in self-heal: rebuild the quarantined
                        # buckets now so the NEXT query runs clean.  A
                        # repair failure must never cost this query its
                        # (already computed) answer.
                        for name in index_names:
                            try:
                                self.session.index_collection_manager \
                                    .refresh(name, "repair")
                            except Exception as repair_exc:  # noqa: BLE001
                                # Best-effort self-heal; the failure must
                                # still be visible in the run report.
                                run_report.record(
                                    "replan", mode="auto-repair-failed",
                                    stage="execution",
                                    error=repr(repair_exc))
            if out is None:
                # Degraded mode, execution stage — the LAST resort: re-plan
                # WITHOUT index rewrites and run the source scan; a failure
                # of that plan is a genuine source problem and propagates.
                emit_event(IndexDegradedEvent(
                    index_name=",".join(index_names),
                    reason=f"index scan failed at execution: {e!r}",
                    message="re-executed against the source scan"))
                run_report.record("replan", mode="source-fallback",
                                  stage="execution")
                executor = Executor(self.session)
                with span("execute.replan", mode="source-fallback"):
                    out = executor.execute(
                        self.optimized_plan(use_indexes=False))
        # Physical stats of the most recent execution (join strategies,
        # scan file counts) — read by verbose explain and tests.
        executor.finalize_stats()
        self.session.last_execution_stats = executor.stats
        return out

    def to_pandas(self):
        return self.collect().to_pandas()

    def count(self) -> int:
        return self.collect().num_rows

    @property
    def columns(self) -> List[str]:
        return self.plan.output_columns(self.session.schema_of)

    def explain_string(self) -> str:
        return self.plan.tree_string()

    def show(self, n: int = 20) -> None:
        """Print the first ``n`` rows (df.show analog; the reference shims
        Spark's showString, org/apache/spark/sql/hyperspace/utils).

        Materializes the full result like ``collect()`` does (there is no
        limit pushdown); use a selective filter for large datasets."""
        table = self.collect()
        head = table.slice(0, n)
        names = head.column_names
        rows = [[str(v) for v in row.values()] for row in head.to_pylist()]
        widths = [max(len(name), *(len(r[i]) for r in rows), 1) if rows
                  else len(name) for i, name in enumerate(names)]
        print(" ".join(name.rjust(w) for name, w in zip(names, widths)))
        for r in rows:
            print(" ".join(v.rjust(w) for v, w in zip(r, widths)))
        if table.num_rows > n:
            print(f"... ({table.num_rows - n} more rows)")
