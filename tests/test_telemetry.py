"""The trace/metrics/run-report contract (docs/16-observability.md):
span nesting (including under exceptions), contextvar isolation across
the IO thread pool, zero-allocation disabled path, metrics
snapshot/reset + Prometheus rendering, JSONL sink format, run reports on
clean and degraded queries, conflict-retry ActionEvents, and the
profiling deprecation alias."""

from __future__ import annotations

import json
import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from hyperspace_tpu import Hyperspace, HyperspaceSession, IndexConfig, col
from hyperspace_tpu.telemetry import metrics, report, trace
from hyperspace_tpu.telemetry.trace import (
    CollectingTraceSink,
    JsonlTraceSink,
    NOOP_SPAN,
    current_span,
    span,
)


@pytest.fixture()
def traced():
    trace.enable_tracing()
    sink = trace.add_sink(CollectingTraceSink())
    yield sink
    trace.remove_sink(sink)
    trace.disable_tracing()


# -- spans ------------------------------------------------------------------
def test_span_nesting_and_delivery(traced):
    with span("outer", a=1) as outer:
        with span("inner") as inner:
            inner.set(rows=3)
    assert [s.name for s in traced.spans] == ["outer"]
    assert outer.children == [inner]
    assert inner.tags["rows"] == 3
    assert outer.duration_ms >= inner.duration_ms >= 0.0
    assert outer.status == inner.status == "ok"


def test_span_nesting_under_exceptions(traced):
    """An exception unwinds every open span, marks each error, and still
    delivers the root — the trace of a failed query must exist."""
    with pytest.raises(ValueError):
        with span("root"):
            with span("child"):
                raise ValueError("boom")
    (root,) = traced.spans
    assert root.status == "error" and "boom" in root.error
    (child,) = root.children
    assert child.status == "error"
    # The contextvar fully unwound: a new span is a fresh root.
    with span("next"):
        pass
    assert [s.name for s in traced.spans] == ["root", "next"]


def test_disabled_span_is_shared_noop():
    trace.disable_tracing()
    s = span("anything", big_tag="x")
    assert s is NOOP_SPAN
    with s as live:
        live.set(whatever=1)  # no-op, no error
    assert current_span() is NOOP_SPAN


def test_current_span_tagging(traced):
    with span("outer"):
        current_span().set(late=True)
    assert traced.spans[0].tags["late"] is True


def test_contextvar_isolation_across_threads(traced):
    """Worker threads (utils/parallel_map) must not attach their spans to
    the submitting thread's span — each thread's trace is its own tree."""
    from hyperspace_tpu.utils.parallel_map import parallel_map_ordered

    def work(i: int) -> int:
        with span(f"worker.{i}"):
            return i

    with span("driver") as driver:
        out = parallel_map_ordered(work, list(range(8)))
    assert out == list(range(8))
    # The driver span has no worker children; every worker span was
    # delivered as its own root (or, for the inline nested path, none
    # landed under the driver unnoticed).
    assert all(not c.name.startswith("worker.") for c in driver.children)
    delivered = {s.name for s in traced.spans}
    assert "driver" in delivered
    assert {f"worker.{i}" for i in range(8)} <= delivered


def test_jsonl_sink_format(tmp_path, traced):
    path = str(tmp_path / "trace.jsonl")
    sink = trace.add_sink(JsonlTraceSink(path))
    try:
        with span("root", files=2):
            with span("leaf"):
                pass
    finally:
        trace.remove_sink(sink)
    (line,) = open(path, encoding="utf-8").read().splitlines()
    d = json.loads(line)
    assert d["name"] == "root" and d["status"] == "ok"
    assert d["tags"] == {"files": 2}
    assert d["children"][0]["name"] == "leaf"
    assert d["duration_ms"] >= 0.0


def test_span_to_dict_roundtrip_error(traced):
    with pytest.raises(RuntimeError):
        with span("r"):
            raise RuntimeError("x")
    d = traced.spans[0].to_dict()
    assert d["status"] == "error" and d["error"].startswith("RuntimeError")


# -- metrics ----------------------------------------------------------------
def test_metrics_snapshot_and_reset():
    reg = metrics.MetricsRegistry()
    reg.inc("a.count")
    reg.inc("a.count", 2)
    reg.set_gauge("b.gauge", 7.5)
    reg.observe("c.hist", 3.0)
    reg.observe("c.hist", 400.0)
    snap = reg.snapshot()
    assert snap["a.count"] == 3.0
    assert snap["b.gauge"] == 7.5
    assert snap["c.hist"]["count"] == 2
    assert snap["c.hist"]["min"] == 3.0 and snap["c.hist"]["max"] == 400.0
    reg.reset()
    assert reg.snapshot() == {}


def test_metrics_hit_ratio_derived():
    reg = metrics.MetricsRegistry()
    reg.inc("cache.device.hits", 3)
    reg.inc("cache.device.misses", 1)
    assert reg.snapshot()["cache.device.hit_ratio"] == 0.75


def test_metrics_prometheus_rendering():
    reg = metrics.MetricsRegistry()
    reg.inc("io.retry.attempts", 2)
    reg.set_gauge("cache.device.bytes", 1024)
    reg.observe("span.ms", 12.0)
    text = reg.render_prometheus()
    assert "# TYPE hyperspace_io_retry_attempts counter" in text
    assert "hyperspace_io_retry_attempts 2" in text
    assert "hyperspace_cache_device_bytes 1024" in text
    assert 'hyperspace_span_ms_bucket{le="25"} 1' in text
    assert "hyperspace_span_ms_count 1" in text


def test_metrics_bounded_series():
    reg = metrics.MetricsRegistry()
    for i in range(5000):
        reg.inc(f"runaway.{i}")
    assert len(reg.snapshot()) <= 4096
    # Known names keep counting even at the cap.
    reg.inc("runaway.0")
    assert reg.counter("runaway.0") == 2.0


def test_metrics_thread_safety():
    import threading

    reg = metrics.MetricsRegistry()

    def bump():
        for _ in range(1000):
            reg.inc("n")

    threads = [threading.Thread(target=bump) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert reg.counter("n") == 8000.0


# -- end-to-end: query lifecycle -------------------------------------------
@pytest.fixture()
def indexed(tmp_path):
    d = str(tmp_path / "data")
    os.makedirs(d)
    pq.write_table(pa.table({"k": pa.array(np.arange(200, dtype=np.int64)),
                             "v": pa.array(np.arange(200) * 2.0)}),
                   os.path.join(d, "p.parquet"))
    s = HyperspaceSession(system_path=str(tmp_path / "ix"))
    s.conf.num_buckets = 2
    hs = Hyperspace(s)
    hs.create_index(s.read.parquet(d), IndexConfig("tix", ["k"], ["v"]))
    s.enable_hyperspace()
    return s, hs, d


def test_query_trace_covers_lifecycle(indexed, traced):
    s, hs, d = indexed
    ds = s.read.parquet(d).filter(col("k") == 7).select("k", "v")
    assert ds.collect().column("v").to_pylist() == [14.0]
    (root,) = [r for r in traced.spans if r.name == "query.collect"]
    names = {sp.name for sp in root.walk()}
    assert {"query.collect", "optimize", "optimize.rule.filter",
            "execute", "exec.scan", "io.read"} <= names
    scan = root.find("exec.scan")[0]
    assert scan.tags["is_index"] is True
    assert scan.tags["files_read"] >= 1
    # Rows the scan PRODUCED (the pruned bucket), before the filter.
    assert scan.tags["rows"] >= 1


def test_spans_land_on_the_profiler_host_plane(indexed, traced, tmp_path):
    """With tracing on, each span is also a profiler annotation: a
    query's scan, decode and device-sync names are events on the
    ``/host:CPU`` plane of a ``jax.profiler`` trace."""
    import glob

    import jax

    s, hs, d = indexed
    s.conf.device_filter_min_rows = 1  # the device mask: a device.sync
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    out_dir = str(tmp_path / "profile")
    jax.profiler.start_trace(out_dir, profiler_options=options)
    try:
        ds = s.read.parquet(d).filter(col("v") < 100.0).select("k", "v")
        assert ds.collect().num_rows == 50
    finally:
        jax.profiler.stop_trace()
    assert s.last_execution_stats["filters"][0]["strategy"] == "device"
    (path,) = glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                        recursive=True)
    planes = jax.profiler.ProfileData.from_file(path).planes
    (host,) = [p for p in planes if p.name == "/host:CPU"]
    names = {e.name for line in host.lines for e in line.events}
    assert {"exec.scan", "io.read", "device.sync"} <= names


def test_tracing_off_opens_no_span(monkeypatch):
    """The disabled path of every new timing site: ``span()``, the sync
    seams and a build phase allocate no Span and no annotation (the
    phase still counts its seconds)."""
    import jax
    import jax.numpy as jnp

    from hyperspace_tpu.execution import sync_guard
    from hyperspace_tpu.telemetry.build_report import BuildReport

    def refuse(*_a, **_k):
        raise AssertionError("opened while tracing is off")

    trace.disable_tracing()
    monkeypatch.setattr(trace, "Span", refuse)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    assert span("exec.scan") is NOOP_SPAN
    assert sync_guard.pull(jnp.arange(3), "t").tolist() == [0, 1, 2]
    assert sync_guard.scalar(jnp.int32(7), "t") == 7
    report = BuildReport()
    with report.phase("read"):
        pass
    assert "read" in report.phases


def test_run_report_on_clean_query(indexed):
    s, hs, d = indexed
    ds = s.read.parquet(d).filter(col("k") == 7).select("k", "v")
    ds.collect()
    rep = ds.last_run_report()
    assert rep.outcome == "ok" and not rep.degraded
    assert rep.indexes_considered == ["tix"]
    assert rep.indexes_used == ["tix"]
    assert rep.skipped_indexes() == []
    rules = {r["rule"]: r["applied"] for r in rep.rules()}
    assert rules["FilterIndexRule"] is True
    # Tracing was off: the report still exists, just without spans.
    assert rep.span_timings() == []
    # And it serializes.
    assert json.dumps(rep.to_dict())
    assert "FilterIndexRule: applied" in rep.render()


def test_run_report_thread_local(indexed):
    import threading

    s, hs, d = indexed
    ds = s.read.parquet(d).filter(col("k") == 7).select("k", "v")
    ds.collect()
    mine = ds.last_run_report()

    seen = {}

    def other():
        seen["report"] = ds.last_run_report()

    t = threading.Thread(target=other)
    t.start()
    t.join()
    assert mine is not None and seen["report"] is None


def test_rule_and_query_metrics_feed(indexed):
    s, hs, d = indexed
    metrics.reset()
    s.read.parquet(d).filter(col("k") == 7).select("k", "v").collect()
    snap = hs.metrics()
    assert snap["rule.filter.applied"] >= 1
    assert snap["io.files.read"] >= 1
    text = hs.metrics_text()
    assert "hyperspace_rule_filter_applied" in text
    hs.reset_metrics()
    assert "rule.filter.applied" not in hs.metrics()


def test_scrub_metrics_feed(indexed):
    s, hs, d = indexed
    metrics.reset()
    hs.verify_index("tix", mode="full")
    snap = hs.metrics()
    assert snap["scrub.files_checked"] >= 1
    assert snap.get("scrub.files_flagged", 0.0) == 0.0


def test_io_retry_metric_and_report_record():
    from hyperspace_tpu.io import faults
    from hyperspace_tpu.utils.retry import RetryPolicy

    metrics.reset()
    faults.install(faults.FaultPlan(site="data.read", kind="eio", count=2))
    token = report.start()
    try:
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            faults.check("data.read")
            return "ok"

        out = RetryPolicy(initial_backoff_ms=0.1).call(flaky)
    finally:
        rep = report.finish(token)
        faults.clear()
    assert out == "ok" and calls["n"] == 3
    assert metrics.snapshot()["io.retry.attempts"] == 2.0
    retries = [dec for dec in rep.decisions if dec["kind"] == "io.retry"]
    assert len(retries) == 2 and "Error" in retries[0]["error"]


def test_conflict_retry_action_events(tmp_path):
    """The optimistic transaction loop emits a CONFLICT_RETRY ActionEvent
    per absorbed conflict (attempt number in state, reason in message)
    and feeds action.conflict.retries."""
    from hyperspace_tpu.exceptions import ConcurrentWriteError
    from hyperspace_tpu.telemetry.events import (
        CollectingEventLogger,
        CreateActionEvent,
        set_event_logger,
    )

    d = str(tmp_path / "data")
    os.makedirs(d)
    pq.write_table(pa.table({"k": pa.array([1, 2], type=pa.int64()),
                             "v": [1.0, 2.0]}), os.path.join(d, "p.parquet"))
    s = HyperspaceSession(system_path=str(tmp_path / "ix"))
    s.conf.num_buckets = 1
    hs = Hyperspace(s)
    log = CollectingEventLogger()
    set_event_logger(log)
    metrics.reset()
    try:
        from hyperspace_tpu.actions.create import CreateAction

        real_attempt = CreateAction._attempt
        state = {"left": 2}

        def flaky_attempt(self, emit):
            if state["left"] > 0:
                state["left"] -= 1
                raise ConcurrentWriteError("injected racer won")
            return real_attempt(self, emit)

        CreateAction._attempt = flaky_attempt
        try:
            hs.create_index(s.read.parquet(d),
                            IndexConfig("cfx", ["k"], ["v"]))
        finally:
            CreateAction._attempt = real_attempt
    finally:
        set_event_logger(None)
    retries = [e for e in log.events if isinstance(e, CreateActionEvent)
               and e.state.startswith("CONFLICT_RETRY")]
    assert [e.state.split()[1] for e in retries] == ["1/3", "2/3"]
    assert all("injected racer won" in e.message for e in retries)
    assert metrics.snapshot()["action.conflict.retries"] == 2.0
    # The action ultimately succeeded.
    assert s.index_collection_manager.get_index("cfx") is not None


def test_cas_conflict_metric(tmp_path):
    from hyperspace_tpu.io.log_store import EmulatedObjectStore

    metrics.reset()
    store = EmulatedObjectStore(str(tmp_path / "store"))
    assert store.put_if_absent("key", b"a")
    assert not store.put_if_absent("key", b"b")  # generation moved on
    snap = metrics.snapshot()
    assert snap["log.store.puts"] == 2.0
    assert snap["log.cas.conflicts"] == 1.0


def test_conf_enables_tracing_and_sink(tmp_path):
    path = str(tmp_path / "sink.jsonl")
    d = str(tmp_path / "data")
    os.makedirs(d)
    pq.write_table(pa.table({"k": pa.array([1], type=pa.int64()),
                             "v": [2.0]}), os.path.join(d, "p.parquet"))
    s = HyperspaceSession(system_path=str(tmp_path / "ix"))
    s.conf.set("hyperspace.system.telemetry.tracing.enabled", True)
    s.conf.set("hyperspace.system.telemetry.trace.sink", path)
    s.read.parquet(d).select("k").collect()
    roots = [json.loads(ln) for ln in open(path, encoding="utf-8")]
    assert any(r["name"] == "query.collect" for r in roots)


def test_profiling_deprecation_alias():
    from hyperspace_tpu.telemetry.trace import profiler_trace as canonical
    from hyperspace_tpu.utils.profiling import profiler_trace as alias

    assert alias is canonical


def test_explain_verbose_shows_optimizer_decisions(indexed):
    s, hs, d = indexed
    ds = s.read.parquet(d).filter(col("k") == 7).select("k", "v")
    out = hs.explain(ds, verbose=True)
    assert "Optimizer decisions:" in out
    assert "indexes considered: tix" in out
    assert "rule FilterIndexRule: applied" in out
    # After a collect, the last run report is embedded too.
    trace.enable_tracing()
    ds.collect()
    out = hs.explain(ds, verbose=True)
    assert "Last run report:" in out
    assert "where time went:" in out
