"""The program emits what the chip benchmark measures.

Each per-layer metric of ``BENCHMARK.json`` reads a span, a build phase
or a counter by name (``benchmark/layer_metrics/``).  A renamed span
would null its metric silently, so this module runs the benchmark's
three paths at a tiny size on the CPU, with tracing on and a JSONL sink:

  - Q3 served over covering indexes, twice: the fused join decodes its
    sides once, then serves them from HBM; then Q3's join alone;
  - Q3 served over Delta tables under hybrid scan, with a commit the
    index has not absorbed before each request;
  - one ``refresh_index(mode="full")`` of a Delta-sourced index.

It then checks the trace against docs/16's span catalog and looks for
every name a metric reads.
"""

from __future__ import annotations

import datetime
import json
import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from hyperspace_tpu import Hyperspace, HyperspaceSession, IndexConfig
from hyperspace_tpu.interop import QueryServer
from hyperspace_tpu.interop.server import request_query
from hyperspace_tpu.lint import catalog as lint_catalog
from hyperspace_tpu.lint import engine as lint_engine
from hyperspace_tpu.sources.delta import write_delta
from hyperspace_tpu.telemetry import metrics, trace

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Spans the per-layer metrics read (lint.catalog.REQUIRED_SPANS), named
# here one by one so a failure names the span.
SPANS = ("exec.scan", "scan.files", "io.read", "exec.join",
         "exec.aggregate", "exec.filter", "device.sync", "source.snapshot",
         "exec.hybrid_appended", "serve.request")
PHASES = ("read", "prefetch", "spill_route", "spill_finish", "write",
          "validate", "commit")
COUNTERS = ("cache.device.hits", "cache.device.misses",
            "exec.join_agg.sides", "exec.join_agg.sides_resident",
            "serve.plan_cache.source_stale", "hybrid.appended_rows")

_EPOCH = datetime.date(1970, 1, 1)
_DAY0 = (datetime.date(1995, 1, 1) - _EPOCH).days
_CONF = {"hyperspace.tpu.deviceFilterMinRows": 0,
         "hyperspace.tpu.deviceJoinMinRows": 0,
         "hyperspace.tpu.deviceAggMinRows": 0,
         "hyperspace.tpu.deviceBuildMinRows": 0,
         "hyperspace.tpu.deviceResidentMinRows": 0}


def _tables(n_orders: int = 2400, per: int = 4):
    """TPC-H-shaped ORDERS and LINEITEM with the Q3 columns."""
    rng = np.random.default_rng(27)
    keys = np.arange(n_orders, dtype=np.int64)
    odate = _DAY0 + rng.integers(0, 120, n_orders)
    li_key = np.repeat(keys, per)
    ship = odate[li_key] + rng.integers(1, 60, li_key.size)

    def date(days):
        return pa.array(days.astype(np.int32)).cast(pa.date32())

    orders = pa.table({
        "o_orderkey": keys, "o_orderdate": date(odate),
        "o_shippriority": pa.array(np.zeros(n_orders, dtype=np.int32)),
        "o_custkey": rng.integers(1, 150, n_orders)})
    lineitem = pa.table({
        "l_orderkey": li_key, "l_shipdate": date(ship),
        "l_extendedprice": np.round(rng.uniform(1, 1000, li_key.size), 2),
        "l_discount": rng.integers(0, 11, li_key.size) / 100.0,
        "l_quantity": rng.integers(1, 51, li_key.size).astype(np.float64)})
    return orders, lineitem


def _session(system_path: str) -> HyperspaceSession:
    s = HyperspaceSession(system_path=system_path)
    s.conf.num_buckets = 4
    for key, value in _CONF.items():
        s.conf.set(key, value)
    # One chip's build: the external (spilling) route, not the mesh's.
    s.conf.parallel_build = "off"
    return s


def _indexes(s, read) -> None:
    hs = Hyperspace(s)
    hs.create_index(read("orders"), IndexConfig(
        "ord_ix", ["o_orderkey"],
        ["o_orderdate", "o_shippriority", "o_custkey"]))
    hs.create_index(read("lineitem"), IndexConfig(
        "li_ix", ["l_orderkey"],
        ["l_extendedprice", "l_discount", "l_shipdate", "l_quantity"]))


def _q3(fmt: str, paths: dict, day: int) -> dict:
    """The benchmark's served Q3 spec (TPC-H Q3 without CUSTOMER)."""
    lit = {"op": "cast", "type": "date", "child": {
        "value": (_EPOCH + datetime.timedelta(days=day)).isoformat()}}
    return {
        "source": {"format": fmt, "path": paths["orders"]},
        "filter": {"op": "<", "left": {"col": "o_orderdate"},
                   "right": lit},
        "join": {"source": {"format": fmt, "path": paths["lineitem"]},
                 "on": {"op": "==", "col": "o_orderkey",
                        "right_col": "l_orderkey"},
                 "filter": {"op": ">", "left": {"col": "l_shipdate"},
                            "right": lit}},
        "group_by": ["l_orderkey", "o_orderdate", "o_shippriority"],
        "aggs": {"revenue": [
            {"op": "*", "left": {"col": "l_extendedprice"},
             "right": {"op": "-", "left": {"value": 1},
                       "right": {"col": "l_discount"}}}, "sum"]},
        "sort": [["revenue", False], "o_orderdate"],
        "limit": 10,
        "select": ["l_orderkey", "revenue", "o_orderdate",
                   "o_shippriority"]}


def _served_covering(root: str, orders, lineitem) -> None:
    paths = {}
    for name, table in (("orders", orders), ("lineitem", lineitem)):
        paths[name] = os.path.join(root, "pq", name)
        os.makedirs(paths[name])
        for i, part in enumerate(np.array_split(
                np.arange(table.num_rows), 4)):
            pq.write_table(table.take(part), os.path.join(
                paths[name], f"part-{i}.parquet"))
    s = _session(os.path.join(root, "pq_ix"))
    _indexes(s, lambda name: s.read.parquet(paths[name]))
    s.enable_hyperspace()
    q3 = _q3("parquet", paths, _DAY0 + 60)
    # Q3's join without its aggregate: the fused join-aggregate times its
    # join inside ``exec.aggregate``, so only an unfused join opens the
    # ``exec.join`` span that ``join_agg_ms`` also reads.
    join = {key: q3[key] for key in ("source", "filter", "join")}
    with QueryServer(s) as server:
        for spec in (q3, q3, join):  # decoded sides, then HBM-resident ones
            request_query(server.address, spec)


def _hybrid_and_refresh(root: str, orders, lineitem) -> None:
    paths = {name: os.path.join(root, "delta", name)
             for name in ("orders", "lineitem")}
    keys = lineitem.column("l_orderkey").to_numpy()

    def commit(lo: int, hi: int) -> None:
        part = np.arange(lo, hi)
        write_delta(orders.take(part), paths["orders"])
        write_delta(lineitem.take(np.flatnonzero(np.isin(keys, part))),
                    paths["lineitem"])

    n = orders.num_rows
    for lo in range(0, 2000, 500):
        commit(lo, lo + 500)
    s = _session(os.path.join(root, "delta_ix"))
    _indexes(s, lambda name: s.read.delta(paths[name]))
    s.conf.hybrid_scan_enabled = True
    s.enable_hyperspace()
    with QueryServer(s) as server:
        for lo, hi in ((2000, 2200), (2200, n)):  # one commit a request
            commit(lo, hi)
            request_query(server.address, _q3("delta", paths, _DAY0 + 60))
    Hyperspace(s).refresh_index("li_ix", mode="full")


@pytest.fixture(scope="module")
def measured(tmp_path_factory):
    """The three paths run once under a JSONL sink: (sink path, span
    names seen, counter deltas)."""
    root = str(tmp_path_factory.mktemp("measured"))
    path = os.path.join(root, "trace.jsonl")
    orders, lineitem = _tables()
    was_on = trace.tracing_enabled()
    sink = trace.add_sink(trace.JsonlTraceSink(path))
    trace.enable_tracing()
    before = metrics.snapshot()
    try:
        _served_covering(root, orders, lineitem)
        _hybrid_and_refresh(root, orders, lineitem)
    finally:
        after = metrics.snapshot()
        trace.remove_sink(sink)
        if not was_on:
            trace.disable_tracing()
    seen = set()

    def walk(span):
        seen.add(span["name"])
        for child in span.get("children", ()):
            walk(child)

    with open(path, encoding="utf-8") as f:
        for line in f:
            walk(json.loads(line))
    deltas = {name: after.get(name, 0.0) - before.get(name, 0.0)
              for name in COUNTERS}
    return path, seen, deltas


def test_trace_holds_required_spans_and_only_catalogued_ones(measured):
    path, _seen, _deltas = measured
    ctx = lint_engine.build_context(REPO_ROOT)
    _metrics, spans = lint_catalog.telemetry_catalog(ctx)
    assert lint_catalog.check_trace(path, spans) == []


@pytest.mark.parametrize("name", SPANS + tuple(
    f"build.phase.{p}" for p in PHASES))
def test_measured_span_is_emitted(measured, name):
    _path, seen, _deltas = measured
    assert name in seen, sorted(seen)


@pytest.mark.parametrize("name", COUNTERS)
def test_measured_counter_moves(measured, name):
    _path, _seen, deltas = measured
    assert deltas[name] > 0, deltas


def test_required_spans_are_the_measured_ones():
    assert set(lint_catalog.REQUIRED_SPANS) == set(SPANS) | {
        f"build.phase.{p}" for p in PHASES}
