"""Served queries over Delta tables that take commits the index has not
absorbed: Hybrid Scan on the serving path, fresh to the last commit.

The plan cache keys a plan to the source versions it was optimized
against (``sources/snapshots.py``), each answer states the versions it
read (schema metadata ``hyperspace.source_versions``), and a Q3-shaped
join-aggregate over two hybrid sides stays on the fused device path.
"""

from __future__ import annotations

import os

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq
import pytest

from hyperspace_tpu import Hyperspace, HyperspaceSession, IndexConfig, col
from hyperspace_tpu.execution.plan_cache import PlanCache
from hyperspace_tpu.interop import QueryServer
from hyperspace_tpu.interop.server import request_query
from hyperspace_tpu.plan.nodes import BucketUnion
from hyperspace_tpu.sources.delta import DeltaLog, write_delta
from hyperspace_tpu.sources.manager import FileBasedSourceProviderManager
from hyperspace_tpu.sources.snapshots import source_versions_of
from hyperspace_tpu.telemetry import metrics, trace


def _rows(lo, hi):
    ids = np.arange(lo, hi, dtype=np.int64)
    return pa.table({"id": ids, "v": ids * 7 % 1000,
                     "name": pa.array([f"n{i}" for i in ids])})


@pytest.fixture()
def served_delta(tmp_path):
    """A Delta table of four commits, its covering index, hybrid scan on."""
    path = str(tmp_path / "t")
    for part in range(4):
        write_delta(_rows(part * 500, (part + 1) * 500), path)
    s = HyperspaceSession(system_path=str(tmp_path / "ix"))
    s.conf.num_buckets = 4
    Hyperspace(s).create_index(s.read.delta(path),
                               IndexConfig("tix", ["id"], ["v"]))
    s.conf.hybrid_scan_enabled = True
    s.enable_hyperspace()
    return s, os.path.abspath(path)


def _spec(path):
    return {"source": {"format": "delta", "path": path},
            "filter": {"op": ">=", "col": "id", "value": 1990},
            "select": ["id", "v"]}


def _plain_scan(path, version):
    """The spec's answer from the Delta version's files, by pyarrow."""
    files = [f.path for f in DeltaLog(path).snapshot(version).files]
    t = pa.concat_tables([pq.read_table(f, columns=["id", "v"])
                          for f in files])
    return t.filter(pc.greater_equal(t["id"], 1990)).sort_by("id")


def _counter(name):
    return metrics.registry().counter(name)


def test_served_answer_follows_each_commit(served_delta):
    """Each append is seen by the next served query, through the plan
    cache, and the answer states the version it read."""
    s, path = served_delta
    with QueryServer(s) as server:
        for version, rows in ((3, None), (4, (5000, 5002)), (5, (5002, 5003))):
            if rows is not None:
                assert write_delta(_rows(*rows), path) == version
            for _ in range(2):  # the second request is a plan-cache hit
                got = request_query(server.address, _spec(path))
                assert source_versions_of(got) == {path: version}
                assert got.sort_by("id").equals(_plain_scan(path, version))
        plan = s.read.delta(path).filter(col("id") >= 1990) \
            .select("id", "v").optimized_plan()
    assert any(scan.relation.index_scan_of == "tix"
               for scan in plan.leaf_relations())


def test_plan_cache_hits_between_commits_and_drops_once(served_delta):
    s, path = served_delta
    cache = PlanCache(ttl_s=1e9)

    def query():
        return s.read.delta(path).filter(col("id") >= 1990) \
            .select("id", "v").collect(plan_cache=cache)

    query()
    hits, stale = _counter("serve.plan_cache.hits"), \
        _counter("serve.plan_cache.source_stale")
    query()
    assert _counter("serve.plan_cache.hits") == hits + 1
    write_delta(_rows(6000, 6004), path)
    assert query().num_rows == 14
    assert _counter("serve.plan_cache.source_stale") == stale + 1
    assert _counter("serve.plan_cache.hits") == hits + 1
    query()
    assert _counter("serve.plan_cache.hits") == hits + 2
    assert _counter("serve.plan_cache.source_stale") == stale + 1


def test_reused_plan_states_the_version_it_read(served_delta, monkeypatch):
    """A cached hybrid plan served after a commit (a cache blind to
    versions) states the version of the appended files it read, not the
    version resolved for the query."""
    s, path = served_delta
    write_delta(_rows(6000, 6002), path)
    cache = PlanCache(ttl_s=1e9)
    real = PlanCache.get
    monkeypatch.setattr(PlanCache, "get", lambda self, key, versions=None:
                        real(self, key, (self._lru.peek(key) or
                                         (None,) * 4)[3] or versions))

    def query():
        return s.read.delta(path).filter(col("id") >= 1990) \
            .select("id", "v").collect(plan_cache=cache)

    assert source_versions_of(query()) == {path: 4}
    write_delta(_rows(6002, 6003), path)
    got = query()
    assert source_versions_of(got) == {path: 4}
    assert got.sort_by("id").equals(_plain_scan(path, 4))


def test_snapshot_resolved_once_per_query(served_delta):
    """One log listing per table per query; the hybrid branch is traced
    with its files and rows."""
    s, path = served_delta
    write_delta(_rows(7000, 7003), path)
    cache = PlanCache(ttl_s=1e9)
    sink = trace.add_sink(trace.CollectingTraceSink())
    trace.enable_tracing()
    files = _counter("hybrid.appended_files")
    try:
        for _ in range(2):
            sink.spans.clear()
            s.read.delta(path).filter(col("id") >= 1990) \
                .select("id", "v").collect(plan_cache=cache)
            spans = [sp for root in sink.spans for sp in root.walk()]
            listed = [sp for sp in spans if sp.name == "source.snapshot"
                      and "files" not in sp.tags]
            assert [sp.tags["version"] for sp in listed] == [4]
            appended = [sp for sp in spans
                        if sp.name == "exec.hybrid_appended"]
            assert [sp.tags["rows"] for sp in appended] == [3]
    finally:
        trace.disable_tracing()
        trace.remove_sink(sink)
    assert _counter("hybrid.appended_files") == files + 2


def test_parquet_source_adds_no_version_listing(tmp_path, monkeypatch):
    data = str(tmp_path / "p")
    os.makedirs(data)
    pq.write_table(_rows(0, 100), os.path.join(data, "f.parquet"))
    s = HyperspaceSession(system_path=str(tmp_path / "ix"))
    ds = s.read.parquet(data).filter(col("id") < 5).select("id")

    def refused(self, scan):
        raise AssertionError("a parquet scan reached a source provider")

    with monkeypatch.context() as m:
        m.setattr(FileBasedSourceProviderManager, "get_relation", refused)
        assert s.source_provider_manager.source_versions(ds.plan) == {}
    out = ds.collect(plan_cache=PlanCache())
    assert out.num_rows == 5 and source_versions_of(out) == {}
    assert out.schema.metadata is None


def test_q3_shaped_hybrid_join_aggregate_is_fused(tmp_path):
    """Both sides Filter(BucketUnion(index, appended)) go through the
    fused device join and aggregate, and answer as numpy does."""
    rng = np.random.default_rng(3)
    n_orders, per = 2400, 4
    keys = np.arange(n_orders, dtype=np.int64)
    odate = rng.integers(0, 100, n_orders)
    li_key = np.repeat(keys, per)
    shipdate = odate[li_key] + rng.integers(1, 60, li_key.size)
    price = np.round(rng.uniform(1, 1000, li_key.size), 2)
    disc = rng.integers(0, 11, li_key.size) / 100.0
    orders = pa.table({"o_orderkey": keys, "o_orderdate": odate})
    lineitem = pa.table({"l_orderkey": li_key, "l_shipdate": shipdate,
                         "l_extendedprice": price, "l_discount": disc})
    opath, lpath = str(tmp_path / "orders"), str(tmp_path / "lineitem")
    base = 2000  # the rest arrives after the index build
    for part in np.array_split(np.arange(base), 4):
        write_delta(orders.take(part), opath)
        write_delta(lineitem.take(np.flatnonzero(
            np.isin(li_key, part))), lpath)
    s = HyperspaceSession(system_path=str(tmp_path / "ix"))
    s.conf.num_buckets = 4
    for key in ("hyperspace.tpu.deviceFilterMinRows",
                "hyperspace.tpu.deviceJoinMinRows",
                "hyperspace.tpu.deviceAggMinRows",
                "hyperspace.tpu.deviceResidentMinRows"):
        s.conf.set(key, 0)
    hs = Hyperspace(s)
    hs.create_index(s.read.delta(opath),
                    IndexConfig("ord_ix", ["o_orderkey"], ["o_orderdate"]))
    hs.create_index(s.read.delta(lpath),
                    IndexConfig("li_ix", ["l_orderkey"],
                                ["l_shipdate", "l_extendedprice",
                                 "l_discount"]))
    for lo, hi in ((base, 2200), (2200, n_orders)):
        part = np.arange(lo, hi)
        write_delta(orders.take(part), opath)
        write_delta(lineitem.take(np.flatnonzero(np.isin(li_key, part))),
                    lpath)
    s.conf.hybrid_scan_enabled = True
    s.enable_hyperspace()
    day = 50
    ds = (s.read.delta(opath).filter(col("o_orderdate") < day)
          .join(s.read.delta(lpath).filter(col("l_shipdate") > day),
                col("o_orderkey") == col("l_orderkey"))
          .group_by("l_orderkey", "o_orderdate")
          .agg(revenue=(col("l_extendedprice") * (1 - col("l_discount")),
                        "sum"))
          .sort(("revenue", False), "o_orderdate").limit(10))

    def unions(node):
        return (isinstance(node, BucketUnion)) + sum(
            unions(c) for c in node.children)

    assert unions(ds.optimized_plan()) == 2
    got = ds.collect()
    stats = s.last_execution_stats
    assert [j["strategy"] for j in stats["joins"]] == ["device-fused-agg"]
    assert source_versions_of(got) == {os.path.abspath(opath): 5,
                                       os.path.abspath(lpath): 5}

    m = (odate[li_key] < day) & (shipdate > day)
    rev = np.zeros(n_orders)
    np.add.at(rev, li_key[m], price[m] * (1 - disc[m]))
    hit = np.flatnonzero(np.bincount(li_key[m], minlength=n_orders))
    top = hit[np.lexsort((odate[hit], -rev[hit]))][:10]
    assert got["l_orderkey"].to_pylist() == top.tolist()
    assert got["o_orderdate"].to_pylist() == odate[top].tolist()
    np.testing.assert_allclose(got["revenue"].to_numpy(), rev[top],
                               rtol=1e-12)


def _join_count(matched, repeat=3, topn=None):
    """Join 100 keys with ``matched`` of them hit ``repeat`` times each,
    counted per key; (counts, the group slots the kernel reduced into)."""
    from hyperspace_tpu.ops import join_agg

    l_key = np.arange(100, dtype=np.int64)
    hits = np.repeat(np.arange(matched, dtype=np.int64), repeat)
    r_key = np.concatenate([hits, 1000 + np.arange(256 - hits.size)])
    slots = []
    real = join_agg._segment_reduce

    def spy(*args, capacity, **kw):
        slots.append(capacity)
        return real(*args, capacity=capacity, **kw)

    join_agg._segment_reduce = spy
    try:
        counts = join_agg.join_group_aggregate(
            l_key, r_key, [l_key], ["l"], [0], ["count"], [], [],
            topn=topn)[0]
    finally:
        join_agg._segment_reduce = real
    return counts, slots


@pytest.mark.parametrize("matched", [60, 70])
def test_group_count_crossing_a_power_of_two_compiles_nothing(matched):
    """A new snapshot's rows move the group count (here past 64) while
    the match count stays under its power of two: the fused kernel's
    programs are the ones it already has, on the plain path (Q3's, whose
    two sort keys take no fused top-N) and under a fused top-N."""
    from hyperspace_tpu.ops.aggregate import _segment_reduce
    from hyperspace_tpu.ops.join_agg import _topk_groups

    top = (0, False, 5)
    np.testing.assert_array_equal(_join_count(50)[0], np.full(50, 3))
    np.testing.assert_array_equal(_join_count(50, topn=top)[0],
                                  np.full(5, 3))
    programs = (_segment_reduce._cache_size(), _topk_groups._cache_size())
    counts, slots = _join_count(matched)
    np.testing.assert_array_equal(counts, np.full(matched, 3))
    assert slots == [128]  # half the match capacity of 256
    counts, slots = _join_count(matched, topn=top)
    np.testing.assert_array_equal(counts, np.full(5, 3))
    assert slots == [128]
    assert (_segment_reduce._cache_size(),
            _topk_groups._cache_size()) == programs


def test_low_cardinality_aggregate_keeps_group_sized_slots():
    """Few groups over many matches: the slots are padded at most 8
    times past the group count's power of two, not toward the join's
    match capacity, on either path."""
    counts, slots = _join_count(4, repeat=60)
    np.testing.assert_array_equal(counts, np.full(4, 60))
    assert slots == [32]  # 4 groups, 256 matches: 8 x 4, not 128
    counts, slots = _join_count(4, repeat=60, topn=(0, False, 2))
    np.testing.assert_array_equal(counts, np.full(2, 60))
    assert slots == [32]
