"""HBM-resident index-column cache (execution/device_cache.py).

Round-3 verdict item 2: repeated queries must pay the transfer once —
post-decode device arrays cached by file identity, residency lowering the
routing threshold so the device path fires organically, hits visible in
last_execution_stats, stale entries impossible after file changes.
"""

from __future__ import annotations

import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from hyperspace_tpu import HyperspaceSession, col
from hyperspace_tpu.execution.device_cache import (
    DeviceColumnCache,
    global_cache,
    stat_files,
)


class _FakeArray:
    def __init__(self, nbytes):
        self.nbytes = nbytes


class TestLRU:
    def test_byte_budget_evicts_lru(self):
        c = DeviceColumnCache()
        c.put(("f", "a", "num"), _FakeArray(400), budget_bytes=1000)
        c.put(("f", "b", "num"), _FakeArray(400), budget_bytes=1000)
        assert c.get(("f", "a", "num")) is not None  # a is now most-recent
        c.put(("f", "c", "num"), _FakeArray(400), budget_bytes=1000)
        assert c.get(("f", "b", "num")) is None      # b was LRU -> evicted
        assert c.get(("f", "a", "num")) is not None
        assert c.get(("f", "c", "num")) is not None
        assert c.stats()["evictions"] == 1
        assert c.bytes_cached == 800

    def test_oversize_entry_rejected(self):
        c = DeviceColumnCache()
        c.put(("f", "a", "num"), _FakeArray(2000), budget_bytes=1000)
        assert c.stats()["entries"] == 0

    def test_contains_does_not_skew_hit_stats(self):
        c = DeviceColumnCache()
        c.put(("f", "a", "num"), _FakeArray(10), budget_bytes=100)
        assert c.contains(("f", "a", "num"))
        assert not c.contains(("f", "b", "num"))
        assert c.stats()["hits"] == 0 and c.stats()["misses"] == 0

    def test_side_manifest_lookup_counts_no_hit_or_miss(self):
        """A manifest is a host record: looking it up, found or not,
        leaves the device columns' hit and miss counts alone, and a
        found one is freshened in the LRU."""
        c = DeviceColumnCache()
        c.put_side("fp", pa.table({"k": [1, None]}), budget_bytes=10_000)
        m = c.side_manifest("fp")
        assert (m.num_rows, m.null_counts) == (2, {"k": 1})
        assert c.side_manifest("other") is None
        budget = m.nbytes + 150
        c.put(("f", "a", "num"), _FakeArray(100), budget_bytes=budget)
        assert c.side_manifest("fp") is m
        c.put(("f", "b", "num"), _FakeArray(100), budget_bytes=budget)
        assert c.side_manifest("fp") is m
        assert not c.contains(("f", "a", "num"))
        assert c.stats()["hits"] == 0 and c.stats()["misses"] == 0


class TestFingerprint:
    def test_changes_with_content_identity(self, tmp_path):
        p = tmp_path / "x.parquet"
        p.write_bytes(b"aaaa")
        fp1, size = stat_files([str(p)])
        assert (fp1, size) == (stat_files([str(p)])[0], 4)
        p.write_bytes(b"bbbbbb")  # size + mtime change
        assert stat_files([str(p)])[0] != fp1

    def test_missing_file_yields_none(self, tmp_path):
        p = tmp_path / "x.parquet"
        p.write_bytes(b"aaaa")
        gone = str(tmp_path / "gone.parquet")
        assert stat_files([str(p), gone]) == (None, 4)

    def test_order_is_part_of_identity(self, tmp_path):
        paths = []
        for i, name in enumerate(["a.parquet", "b.parquet"]):
            p = tmp_path / name
            p.write_bytes(b"x" * (i + 1))
            paths.append(str(p))
        fwd, back = stat_files(paths), stat_files(paths[::-1])
        assert fwd[1] == back[1] == 3 and fwd[0] != back[0]
        assert stat_files([])[1] == 0


@pytest.fixture()
def env(tmp_path):
    data = str(tmp_path / "data")
    os.makedirs(data)
    rng = np.random.default_rng(2)
    n = 20_000
    pq.write_table(pa.table({
        "k": pa.array(np.arange(n, dtype=np.int64)),
        "g": pa.array((np.arange(n) % 64).astype(np.int64)),
        "v": pa.array(rng.random(n)),
    }), os.path.join(data, "p.parquet"))
    s = HyperspaceSession(system_path=str(tmp_path / "ix"))
    global_cache().clear()
    return s, data


def test_warm_repeat_filter_fires_resident_device_path(env):
    s, data = env
    s.conf.device_cache_policy = "eager"
    s.conf.device_resident_min_rows = 1

    def q():
        return (s.read.parquet(data).filter(col("k") >= 19_000)
                .collect())

    first = q()
    st1 = s.last_execution_stats
    assert st1["filters"][-1]["strategy"] == "device"
    assert st1["filters"][-1]["resident"] is False  # populated this pass
    assert st1["device_cache"]["misses"] == 1

    second = q()
    st2 = s.last_execution_stats
    assert st2["filters"][-1]["resident"] is True   # organic warm hit
    assert st2["device_cache"]["hits"] == 1
    assert st2["device_cache"].get("misses", 0) == 0
    assert first.equals(second)
    # Answer parity with the pure host path.
    s.conf.device_cache_policy = "off"
    s.conf.device_filter_min_rows = 1 << 60
    host = q()
    assert sorted(host.column("k").to_pylist()) \
        == sorted(second.column("k").to_pylist())


def test_auto_policy_populates_only_when_device_path_runs(env):
    s, data = env
    s.conf.device_cache_policy = "auto"
    s.conf.device_resident_min_rows = 1
    # Host-routed (cold threshold high): nothing cached.
    s.conf.device_filter_min_rows = 1 << 60
    s.read.parquet(data).filter(col("k") >= 100).collect()
    assert "device_cache" not in (s.last_execution_stats or {})
    # Device-routed: populates; the repeat is resident.
    s.conf.device_filter_min_rows = 1
    s.read.parquet(data).filter(col("k") >= 100).collect()
    assert s.last_execution_stats["device_cache"]["misses"] == 1
    # Even with the cold threshold raised back, residency now routes the
    # repeat to the device organically.
    s.conf.device_filter_min_rows = 1 << 60
    s.read.parquet(data).filter(col("k") >= 200).collect()
    st = s.last_execution_stats
    assert st["filters"][-1]["strategy"] == "device"
    assert st["filters"][-1]["resident"] is True
    assert st["device_cache"]["hits"] == 1


def test_warm_repeat_aggregate_resident(env):
    s, data = env
    s.conf.device_cache_policy = "eager"
    s.conf.device_resident_min_rows = 1

    def q():
        return (s.read.parquet(data).group_by("g")
                .agg(total=("v", "sum"), n=("k", "count"))
                .sort("g").collect())

    first = q()
    assert s.last_execution_stats["aggregates"][-1]["resident"] is False
    second = q()
    st = s.last_execution_stats
    assert st["aggregates"][-1]["strategy"] == "device-segment"
    assert st["aggregates"][-1]["resident"] is True
    assert st["device_cache"]["hits"] == 2  # key words + value column
    assert first.column("g").equals(second.column("g"))
    np.testing.assert_allclose(first.column("total").to_numpy(),
                               second.column("total").to_numpy())
    # Parity with the host hash aggregation.
    s.conf.device_cache_policy = "off"
    s.conf.device_agg_min_rows = 1 << 60
    host = q()
    np.testing.assert_allclose(host.column("total").to_numpy(),
                               second.column("total").to_numpy())
    assert host.column("n").equals(second.column("n"))


def test_file_change_invalidates_residency(env):
    s, data = env
    s.conf.device_cache_policy = "eager"
    s.conf.device_resident_min_rows = 1

    def q():
        return s.read.parquet(data).filter(col("k") >= 19_000).count()

    n1 = q()
    assert q() == n1
    assert s.last_execution_stats["filters"][-1]["resident"] is True
    # Append a file: the scan's fingerprint changes, stale arrays cannot
    # serve, and the answer reflects the new data.
    pq.write_table(pa.table({
        "k": pa.array([1_000_000], type=pa.int64()),
        "g": pa.array([0], type=pa.int64()),
        "v": pa.array([0.5]),
    }), os.path.join(data, "p2.parquet"))
    n2 = q()
    assert n2 == n1 + 1
    assert s.last_execution_stats["filters"][-1]["resident"] is False


def test_computed_agg_inputs_never_served_stale(env):
    """Two different expression aggregates over the same files must not
    share a cached hidden column."""
    s, data = env
    s.conf.device_cache_policy = "eager"
    s.conf.device_resident_min_rows = 1

    def q(mult):
        return (s.read.parquet(data).group_by("g")
                .agg(total=(col("v") * mult, "sum"))
                .sort("g").collect())

    a = q(2)
    b = q(4)
    np.testing.assert_allclose(b.column("total").to_numpy(),
                               2 * a.column("total").to_numpy())


def test_cache_off_policy_unchanged_behavior(env):
    s, data = env
    s.conf.device_cache_policy = "off"
    s.conf.device_filter_min_rows = 1
    n = s.read.parquet(data).filter(col("k") >= 100).count()
    assert n == 20_000 - 100
    assert global_cache().stats()["entries"] == 0


def test_eager_policy_ignores_uncacheable_computed_inputs(env):
    """Eager must not lower the routing threshold for an aggregate whose
    expression input can never be cached — that would re-ship the
    computed column every query, never amortizing."""
    s, data = env
    s.conf.device_cache_policy = "eager"
    s.conf.device_resident_min_rows = 1
    (s.read.parquet(data).group_by("g")
     .agg(total=(col("v") * 2, "sum")).collect())
    aggs = (s.last_execution_stats or {}).get("aggregates", [])
    assert not aggs, aggs  # host hash aggregation, no device record


def test_eager_stops_lowering_after_budget_rejection(env):
    """A column too big for the byte budget is rejected once; eager must
    then stop routing repeats through the device ('pay forever' guard)."""
    s, data = env
    s.conf.device_cache_policy = "eager"
    s.conf.device_resident_min_rows = 1
    s.conf.device_cache_bytes = 1024  # smaller than any 20k-row column

    def q():
        return s.read.parquet(data).filter(col("k") >= 19_000).count()

    assert q() == 1000
    st1 = s.last_execution_stats
    assert st1["filters"][-1]["strategy"] == "device"  # first try ships
    assert q() == 1000
    st2 = s.last_execution_stats
    assert st2["filters"][-1]["strategy"] == "host", st2["filters"]


def test_refresh_rebuild_invalidates_index_residency(tmp_path):
    """An index REFRESH writes a new version directory: the query's file
    list (and so the cache fingerprint) changes, resident arrays from
    the old version can never serve, and answers track the new data."""
    from hyperspace_tpu import Hyperspace, IndexConfig

    data = str(tmp_path / "data")
    os.makedirs(data)
    n = 20_000
    pq.write_table(pa.table({
        "k": pa.array(np.arange(n, dtype=np.int64)),
        "v": pa.array(np.arange(n, dtype=np.int64) % 5),
    }), os.path.join(data, "p.parquet"))
    s = HyperspaceSession(system_path=str(tmp_path / "ix"))
    s.conf.num_buckets = 2
    s.conf.device_cache_policy = "eager"
    s.conf.device_resident_min_rows = 1
    hs = Hyperspace(s)
    hs.create_index(s.read.parquet(data), IndexConfig("rix", ["k"], ["v"]))
    s.enable_hyperspace()
    global_cache().clear()

    def q():
        return (s.read.parquet(data).filter(col("k") >= n - 100)
                .select("k", "v").collect())

    assert q().num_rows == 100
    assert q().num_rows == 100  # warm: resident on the index files
    assert s.last_execution_stats["filters"][-1]["resident"] is True
    # Append source data + full refresh -> new v__=1 index files.
    pq.write_table(pa.table({
        "k": pa.array(np.arange(n, n + 50, dtype=np.int64)),
        "v": pa.array(np.zeros(50, dtype=np.int64)),
    }), os.path.join(data, "p2.parquet"))
    hs.refresh_index("rix", mode="full")
    out = q()
    assert out.num_rows == 150  # new rows visible, no stale arrays
    assert s.last_execution_stats["filters"][-1]["resident"] is False


def test_dataset_cache_materializes(tmp_path):
    d = str(tmp_path / "data")
    os.makedirs(d)
    pq.write_table(pa.table({"k": pa.array([1, 2, 3], type=pa.int64())}),
                   os.path.join(d, "p.parquet"))
    s = HyperspaceSession(system_path=str(tmp_path / "ix"))
    cached = s.read.parquet(d).filter(col("k") > 1).cache()
    assert cached.count() == 2
    # Like a cached RDD: later file changes do not affect it.
    pq.write_table(pa.table({"k": pa.array([9], type=pa.int64())}),
                   os.path.join(d, "p2.parquet"))
    assert cached.count() == 2
    assert s.read.parquet(d).filter(col("k") > 1).count() == 3
    assert cached.filter(col("k") == 3).count() == 1


def test_cached_dataset_self_join_uniquifies(tmp_path):
    """A cached Dataset reused on both sides of a join is a DAG; the
    optimizer's uniquify pass must split the shared InMemory leaf into
    distinct node objects (identity-keyed rewrite state must not
    cross-contaminate branches)."""
    d = str(tmp_path / "data")
    os.makedirs(d)
    pq.write_table(pa.table({"k": pa.array([1, 2, 3], type=pa.int64()),
                             "v": pa.array([10, 20, 30], type=pa.int64())}),
                   os.path.join(d, "p.parquet"))
    s = HyperspaceSession(system_path=str(tmp_path / "ix"))
    c = s.read.parquet(d).cache()
    joined = c.join(c, col("k") == col("k"))
    plan = joined.optimized_plan()
    from hyperspace_tpu.plan.nodes import InMemory

    leaves = []

    def walk(p):
        if isinstance(p, InMemory):
            leaves.append(p)
        for ch in p.children:
            walk(ch)

    walk(plan)
    assert len(leaves) == 2
    assert leaves[0] is not leaves[1]
    assert leaves[0].table is leaves[1].table  # data itself stays shared
    assert joined.collect().num_rows == 3


# -- resident device JOIN path (round-5 verdict item 1) ----------------------

@pytest.fixture()
def join_env(tmp_path):
    left_dir = str(tmp_path / "orders")
    right_dir = str(tmp_path / "lineitem")
    os.makedirs(left_dir)
    os.makedirs(right_dir)
    rng = np.random.default_rng(5)
    n_o, n_l = 8_000, 30_000
    pq.write_table(pa.table({
        "o_orderkey": pa.array(np.arange(n_o, dtype=np.int64)),
        "o_totalprice": pa.array(rng.random(n_o) * 100_000),
    }), os.path.join(left_dir, "p.parquet"))
    pq.write_table(pa.table({
        "l_orderkey": pa.array(rng.integers(0, n_o, n_l).astype(np.int64)),
        "l_quantity": pa.array(rng.integers(1, 50, n_l).astype(np.int64)),
    }), os.path.join(right_dir, "p.parquet"))
    s = HyperspaceSession(system_path=str(tmp_path / "ix"))
    global_cache().clear()
    return s, left_dir, right_dir


def _join_q(s, left_dir, right_dir, price_cap=20_000.0):
    return (s.read.parquet(left_dir)
            .filter(col("o_totalprice") < price_cap)
            .join(s.read.parquet(right_dir),
                  col("o_orderkey") == col("l_orderkey"))
            .collect())


def test_warm_repeat_join_fires_resident_device_path(join_env):
    s, left_dir, right_dir = join_env
    s.conf.device_cache_policy = "eager"
    s.conf.device_resident_min_rows = 1

    first = _join_q(s, left_dir, right_dir)
    st1 = s.last_execution_stats
    assert st1["join_kernels"][-1]["strategy"] == "device"
    assert st1["join_kernels"][-1]["resident"] is False  # populating pass

    second = _join_q(s, left_dir, right_dir)
    st2 = s.last_execution_stats
    assert st2["join_kernels"][-1]["strategy"] == "device"
    # The warm repeat is routed by residency: both key columns (one of
    # them FILTER-DERIVED) served from the cache, zero shipped.
    assert st2["join_kernels"][-1]["resident"] is True
    assert st2["device_cache"]["hits"] >= 2
    assert st2["device_cache"].get("misses", 0) == 0
    assert first.num_rows == second.num_rows

    # Answer parity with the pure host join.
    s.conf.device_cache_policy = "off"
    s.conf.device_join_min_rows = 1 << 60
    host = _join_q(s, left_dir, right_dir)
    assert sorted(host.column("l_quantity").to_pylist()) \
        == sorted(second.column("l_quantity").to_pylist())


def test_changed_filter_predicate_never_serves_stale_join(join_env):
    s, left_dir, right_dir = join_env
    s.conf.device_cache_policy = "eager"
    s.conf.device_resident_min_rows = 1

    _join_q(s, left_dir, right_dir, price_cap=20_000.0)
    warm = _join_q(s, left_dir, right_dir, price_cap=20_000.0)
    assert s.last_execution_stats["join_kernels"][-1]["resident"] is True
    # A DIFFERENT predicate produces a different derived identity: the
    # filtered key column must re-ship, never alias the cached rows.
    other = _join_q(s, left_dir, right_dir, price_cap=60_000.0)
    assert s.last_execution_stats["join_kernels"][-1]["resident"] is False
    assert other.num_rows > warm.num_rows
    # Host parity for the new predicate.
    s.conf.device_cache_policy = "off"
    s.conf.device_join_min_rows = 1 << 60
    host = _join_q(s, left_dir, right_dir, price_cap=60_000.0)
    assert host.num_rows == other.num_rows


def test_null_keys_resident_join_matches_host(join_env, tmp_path):
    s, _left, right_dir = join_env
    nl_dir = str(tmp_path / "orders_nulls")
    os.makedirs(nl_dir)
    keys = np.arange(8_000, dtype=np.int64)
    pq.write_table(pa.table({
        "o_orderkey": pa.array(
            [None if i % 7 == 0 else int(k) for i, k in enumerate(keys)],
            type=pa.int64()),
        "o_totalprice": pa.array(np.linspace(0, 100_000, 8_000)),
    }), os.path.join(nl_dir, "p.parquet"))
    s.conf.device_cache_policy = "eager"
    s.conf.device_resident_min_rows = 1

    def q():
        return (s.read.parquet(nl_dir)
                .join(s.read.parquet(right_dir),
                      col("o_orderkey") == col("l_orderkey"))
                .collect())

    first = q()
    second = q()
    assert s.last_execution_stats["join_kernels"][-1]["resident"] is True
    s.conf.device_cache_policy = "off"
    s.conf.device_join_min_rows = 1 << 60
    host = q()
    assert host.num_rows == first.num_rows == second.num_rows


# -- join sides served from HBM without decode -------------------------------

# A group column of each type the fused path admits as a group key.
_GROUP_TYPES = ("g_i32", "g_i64", "g_date", "g_ts", "g_bool")


def _typed_orders(path: str, null_keys: bool = False) -> None:
    n = 8_000
    k = np.arange(n, dtype=np.int64)
    keys = pa.array(k)
    if null_keys:
        keys = pa.array([None if i % 7 == 0 else int(i) for i in k],
                        type=pa.int64())
    os.makedirs(path)
    pq.write_table(pa.table({
        "o_orderkey": keys,
        "o_totalprice": pa.array(np.linspace(0, 100_000, n)),
        "g_i32": pa.array((k % 7 - 3).astype(np.int32)),
        "g_i64": pa.array((k % 11) * 3_000_000_007 - 10**10),
        "g_date": pa.array((k % 13 + 9_000).astype(np.int32)).cast(
            pa.date32()),
        "g_ts": pa.array(k % 5 * 1_000_003 + 1_600_000_000_000_000).cast(
            pa.timestamp("us")),
        "g_bool": pa.array(k % 3 == 0),
    }), os.path.join(path, "p.parquet"))


@pytest.fixture()
def indexed_join_env(join_env, tmp_path):
    """``join_env``'s lineitem and an orders table holding a group column
    of each admitted type, both under covering indexes, so the join rule
    makes each side a filter chain over a bucketed index scan; the
    ``eager`` policy fills the device cache on first use."""
    from hyperspace_tpu import Hyperspace, IndexConfig

    s, _left, right_dir = join_env
    dirs = {"plain": str(tmp_path / "orders_typed"),
            "null_keys": str(tmp_path / "orders_null_keys")}
    _typed_orders(dirs["plain"])
    _typed_orders(dirs["null_keys"], null_keys=True)
    s.conf.num_buckets = 4
    s.conf.device_cache_policy = "eager"
    s.conf.device_resident_min_rows = 1
    hs = Hyperspace(s)
    for name, d in dirs.items():
        hs.create_index(s.read.parquet(d), IndexConfig(
            f"ord_{name}", ["o_orderkey"], ["o_totalprice", *_GROUP_TYPES]))
    hs.create_index(s.read.parquet(right_dir),
                    IndexConfig("li", ["l_orderkey"], ["l_quantity"]))
    s.enable_hyperspace()
    global_cache().clear()
    return s, dirs, right_dir


def _side_q(s, orders_dir, lineitem_dir, group, price_cap=60_000.0):
    return (s.read.parquet(orders_dir)
            .filter(col("o_totalprice") < price_cap)
            .join(s.read.parquet(lineitem_dir),
                  col("o_orderkey") == col("l_orderkey"))
            .group_by(group)
            .agg(q=(col("l_quantity"), "sum"), n=(col("l_quantity"), "count"))
            .collect().sort_by(group))


def _side_host(orders_dir, lineitem_dir, group, price_cap=60_000.0):
    """The same query in plain pyarrow."""
    import pyarrow.compute as pc

    o = pq.read_table(orders_dir)
    o = o.filter(pc.less(o["o_totalprice"], price_cap))
    j = o.join(pq.read_table(lineitem_dir), "o_orderkey", "l_orderkey")
    out = j.group_by(group).aggregate([("l_quantity", "sum"),
                                       ("l_quantity", "count")])
    return out.rename_columns(
        {"l_quantity_sum": "q", "l_quantity_count": "n"}).select(
        [group, "q", "n"]).sort_by(group)


def _side_stats(s):
    st = s.last_execution_stats
    return ({sc["relation"]: sc["decoded"] for sc in st["scans"]},
            st["joins"][-1])


def _sides_counted(before):
    from hyperspace_tpu.telemetry import metrics

    now = metrics.snapshot()
    return tuple(now.get(k, 0.0) - before.get(k, 0.0)
                 for k in ("exec.join_agg.sides",
                           "exec.join_agg.sides_resident"))


def _no_file_reads(monkeypatch):
    from hyperspace_tpu.execution import executor

    def refuse(*_a, **_kw):
        raise AssertionError("a resident side opened a file")

    monkeypatch.setattr(executor, "read_table", refuse)


@pytest.mark.parametrize("group,orders", [
    *[(g, "plain") for g in _GROUP_TYPES], ("g_i64", "null_keys")])
def test_warm_join_sides_served_from_hbm_without_decode(
        indexed_join_env, monkeypatch, group, orders):
    """A warm repeat serves both sides from HBM: no file opened, each
    group key cast back to its exact type, the answer the cold run and
    plain pyarrow give.  With null join keys the side resolves through
    its ``dropnull:`` identity.  The two counters count the sides."""
    from hyperspace_tpu.telemetry import metrics

    s, dirs, lineitem = indexed_join_env
    before = metrics.snapshot()
    cold = _side_q(s, dirs[orders], lineitem, group)
    assert _sides_counted(before) == (2, 0)
    decoded, join = _side_stats(s)
    assert decoded == {f"ord_{orders}": True, "li": True}
    assert join["strategy"] == "device-fused-agg"
    assert join["resident_sides"] == 0

    _no_file_reads(monkeypatch)
    before = metrics.snapshot()
    warm = _side_q(s, dirs[orders], lineitem, group)
    assert _sides_counted(before) == (2, 2)
    assert metrics.snapshot().get("io.files.read", 0) \
        == before.get("io.files.read", 0)
    decoded, join = _side_stats(s)
    assert decoded == {f"ord_{orders}": False, "li": False}
    assert join["resident_sides"] == 2
    assert warm.schema.field(group).type == cold.schema.field(group).type
    assert warm.equals(cold)
    assert warm.equals(_side_host(dirs[orders], lineitem, group))


def test_rewritten_index_file_is_decoded_not_served(indexed_join_env):
    """A rewritten index file (new size and mtime) changes the side's
    identity: the side is decoded, and answers from the new file."""
    s, dirs, lineitem = indexed_join_env
    _side_q(s, dirs["plain"], lineitem, "g_i32")
    entry = s.index_collection_manager.get_index("li")
    path = max((f.name for f in entry.content.file_infos()),
               key=os.path.getsize)
    table = pq.read_table(path)
    pq.write_table(table, path, compression="none")
    warm = _side_q(s, dirs["plain"], lineitem, "g_i32")
    decoded, join = _side_stats(s)
    assert decoded == {"ord_plain": False, "li": True}
    assert join["resident_sides"] == 1
    assert warm.equals(_side_host(dirs["plain"], lineitem, "g_i32"))


@pytest.mark.parametrize("mesh", [False, True])
def test_changed_predicate_decodes_its_side_only(indexed_join_env, mesh):
    """A new predicate is a new identity: that side is decoded while the
    other, unchanged, is served from HBM, and the answer is right.  Where
    the join then runs sharded over the mesh, which takes host arrays,
    the resident side is decoded too."""
    s, dirs, lineitem = indexed_join_env
    _side_q(s, dirs["plain"], lineitem, "g_date", price_cap=20_000.0)
    if mesh:
        s.conf.mesh_join_min_rows = 1
    other = _side_q(s, dirs["plain"], lineitem, "g_date",
                    price_cap=70_000.0)
    decoded, join = _side_stats(s)
    assert decoded == {"ord_plain": True, "li": mesh}
    assert join["strategy"] == ("mesh-fused-agg" if mesh
                                else "device-fused-agg")
    assert join["resident_sides"] == (0 if mesh else 1)
    assert other.equals(_side_host(dirs["plain"], lineitem, "g_date",
                                   price_cap=70_000.0))


def test_eviction_between_check_and_use_decodes(indexed_join_env,
                                                monkeypatch):
    """A column evicted after the presence probe and before it is taken
    leaves its side to decode, and the answer stays right."""
    s, dirs, lineitem = indexed_join_env
    _side_q(s, dirs["plain"], lineitem, "g_bool")
    cache = global_cache()
    real_get = cache.get

    def get_after_eviction(key):
        if key[1:] == ("l_quantity", "num"):
            cache.pop(key)
        return real_get(key)

    monkeypatch.setattr(cache, "get", get_after_eviction)
    warm = _side_q(s, dirs["plain"], lineitem, "g_bool")
    decoded, join = _side_stats(s)
    assert decoded == {"ord_plain": False, "li": True}
    assert join["resident_sides"] == 1
    assert warm.equals(_side_host(dirs["plain"], lineitem, "g_bool"))


def test_warm_repeat_window_aggregate_resident(env):
    """Whole-partition window aggregates route through the segment
    kernel over resident columns (round-5: windows' device story)."""
    s, data = env
    s.conf.device_cache_policy = "eager"
    s.conf.device_resident_min_rows = 1

    def q():
        return (s.read.parquet(data)
                .with_window("total", "sum", partition_by=["g"],
                             value="v")
                .with_window("n", "count", partition_by=["g"])
                .sort("k").collect())

    first = q()
    st1 = s.last_execution_stats
    # BOTH windows route device-side (identity propagates through the
    # first window's output to the chained count window).
    assert len(st1["windows"]) == 2
    assert all(w["strategy"] == "device-segment"
               for w in st1["windows"])
    assert st1["windows"][0]["resident"] is False
    second = q()
    st2 = s.last_execution_stats
    assert len(st2["windows"]) == 2
    assert all(w["resident"] for w in st2["windows"])
    assert first.column("total").equals(second.column("total"))
    # Parity with the pure host window engine.
    s.conf.device_cache_policy = "off"
    s.conf.device_agg_min_rows = 1 << 60
    host = q()
    assert "windows" not in (s.last_execution_stats or {})
    np.testing.assert_allclose(host.column("total").to_numpy(),
                               second.column("total").to_numpy())
    assert host.column("n").equals(second.column("n"))


def test_device_window_ineligible_shapes_stay_host(env):
    s, data = env
    s.conf.device_cache_policy = "eager"
    s.conf.device_resident_min_rows = 1
    # ORDER BY (running frame) -> host engine, answers still right.
    out = (s.read.parquet(data)
           .with_window("rs", "sum", partition_by=["g"],
                        order_by=["k"], value="v")
           .collect())
    st = s.last_execution_stats
    assert "windows" not in (st or {})
    assert out.num_rows == 20_000


def test_device_count_star_window_matches_host(env):
    s, data = env
    s.conf.device_cache_policy = "eager"
    s.conf.device_resident_min_rows = 1

    def q():
        return (s.read.parquet(data)
                .with_window("n", "count", partition_by=["g"])
                .sort("k").collect())

    dev = q()
    st = s.last_execution_stats
    assert st["windows"][-1]["strategy"] == "device-segment"
    s.conf.device_cache_policy = "off"
    s.conf.device_agg_min_rows = 1 << 60
    host = q()
    assert "windows" not in (s.last_execution_stats or {})
    assert host.column("n").equals(dev.column("n"))
    assert dev.schema.field("n").type == pa.int64()
