"""Fused device join+aggregate pipeline (ops/join_agg.py, round-5
verdict item 1): aggregate(inner equi-join) runs entirely in device
memory — join match, gather, expression evaluation, segment reduce —
with only per-group results returning to host.

Every test gates answers against the pure host path.
"""

from __future__ import annotations

import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from hyperspace_tpu import HyperspaceSession, col
from hyperspace_tpu.execution.device_cache import global_cache


@pytest.fixture()
def env(tmp_path):
    orders = str(tmp_path / "orders")
    lineitem = str(tmp_path / "lineitem")
    os.makedirs(orders)
    os.makedirs(lineitem)
    rng = np.random.default_rng(11)
    n_o, n_l = 5_000, 40_000
    pq.write_table(pa.table({
        "o_orderkey": pa.array(np.arange(n_o, dtype=np.int64)),
        "o_shippriority": pa.array(
            rng.integers(0, 5, n_o).astype(np.int64)),
        "o_totalprice": pa.array(rng.random(n_o) * 100_000),
    }), os.path.join(orders, "p.parquet"))
    pq.write_table(pa.table({
        "l_orderkey": pa.array(
            rng.integers(0, n_o, n_l).astype(np.int64)),
        "l_extendedprice": pa.array(rng.random(n_l) * 1000),
        "l_discount": pa.array(rng.random(n_l) * 0.1),
        "l_quantity": pa.array(
            rng.integers(1, 50, n_l).astype(np.int64)),
    }), os.path.join(lineitem, "p.parquet"))
    s = HyperspaceSession(system_path=str(tmp_path / "ix"))
    global_cache().clear()
    return s, orders, lineitem


def _q3(s, orders, lineitem):
    """Q3 shape: filtered side, indexed join key, expression revenue."""
    return (s.read.parquet(orders)
            .filter(col("o_totalprice") < 50_000.0)
            .join(s.read.parquet(lineitem),
                  col("o_orderkey") == col("l_orderkey"))
            .group_by("o_orderkey", "o_shippriority")
            .agg(revenue=(col("l_extendedprice")
                          * (1 - col("l_discount")), "sum"),
                 n=(col("l_quantity"), "count"),
                 qmax=(col("l_quantity"), "max"),
                 avg_price=(col("l_extendedprice"), "mean"))
            .sort("o_orderkey").collect())


def _host(s, fn, *args):
    s.conf.device_cache_policy = "off"
    try:
        return fn(s, *args)
    finally:
        s.conf.device_cache_policy = "eager"


def _assert_tables_close(a: pa.Table, b: pa.Table):
    assert a.column_names == b.column_names
    assert a.num_rows == b.num_rows
    for name in a.column_names:
        ca, cb = a.column(name), b.column(name)
        if pa.types.is_floating(ca.type):
            np.testing.assert_allclose(
                ca.to_numpy(), cb.to_numpy(), rtol=1e-9)
        else:
            assert ca.to_pylist() == cb.to_pylist(), name


def test_fused_q3_shape_matches_host(env):
    s, orders, lineitem = env
    s.conf.device_cache_policy = "eager"
    s.conf.device_resident_min_rows = 1

    dev = _q3(s, orders, lineitem)
    st = s.last_execution_stats
    assert st["aggregates"][-1]["strategy"] == "device-join-agg"
    assert st["joins"][-1]["strategy"] == "device-fused-agg"
    host = _host(s, _q3, orders, lineitem)
    _assert_tables_close(dev, host)


def test_fused_warm_repeat_is_resident(env):
    s, orders, lineitem = env
    s.conf.device_cache_policy = "eager"
    s.conf.device_resident_min_rows = 1

    first = _q3(s, orders, lineitem)
    assert s.last_execution_stats["aggregates"][-1]["resident"] is False
    second = _q3(s, orders, lineitem)
    st = s.last_execution_stats
    assert st["aggregates"][-1]["strategy"] == "device-join-agg"
    # Warm repeat: every referenced column — including the
    # FILTER-DERIVED orders side — served from HBM, nothing re-shipped.
    assert st["aggregates"][-1]["resident"] is True
    assert st["device_cache"].get("misses", 0) == 0
    _assert_tables_close(first, second)


def test_fused_group_key_from_right_side(env):
    s, orders, lineitem = env
    s.conf.device_cache_policy = "eager"
    s.conf.device_resident_min_rows = 1

    def q(s_, orders_, lineitem_):
        return (s_.read.parquet(orders_)
                .join(s_.read.parquet(lineitem_),
                      col("o_orderkey") == col("l_orderkey"))
                .group_by("l_quantity")
                .agg(total=(col("o_totalprice"), "sum"),
                     n_all=("", "count_all"))
                .sort("l_quantity").collect())

    dev = q(s, orders, lineitem)
    assert s.last_execution_stats["aggregates"][-1]["strategy"] \
        == "device-join-agg"
    host = _host(s, q, orders, lineitem)
    _assert_tables_close(dev, host)


def test_fused_min_max_restore_types(env, tmp_path):
    s, orders, lineitem = env
    s.conf.device_cache_policy = "eager"
    s.conf.device_resident_min_rows = 1

    def q(s_, orders_, lineitem_):
        return (s_.read.parquet(orders_)
                .join(s_.read.parquet(lineitem_),
                      col("o_orderkey") == col("l_orderkey"))
                .group_by("o_shippriority")
                .agg(lo=(col("l_quantity"), "min"),
                     hi=(col("l_quantity"), "max"))
                .sort("o_shippriority").collect())

    dev = q(s, orders, lineitem)
    assert s.last_execution_stats["aggregates"][-1]["strategy"] \
        == "device-join-agg"
    assert dev.schema.field("lo").type == pa.int64()
    host = _host(s, q, orders, lineitem)
    _assert_tables_close(dev, host)


def test_string_group_key_falls_back_correctly(env, tmp_path):
    s, _orders, lineitem = env
    named = str(tmp_path / "named")
    os.makedirs(named)
    pq.write_table(pa.table({
        "o_orderkey": pa.array(np.arange(5_000, dtype=np.int64)),
        "o_clerk": pa.array([f"clerk{i % 7}" for i in range(5_000)]),
    }), os.path.join(named, "p.parquet"))
    s.conf.device_cache_policy = "eager"
    s.conf.device_resident_min_rows = 1

    def q(s_, named_, lineitem_):
        return (s_.read.parquet(named_)
                .join(s_.read.parquet(lineitem_),
                      col("o_orderkey") == col("l_orderkey"))
                .group_by("o_clerk")
                .agg(total=(col("l_quantity"), "sum"))
                .sort("o_clerk").collect())

    dev = q(s, named, lineitem)
    # Ineligible (string key): host aggregation, same answer.
    aggs = s.last_execution_stats.get("aggregates", [])
    assert not aggs or aggs[-1]["strategy"] != "device-join-agg"
    host = _host(s, q, named, lineitem)
    _assert_tables_close(dev, host)


def test_nullable_join_keys_fused_matches_host(env, tmp_path):
    s, _orders, lineitem = env
    nl = str(tmp_path / "orders_nl")
    os.makedirs(nl)
    pq.write_table(pa.table({
        "o_orderkey": pa.array(
            [None if i % 11 == 0 else i for i in range(5_000)],
            type=pa.int64()),
        "o_shippriority": pa.array(
            (np.arange(5_000) % 3).astype(np.int64)),
    }), os.path.join(nl, "p.parquet"))
    s.conf.device_cache_policy = "eager"
    s.conf.device_resident_min_rows = 1

    def q(s_, nl_, lineitem_):
        return (s_.read.parquet(nl_)
                .join(s_.read.parquet(lineitem_),
                      col("o_orderkey") == col("l_orderkey"))
                .group_by("o_shippriority")
                .agg(n=(col("l_quantity"), "count"))
                .sort("o_shippriority").collect())

    dev = q(s, nl, lineitem)
    assert s.last_execution_stats["aggregates"][-1]["strategy"] \
        == "device-join-agg"
    host = _host(s, q, nl, lineitem)
    _assert_tables_close(dev, host)


def test_off_policy_untouched_path(env):
    # With the cache off and conservative thresholds the fused path must
    # not even attempt: regular strategies recorded.
    s, orders, lineitem = env
    s.conf.device_cache_policy = "off"
    _q3(s, orders, lineitem)
    aggs = s.last_execution_stats.get("aggregates", [])
    assert not aggs or aggs[-1]["strategy"] != "device-join-agg"
    joins = s.last_execution_stats.get("joins", [])
    assert joins and joins[-1]["strategy"] != "device-fused-agg"


def test_fused_topn_matches_host(env):
    # ORDER BY revenue DESC LIMIT 10 over the fused join+agg: ranking on
    # device, only the top groups return.
    s, orders, lineitem = env
    s.conf.device_cache_policy = "eager"
    s.conf.device_resident_min_rows = 1

    def q(s_, orders_, lineitem_):
        return (s_.read.parquet(orders_)
                .filter(col("o_totalprice") < 50_000.0)
                .join(s_.read.parquet(lineitem_),
                      col("o_orderkey") == col("l_orderkey"))
                .group_by("o_orderkey", "o_shippriority")
                .agg(revenue=(col("l_extendedprice")
                              * (1 - col("l_discount")), "sum"))
                .sort(("revenue", False)).limit(10).collect())

    dev = q(s, orders, lineitem)
    st = s.last_execution_stats
    assert st["aggregates"][-1]["strategy"] == "device-join-agg"
    assert st["aggregates"][-1]["topn"] == 10
    assert dev.num_rows == 10
    host = _host(s, q, orders, lineitem)
    _assert_tables_close(dev, host)


def test_fused_topn_ascending_and_int_key(env):
    s, orders, lineitem = env
    s.conf.device_cache_policy = "eager"
    s.conf.device_resident_min_rows = 1

    def q(s_, orders_, lineitem_):
        return (s_.read.parquet(orders_)
                .join(s_.read.parquet(lineitem_),
                      col("o_orderkey") == col("l_orderkey"))
                .group_by("o_orderkey")
                .agg(total_qty=(col("l_quantity"), "sum"))
                .sort("total_qty").limit(7).collect())

    dev = q(s, orders, lineitem)
    assert s.last_execution_stats["aggregates"][-1]["topn"] == 7
    host = _host(s, q, orders, lineitem)
    # Ascending int sums can tie: compare the VALUE multiset, which the
    # LIMIT-over-ties contract actually specifies.
    assert sorted(dev.column("total_qty").to_pylist()) \
        == sorted(host.column("total_qty").to_pylist())


def test_fused_topn_by_group_column_not_attempted(env):
    s, orders, lineitem = env
    s.conf.device_cache_policy = "eager"
    s.conf.device_resident_min_rows = 1

    def q(s_, orders_, lineitem_):
        return (s_.read.parquet(orders_)
                .join(s_.read.parquet(lineitem_),
                      col("o_orderkey") == col("l_orderkey"))
                .group_by("o_orderkey")
                .agg(total=(col("l_quantity"), "sum"))
                .sort("o_orderkey").limit(5).collect())

    dev = q(s, orders, lineitem)
    # The fused agg may run, but never with a topn (ordering is by the
    # group key, which the device ranking doesn't cover).
    aggs = s.last_execution_stats.get("aggregates", [])
    assert all(a.get("topn") in (None,) for a in aggs)
    host = _host(s, q, orders, lineitem)
    _assert_tables_close(dev, host)


def test_count_of_division_expr_falls_back(env):
    # count(a/b) can produce nulls (x/0) the fused kernel would miss:
    # it must take the host path and match it exactly.
    s, orders, lineitem = env
    s.conf.device_cache_policy = "eager"
    s.conf.device_resident_min_rows = 1

    def q(s_, orders_, lineitem_):
        return (s_.read.parquet(orders_)
                .join(s_.read.parquet(lineitem_),
                      col("o_orderkey") == col("l_orderkey"))
                .group_by("o_shippriority")
                .agg(n=(col("l_extendedprice") / col("l_discount"),
                        "count"))
                .sort("o_shippriority").collect())

    dev = q(s, orders, lineitem)
    aggs = s.last_execution_stats.get("aggregates", [])
    assert not aggs or aggs[-1]["strategy"] != "device-join-agg"
    host = _host(s, q, orders, lineitem)
    _assert_tables_close(dev, host)


def test_small_join_keeps_normal_path_under_eager(env, tmp_path):
    # Footer pre-gate: tiny inputs can never clear the device threshold,
    # so the sides must not be materialized for a doomed attempt (the
    # normal path, bucketed join included, runs untouched).
    import pyarrow.parquet as pq_

    small = str(tmp_path / "small")
    os.makedirs(small)
    pq_.write_table(pa.table({
        "o_orderkey": pa.array([1, 2, 3], type=pa.int64()),
        "o_shippriority": pa.array([0, 1, 0], type=pa.int64()),
    }), os.path.join(small, "p.parquet"))
    s, _orders, lineitem = env
    s.conf.device_cache_policy = "eager"
    # Calibrated/static thresholds (no override): 3 rows can never win.
    s.conf.device_resident_min_rows = None

    def q():
        return (s.read.parquet(small)
                .join(s.read.parquet(lineitem),
                      col("o_orderkey") == col("l_orderkey"))
                .group_by("o_shippriority")
                .agg(n=(col("l_quantity"), "count"))
                .sort("o_shippriority").collect())

    q()
    aggs = s.last_execution_stats.get("aggregates", [])
    assert not aggs or aggs[-1]["strategy"] != "device-join-agg"


class TestTopkGroups:
    """_topk_groups edge ordering (round-5 advisor #1): int64 extremes
    under ascending order (arithmetic negation overflows) and NaN
    aggregate results (lax.top_k ranks NaN unpredictably)."""

    @staticmethod
    def _topk(col_np, n_valid, k, ascending):
        import jax.numpy as jnp

        from hyperspace_tpu.ops.join_agg import _topk_groups
        from jax import enable_x64 as _x64

        with _x64():
            idx = _topk_groups(jnp.asarray(col_np), n_valid, k=k,
                               ascending=ascending,
                               capacity=len(col_np))
        return sorted(np.asarray(idx).tolist())

    def test_int64_min_ranks_first_ascending(self):
        lo = np.iinfo(np.int64).min
        vals = np.array([5, lo, 7, 0], dtype=np.int64)  # all valid
        # ORDER BY ASC LIMIT 2 -> the min value and 0, NOT the overflow
        # artifact (-lo wraps back to lo, parking the true minimum last).
        assert self._topk(vals, 4, 2, ascending=True) == [1, 3]

    def test_int64_max_ranks_first_descending(self):
        hi = np.iinfo(np.int64).max
        vals = np.array([5, hi, -3, 0], dtype=np.int64)
        assert self._topk(vals, 4, 2, ascending=False) == [0, 1]

    def test_nan_never_selected_over_real_values(self):
        vals = np.array([1.0, np.nan, 3.0, -2.0], dtype=np.float64)
        # Descending top-2: 3.0 then 1.0 — never the NaN slot.
        assert self._topk(vals, 4, 2, ascending=False) == [0, 2]
        # Ascending top-2: -2.0 then 1.0 — negation keeps NaN NaN, so the
        # pre-top_k sentinel mapping must still exclude it.
        assert self._topk(vals, 4, 2, ascending=True) == [0, 3]

    def test_padding_never_beats_valid_groups(self):
        vals = np.array([4, 2, 9, 9], dtype=np.int64)  # slots 2+ = padding
        assert self._topk(vals, 2, 2, ascending=False) == [0, 1]
        assert self._topk(vals, 2, 2, ascending=True) == [0, 1]
