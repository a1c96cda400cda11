#!/usr/bin/env python
"""Chip smoke: the main path of hyperspace_tpu on one TPU chip, checked.

Drives what a user of the engine calls, once, at TPC-H scale factor 1:

  1. generates TPC-H ``LINEITEM`` (6,001,215 rows, 16 columns) and
     ``ORDERS`` (1,500,000 rows, 9 columns) from ``--seed`` with the
     spec's value domains (TPC-H v3 §1.4, §4.2.3, §4.2.5), written as
     64 parquet files per table;
  2. builds two covering indexes through ``Hyperspace.create_index``
     (200 buckets each);
  3. runs four queries with hyperspace enabled through
     ``HyperspaceSession`` — a point filter on ``l_orderkey``, a range
     filter on ``l_shipdate``, the ``orders ⋈ lineitem`` equi-join and
     a TPC-H Q3-shaped join, group-by and top-10 — and the same four
     as JSON specs through ``interop/server.py``'s ``QueryServer`` over
     loopback, in this one process;
  4. compares every answer with one computed from the parquet files by
     plain pyarrow/numpy, independently of ``hyperspace_tpu``.

The routing thresholds are set to 0 in the smoke's session, so every
operator takes its device formulation; the executor's own stats must
then say so.  Timings printed on the way are one-off bring-up
observations, not benchmark numbers.  The last line of stdout is the
driver's contract: ``{"ok": true, "device": {...}}``.  With no TPU
backend the script exits non-zero and prints no such line.

``--four-chips`` runs only the mesh path across 4 devices: the SF1
lineitem build with ``parallelBuild=on`` and the mesh on, plus the join
and Q3 through the mesh paths, compared with the same run with the mesh
off (per-bucket sha256 of the index files, and the answers).

Only this process touches JAX; it starts no child process.
"""

from __future__ import annotations

import argparse
import datetime
import hashlib
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional, Tuple

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq

# TPC-H v3 §4.2.3: STARTDATE, CURRENTDATE, ENDDATE.
_EPOCH = datetime.date(1970, 1, 1)
START_DAY = (datetime.date(1992, 1, 1) - _EPOCH).days
CURRENT_DAY = (datetime.date(1995, 6, 17) - _EPOCH).days
END_DAY = (datetime.date(1998, 12, 31) - _EPOCH).days
SF1_LINEITEM_ROWS = 6_001_215
SF1_ORDERS_ROWS = 1_500_000
FILES_PER_TABLE = 64
NUM_BUCKETS = 200
# Q3's substitution defaults (TPC-H v3 §2.4.3.4: DATE = 1995-03-15).
Q3_DATE = datetime.date(1995, 3, 15)
RANGE_LO = datetime.date(1995, 3, 1)
RANGE_HI = datetime.date(1995, 4, 1)

LI_INDEX = "li_orderkey"
ORD_INDEX = "ord_orderkey"
LI_INCLUDED = ["l_extendedprice", "l_discount", "l_shipdate", "l_quantity"]
ORD_INCLUDED = ["o_orderdate", "o_shippriority", "o_custkey"]
QUERY_NAMES = ("point_filter", "range_filter", "join", "q3")
# The thresholds that route each operator between host and device.
DEVICE_THRESHOLD_KEYS = (
    "hyperspace.tpu.deviceFilterMinRows",
    "hyperspace.tpu.deviceJoinMinRows",
    "hyperspace.tpu.deviceAggMinRows",
    "hyperspace.tpu.deviceBuildMinRows",
    "hyperspace.tpu.deviceResidentMinRows",
)


class SmokeFailure(Exception):
    """A phase produced a wrong answer or left the device path."""


def log(kind: str, **fields) -> None:
    """One labelled JSON line on stdout (never the contract's last line)."""
    print(json.dumps({kind: fields}, default=str), flush=True)


# ---------------------------------------------------------------------------
# Device
# ---------------------------------------------------------------------------
def require_tpu() -> Dict[str, object]:
    """The device as JAX reports it; raises SmokeFailure with no TPU."""
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        raise SmokeFailure(
            f"no TPU backend: JAX found {backend!r} "
            f"(devices {jax.devices()}); the chip smoke runs on a TPU only")
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


class CompileCounter:
    """Programs compiled or loaded from the persistent compile cache, the
    seconds that took, and the cache's lookups, hits and writes, from
    JAX's own monitoring events."""

    _EVENTS = {"/jax/compilation_cache/compile_requests_use_cache":
               "persistent_cache_lookups",
               "/jax/compilation_cache/cache_hits": "persistent_cache_hits",
               # JAX records a "miss" when it writes the new entry.
               "/jax/compilation_cache/cache_misses":
               "persistent_cache_writes"}

    def __init__(self) -> None:
        import jax

        self.programs = 0
        self.seconds = 0.0
        self.counts = dict.fromkeys(self._EVENTS.values(), 0)
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += secs

    def _event(self, event: str, **_kw) -> None:
        if event in self._EVENTS:
            self.counts[self._EVENTS[event]] += 1

    def snapshot(self) -> Dict[str, object]:
        import jax

        return {"programs": self.programs,
                "compile_or_load_s": self.seconds, **self.counts,
                "cache_dir": jax.config.jax_compilation_cache_dir}


# ---------------------------------------------------------------------------
# Data: TPC-H LINEITEM and ORDERS from a seed
# ---------------------------------------------------------------------------
_WORDS = ("furiously", "quickly", "carefully", "blithely", "slyly",
          "express", "regular", "final", "ironic", "pending", "special",
          "bold", "even", "unusual", "silent", "requests", "deposits",
          "accounts", "packages", "instructions", "theodolites", "pinto",
          "beans", "foxes", "ideas", "dependencies", "platelets", "asymptotes",
          "courts", "dolphins", "multipliers", "sauternes", "warthogs",
          "frets", "dinos", "attainments", "somas", "nag", "sleep", "wake",
          "haggle", "cajole", "boost", "detect", "integrate", "use", "among",
          "across", "above", "after", "along", "about", "against")


def _text_pool(rng: np.random.Generator, size: int, lo: int, hi: int
               ) -> pa.Array:
    """``size`` random word strings of length in [lo, hi] (§4.2.2.10's
    text domain, drawn from a fixed vocabulary)."""
    out = []
    for target in rng.integers(lo, hi + 1, size):
        words, n = [], 0
        while n < target:
            w = _WORDS[int(rng.integers(len(_WORDS)))]
            words.append(w)
            n += len(w) + 1
        out.append(" ".join(words)[:target].rstrip() or "a")
    return pa.array(out, type=pa.string())


def _pick(pool: List[str], idx: np.ndarray) -> pa.Array:
    return pa.array(pool, type=pa.string()).take(pa.array(idx))


def _lines_per_order(rng: np.random.Generator, n_orders: int,
                     target: Optional[int]) -> np.ndarray:
    """1..7 lineitems per order (§4.2.5.1), nudged to sum to ``target``."""
    counts = rng.integers(1, 8, n_orders)
    if target is None:
        return counts
    if not n_orders <= target <= 7 * n_orders:
        raise ValueError(f"{target} lineitems cannot fit {n_orders} orders")
    diff = target - int(counts.sum())
    while diff:
        step = 1 if diff > 0 else -1
        free = np.flatnonzero(counts < 7 if step > 0 else counts > 1)
        pick = rng.choice(free, size=min(abs(diff), free.size),
                          replace=False)
        counts[pick] += step
        diff -= step * pick.size
    return counts


def generate_tpch(root: str, seed: int, sf: float = 1.0,
                  files: int = FILES_PER_TABLE) -> Dict[str, object]:
    """Write ``<root>/lineitem`` and ``<root>/orders`` as parquet; return
    their paths and row counts.  At ``sf=1`` the row counts are the
    spec's SF1 cardinalities exactly."""
    rng = np.random.default_rng(seed)
    n_orders = max(1, int(round(SF1_ORDERS_ROWS * sf)))
    target = SF1_LINEITEM_ROWS if sf == 1.0 else None
    counts = _lines_per_order(rng, n_orders, target)
    n_li = int(counts.sum())
    n_cust = max(3, int(150_000 * sf))
    n_part = max(1, int(200_000 * sf))
    n_supp = max(4, int(10_000 * sf))

    # ORDERS (§4.2.5.1): sparse keys — 8 of every 32 are used.
    i = np.arange(n_orders, dtype=np.int64)
    o_orderkey = (i // 8) * 32 + (i % 8) + 1
    o_custkey = rng.integers(1, n_cust + 1, n_orders)
    o_custkey = np.where(o_custkey % 3 == 0,        # no custkey % 3 == 0
                         np.maximum(o_custkey - 1, 1), o_custkey)
    o_orderdate = rng.integers(START_DAY, END_DAY - 151 + 1, n_orders)

    # LINEITEM, grouped by order as dbgen emits it.
    order_ix = np.repeat(i, counts)
    starts = np.cumsum(counts) - counts
    l_linenumber = (np.arange(n_li) - starts[order_ix] + 1).astype(np.int32)
    l_partkey = rng.integers(1, n_part + 1, n_li)
    supp_i = rng.integers(0, 4, n_li)
    l_suppkey = (l_partkey + supp_i * (n_supp // 4 + (l_partkey - 1)
                                       // n_supp)) % n_supp + 1
    retail = (90000 + (l_partkey // 10) % 20001
              + 100 * (l_partkey % 1000)) / 100.0
    l_quantity = rng.integers(1, 51, n_li).astype(np.float64)
    l_extendedprice = np.round(l_quantity * retail, 2)
    l_discount = rng.integers(0, 11, n_li) / 100.0
    l_tax = rng.integers(0, 9, n_li) / 100.0
    odate = o_orderdate[order_ix]
    l_shipdate = odate + rng.integers(1, 122, n_li)
    l_commitdate = odate + rng.integers(30, 91, n_li)
    l_receiptdate = l_shipdate + rng.integers(1, 31, n_li)
    shipped = l_shipdate <= CURRENT_DAY
    l_linestatus = np.where(shipped, 1, 0)              # 1 = "F", 0 = "O"
    returned = l_receiptdate <= CURRENT_DAY
    l_returnflag = np.where(returned, rng.integers(0, 2, n_li), 2)

    f_lines = np.add.reduceat(shipped.astype(np.int64), starts)
    o_orderstatus = np.where(f_lines == counts, 0,
                             np.where(f_lines == 0, 1, 2))  # F / O / P
    charge = l_extendedprice * (1 + l_tax) * (1 - l_discount)
    o_totalprice = np.round(np.add.reduceat(charge, starts), 2)

    date = pa.date32()
    lineitem = pa.table({
        "l_orderkey": pa.array(o_orderkey[order_ix], pa.int64()),
        "l_partkey": pa.array(l_partkey, pa.int64()),
        "l_suppkey": pa.array(l_suppkey, pa.int64()),
        "l_linenumber": pa.array(l_linenumber, pa.int32()),
        "l_quantity": pa.array(l_quantity, pa.float64()),
        "l_extendedprice": pa.array(l_extendedprice, pa.float64()),
        "l_discount": pa.array(l_discount, pa.float64()),
        "l_tax": pa.array(l_tax, pa.float64()),
        "l_returnflag": _pick(["R", "A", "N"], l_returnflag),
        "l_linestatus": _pick(["O", "F"], l_linestatus),
        "l_shipdate": pa.array(l_shipdate.astype(np.int32), date),
        "l_commitdate": pa.array(l_commitdate.astype(np.int32), date),
        "l_receiptdate": pa.array(l_receiptdate.astype(np.int32), date),
        "l_shipinstruct": _pick(
            ["DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN"],
            rng.integers(0, 4, n_li)),
        "l_shipmode": _pick(
            ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"],
            rng.integers(0, 7, n_li)),
        "l_comment": _text_pool(rng, 4096, 10, 43).take(
            pa.array(rng.integers(0, 4096, n_li))),
    })
    n_clerk = max(1, int(1000 * sf))
    orders = pa.table({
        "o_orderkey": pa.array(o_orderkey, pa.int64()),
        "o_custkey": pa.array(o_custkey, pa.int64()),
        "o_orderstatus": _pick(["F", "O", "P"], o_orderstatus),
        "o_totalprice": pa.array(o_totalprice, pa.float64()),
        "o_orderdate": pa.array(o_orderdate.astype(np.int32), date),
        "o_orderpriority": _pick(
            ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"],
            rng.integers(0, 5, n_orders)),
        "o_clerk": _pick([f"Clerk#{k:09d}" for k in range(1, n_clerk + 1)],
                         rng.integers(0, n_clerk, n_orders)),
        "o_shippriority": pa.array(np.zeros(n_orders, np.int32), pa.int32()),
        "o_comment": _text_pool(rng, 4096, 19, 78).take(
            pa.array(rng.integers(0, 4096, n_orders))),
    })
    dirs = {}
    for name, table in (("lineitem", lineitem), ("orders", orders)):
        d = os.path.join(root, name)
        os.makedirs(d, exist_ok=True)
        bounds = np.linspace(0, table.num_rows, files + 1).astype(np.int64)
        for f in range(files):
            pq.write_table(table.slice(bounds[f], bounds[f + 1] - bounds[f]),
                           os.path.join(d, f"part-{f:05d}.parquet"))
        dirs[name] = d
    return {"lineitem": dirs["lineitem"], "orders": dirs["orders"],
            "lineitem_rows": n_li, "orders_rows": n_orders,
            # A real key in the middle of the table for the point filter.
            "point_key": int(o_orderkey[n_orders // 2])}


# ---------------------------------------------------------------------------
# Engine: session, indexes, queries
# ---------------------------------------------------------------------------
def make_session(system_path: str, num_buckets: int = NUM_BUCKETS):
    """A session with the indexes' system path and every routing
    threshold pinned to 0 (an explicit conf value wins over the
    calibrated one, config.py ``device_min_rows``)."""
    from hyperspace_tpu import Hyperspace, HyperspaceSession

    session = HyperspaceSession(system_path=system_path)
    session.conf.num_buckets = num_buckets
    for key in DEVICE_THRESHOLD_KEYS:
        session.conf.set(key, 0)
    return session, Hyperspace(session)


def build_indexes(session, hs, data: Dict[str, object]
                  ) -> Dict[str, object]:
    """Both covering indexes through ``Hyperspace.create_index``; returns
    per-index wall seconds and the build report's device seconds."""
    from hyperspace_tpu import IndexConfig

    specs = [(LI_INDEX, data["lineitem"], "l_orderkey", LI_INCLUDED),
             (ORD_INDEX, data["orders"], "o_orderkey", ORD_INCLUDED)]
    out = {}
    for name, path, key, included in specs:
        t0 = time.perf_counter()
        hs.create_index(session.read.parquet(path),
                        IndexConfig(name, [key], included))
        wall = time.perf_counter() - t0
        report = hs.last_build_report()
        # The monolithic build's ``kernel`` phase, or the streaming
        # build's per-device route-kernel attribution.
        device_s = float(report.device_s) + sum(
            report.device_kernel_ms.values()) / 1000.0
        out[name] = {"wall_s": wall, "device_kernel_s": device_s,
                     "phases_s": dict(report.to_dict().get("phases_s", {}))}
    return out


def _date_lit(d: datetime.date) -> Dict[str, object]:
    return {"op": "cast", "child": {"value": d.isoformat()}, "type": "date"}


def query_specs(data: Dict[str, object]) -> Dict[str, Dict[str, object]]:
    """The four queries as the server's JSON specs (interop/query.py)."""
    li = {"format": "parquet", "path": data["lineitem"]}
    orders = {"format": "parquet", "path": data["orders"]}
    on = {"op": "==", "col": "o_orderkey", "right_col": "l_orderkey"}
    return {
        "point_filter": {
            "source": li,
            "filter": {"op": "==", "col": "l_orderkey",
                       "value": data["point_key"]},
            "select": ["l_orderkey"] + LI_INCLUDED},
        "range_filter": {
            "source": li,
            "filter": {"op": "and",
                       "left": {"op": ">=", "left": {"col": "l_shipdate"},
                                "right": _date_lit(RANGE_LO)},
                       "right": {"op": "<", "left": {"col": "l_shipdate"},
                                 "right": _date_lit(RANGE_HI)}},
            "select": ["l_orderkey", "l_shipdate", "l_extendedprice",
                       "l_quantity"]},
        "join": {
            "source": orders,
            "join": {"source": li, "on": on},
            "select": ["o_orderkey", "o_orderdate", "o_custkey",
                       "l_extendedprice", "l_discount", "l_quantity"]},
        "q3": {
            "source": orders,
            "filter": {"op": "<", "left": {"col": "o_orderdate"},
                       "right": _date_lit(Q3_DATE)},
            "join": {"source": li, "on": on,
                     "filter": {"op": ">", "left": {"col": "l_shipdate"},
                                "right": _date_lit(Q3_DATE)}},
            "group_by": ["l_orderkey", "o_orderdate", "o_shippriority"],
            "aggs": {"revenue": [
                {"op": "*", "left": {"col": "l_extendedprice"},
                 "right": {"op": "-", "left": {"value": 1},
                           "right": {"col": "l_discount"}}}, "sum"]},
            "sort": [["revenue", False], "o_orderdate"],
            "limit": 10,
            "select": ["l_orderkey", "revenue", "o_orderdate",
                       "o_shippriority"]},
    }


def session_queries(session, data: Dict[str, object]):
    """The same four queries through the Dataset API (name -> thunk)."""
    from hyperspace_tpu import col

    def li():
        return session.read.parquet(data["lineitem"])

    def orders():
        return session.read.parquet(data["orders"])

    def q3():
        return (orders().filter(col("o_orderdate") < Q3_DATE)
                .join(li().filter(col("l_shipdate") > Q3_DATE),
                      col("o_orderkey") == col("l_orderkey"))
                .group_by("l_orderkey", "o_orderdate", "o_shippriority")
                .agg(revenue=(col("l_extendedprice")
                              * (1 - col("l_discount")), "sum"))
                .sort(("revenue", False), "o_orderdate").limit(10)
                .select("l_orderkey", "revenue", "o_orderdate",
                        "o_shippriority"))

    return {
        "point_filter": lambda: li().filter(
            col("l_orderkey") == data["point_key"]).select(
                "l_orderkey", *LI_INCLUDED),
        "range_filter": lambda: li().filter(
            (col("l_shipdate") >= RANGE_LO)
            & (col("l_shipdate") < RANGE_HI)).select(
                "l_orderkey", "l_shipdate", "l_extendedprice", "l_quantity"),
        "join": lambda: orders().join(
            li(), col("o_orderkey") == col("l_orderkey")).select(
                "o_orderkey", "o_orderdate", "o_custkey", "l_extendedprice",
                "l_discount", "l_quantity"),
        "q3": q3,
    }


def run_in_session(session, data, names=QUERY_NAMES
                   ) -> Tuple[Dict[str, pa.Table], Dict[str, dict]]:
    """Each query collected in-session: (answers, per-query seconds and
    the executor's stats)."""
    thunks = session_queries(session, data)
    answers, info = {}, {}
    for name in names:
        t0 = time.perf_counter()
        answers[name] = thunks[name]().collect()
        info[name] = {"seconds": time.perf_counter() - t0,
                      "stats": session.last_execution_stats or {}}
    return answers, info


def run_through_server(session, data, names=QUERY_NAMES
                       ) -> Tuple[Dict[str, pa.Table], Dict[str, float]]:
    """Each query as a JSON spec through QueryServer/QueryClient over
    loopback, in this process."""
    from hyperspace_tpu.interop.server import QueryClient, QueryServer

    specs = query_specs(data)
    answers, seconds = {}, {}
    with QueryServer(session) as server:
        with QueryClient(server.address, timeout_s=1800) as client:
            for name in names:
                t0 = time.perf_counter()
                answers[name] = client.query(specs[name])
                seconds[name] = time.perf_counter() - t0
    return answers, seconds


# ---------------------------------------------------------------------------
# Reference: plain pyarrow/numpy over the parquet files
# ---------------------------------------------------------------------------
def reference_answers(data: Dict[str, object], names=QUERY_NAMES
                      ) -> Dict[str, pa.Table]:
    li_cols = ["l_orderkey"] + LI_INCLUDED
    li = pq.read_table(data["lineitem"], columns=li_cols)
    orders = pq.read_table(data["orders"],
                           columns=["o_orderkey"] + ORD_INCLUDED)
    out = {}
    if "point_filter" in names:
        out["point_filter"] = li.filter(
            pc.equal(li["l_orderkey"], data["point_key"]))
    if "range_filter" in names:
        sd = li["l_shipdate"]
        out["range_filter"] = li.filter(pc.and_(
            pc.greater_equal(sd, pa.scalar(RANGE_LO, pa.date32())),
            pc.less(sd, pa.scalar(RANGE_HI, pa.date32())))).select(
                ["l_orderkey", "l_shipdate", "l_extendedprice",
                 "l_quantity"])
    if "join" in names:
        out["join"] = _np_join(orders, li).select(
            ["o_orderkey", "o_orderdate", "o_custkey", "l_extendedprice",
             "l_discount", "l_quantity"])
    if "q3" in names:
        o = orders.filter(pc.less(orders["o_orderdate"],
                                  pa.scalar(Q3_DATE, pa.date32())))
        lf = li.filter(pc.greater(li["l_shipdate"],
                                  pa.scalar(Q3_DATE, pa.date32())))
        j = _np_join(o, lf)
        rev = (j["l_extendedprice"].to_numpy()
               * (1 - j["l_discount"].to_numpy()))
        keys = j["l_orderkey"].to_numpy()
        uniq, inv = np.unique(keys, return_inverse=True)
        revenue = np.bincount(inv, weights=rev, minlength=uniq.size)
        first = np.zeros(uniq.size, np.int64)
        first[inv[::-1]] = np.arange(keys.size)[::-1]
        odate = j["o_orderdate"].take(pa.array(first))
        # ORDER BY revenue DESC, o_orderdate; LIMIT 10.
        order = np.lexsort((odate.cast(pa.int32()).to_numpy(), -revenue))
        top = order[:10]
        out["q3"] = pa.table({
            "l_orderkey": pa.array(uniq[top], pa.int64()),
            "revenue": pa.array(revenue[top], pa.float64()),
            "o_orderdate": odate.take(pa.array(top)),
            "o_shippriority": j["o_shippriority"].take(
                pa.array(first[top])),
        })
    return out


def _np_join(orders: pa.Table, li: pa.Table) -> pa.Table:
    """Inner equi-join on orderkey by numpy sort + searchsorted (orders
    keys are unique, as the spec makes them)."""
    ok = orders["o_orderkey"].to_numpy()
    lk = li["l_orderkey"].to_numpy()
    order = np.argsort(ok, kind="stable")
    pos = np.searchsorted(ok[order], lk)
    pos = np.minimum(pos, ok.size - 1)
    hit = ok[order][pos] == lk
    o_rows = order[pos[hit]]
    l_rows = np.flatnonzero(hit)
    cols = {c: orders[c].take(pa.array(o_rows)) for c in orders.column_names}
    cols.update({c: li[c].take(pa.array(l_rows)) for c in li.column_names})
    return pa.table(cols)


def _canonical(table: pa.Table, ordered: bool) -> pa.Table:
    table = table.combine_chunks()
    if ordered:
        return table
    keys = [(c, "ascending") for c in table.column_names]
    return table.take(pc.sort_indices(table, sort_keys=keys))


def compare(name: str, got: pa.Table, want: pa.Table,
            rel_tol: float = 1e-9) -> None:
    """Raise SmokeFailure unless ``got`` equals ``want``: same columns and
    rows; integer, date and string values exactly, float values to
    ``rel_tol`` (sums are reassociated on the device, so the last bits
    of a float64 sum may differ)."""
    if got.column_names != want.column_names:
        raise SmokeFailure(f"{name}: columns {got.column_names} != "
                           f"{want.column_names}")
    if got.num_rows != want.num_rows:
        raise SmokeFailure(f"{name}: {got.num_rows} rows, reference has "
                           f"{want.num_rows}")
    ordered = name == "q3"
    g, w = _canonical(got, ordered), _canonical(want, ordered)
    for c in want.column_names:
        gc, wc = g[c], w[c]
        if pa.types.is_floating(wc.type):
            a = gc.to_numpy(zero_copy_only=False)
            b = wc.to_numpy(zero_copy_only=False)
            if not np.allclose(a, b, rtol=rel_tol, atol=0.0):
                bad = int(np.flatnonzero(~np.isclose(a, b, rtol=rel_tol,
                                                     atol=0.0))[0])
                raise SmokeFailure(f"{name}.{c} row {bad}: {a[bad]!r} != "
                                   f"{b[bad]!r}")
        elif not gc.cast(wc.type).equals(wc):
            raise SmokeFailure(f"{name}.{c}: values differ from reference")


# ---------------------------------------------------------------------------
# Device-path assertions
# ---------------------------------------------------------------------------
def device_strategies(info: Dict[str, dict]) -> Dict[str, List[str]]:
    """Strategies the executor recorded, by operator, over all queries."""
    out = {"filter": [], "join": [], "aggregate": [], "device_cache_hits": 0}
    for entry in info.values():
        st = entry["stats"]
        out["filter"] += [f.get("strategy") for f in st.get("filters", [])]
        out["join"] += [j.get("strategy")
                        for j in st.get("join_kernels", [])]
        out["join"] += [j.get("strategy") for j in st.get("joins", [])
                        if "fused" in str(j.get("strategy"))]
        out["aggregate"] += [a.get("strategy")
                             for a in st.get("aggregates", [])]
        out["device_cache_hits"] += int(
            (st.get("device_cache") or {}).get("hits", 0))
    return out


def assert_device_path(strategies: Dict[str, object],
                       builds: Dict[str, object],
                       q3_repeat_hits: int) -> None:
    for op in ("filter", "join", "aggregate"):
        got = strategies[op]
        if not got or not all(str(s).startswith("device") for s in got):
            raise SmokeFailure(f"{op}: strategies {got}, expected device")
    for name, b in builds.items():
        if not b["device_kernel_s"] > 0:
            raise SmokeFailure(f"build {name}: device kernel seconds "
                               f"{b['device_kernel_s']}")
    if not q3_repeat_hits > 0:
        raise SmokeFailure("repeated q3 was not served from HBM-resident "
                           "columns (0 device-cache hits)")
    from hyperspace_tpu.ops.hash import use_pallas
    from hyperspace_tpu.ops.pallas_kernels import _interpret

    if not use_pallas() or _interpret():
        raise SmokeFailure(f"pallas: use_pallas()={use_pallas()}, "
                           f"_interpret()={_interpret()}")


def peak_device_bytes() -> List[Optional[int]]:
    import jax

    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()]


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------
def one_chip(root: str, seed: int, sf: float = 1.0,
             files: int = FILES_PER_TABLE, num_buckets: int = NUM_BUCKETS,
             check_device: bool = True) -> Dict[str, object]:
    """Generate, build, query in-session and through the server, compare
    with the reference; with ``check_device`` also hold the run to the
    device path.  Returns the bring-up observations."""
    from hyperspace_tpu.utils import calibrate

    t0 = time.perf_counter()
    data = generate_tpch(os.path.join(root, "data"), seed, sf, files)
    log("phase", name="generate", seconds=time.perf_counter() - t0,
        lineitem_rows=data["lineitem_rows"], orders_rows=data["orders_rows"])
    # What the cost model chooses on its own, before the smoke's override.
    log("bring_up_observation", calibration=calibrate.profile_summary())

    session, hs = make_session(os.path.join(root, "indexes"),
                               num_buckets=num_buckets)
    builds = build_indexes(session, hs, data)
    log("bring_up_observation", builds=builds)

    t0 = time.perf_counter()
    want = reference_answers(data)
    log("phase", name="reference", seconds=time.perf_counter() - t0,
        rows={k: v.num_rows for k, v in want.items()})

    session.enable_hyperspace()
    got, info = run_in_session(session, data)
    for name in QUERY_NAMES:
        compare(name, got[name], want[name])
    log("phase", name="in_session", ok=True,
        seconds={k: v["seconds"] for k, v in info.items()},
        rows={k: v.num_rows for k, v in got.items()})
    strategies = device_strategies(info)

    # Repeat Q3: its columns were cached in HBM by the first run.
    q3_again, q3_info = run_in_session(session, data, names=("q3",))
    compare("q3", q3_again["q3"], want["q3"])
    q3_hits = device_strategies(q3_info)["device_cache_hits"]

    served, seconds = run_through_server(session, data)
    for name in QUERY_NAMES:
        compare(name, served[name], want[name])
    log("phase", name="query_server", ok=True, seconds=seconds)

    log("device_path", strategies=strategies,
        q3_repeat_device_cache_hits=q3_hits,
        q3_repeat_seconds=q3_info["q3"]["seconds"])
    if check_device:
        assert_device_path(strategies, builds, q3_hits)
        from hyperspace_tpu.ops.hash import use_pallas
        from hyperspace_tpu.ops.pallas_kernels import _interpret

        log("device_path", use_pallas=use_pallas(), interpret=_interpret(),
            peak_bytes_in_use=peak_device_bytes())
    return {"data": data, "builds": builds, "strategies": strategies}


def _bucket_digests(session, index_name: str) -> Dict[int, List[str]]:
    """sha256 of each of the index's files, by bucket."""
    from hyperspace_tpu.io.parquet import bucket_id_of_file

    entry = session.index_collection_manager.get_index(index_name)
    out: Dict[int, List[str]] = {}
    for f in entry.content.file_infos():
        with open(f.name, "rb") as fh:
            out.setdefault(bucket_id_of_file(f.name), []).append(
                hashlib.sha256(fh.read()).hexdigest())
    return {b: sorted(d) for b, d in out.items()}


def four_chips(root: str, seed: int, sf: float = 1.0,
               files: int = FILES_PER_TABLE, num_buckets: int = NUM_BUCKETS,
               check_device: bool = True) -> Dict[str, object]:
    """The mesh path across the visible devices against the same run with
    the mesh off: index bytes per bucket, then the join and Q3 answers.
    Each check logs as soon as it passes, cheapest first, so a run cut
    by its time limit still says how far the mesh path got."""
    import jax

    n_dev = len(jax.devices())
    if check_device and n_dev != 4:
        raise SmokeFailure(f"--four-chips needs 4 devices, JAX sees {n_dev}")
    data = generate_tpch(os.path.join(root, "data"), seed, sf, files)
    sessions, digests = {}, {}
    for mode in ("off", "on"):
        session, hs = make_session(os.path.join(root, f"indexes-{mode}"),
                                   num_buckets=num_buckets)
        session.conf.mesh_enabled = mode
        session.conf.parallel_build = "on" if mode == "on" else "off"
        if mode == "on":
            session.conf.mesh_join_min_rows = 1
            session.conf.mesh_agg_min_rows = 1
            session.conf.mesh_filter_min_rows = 1
        builds = build_indexes(session, hs, data)
        digests[mode] = {name: _bucket_digests(session, name)
                         for name in (LI_INDEX, ORD_INDEX)}
        session.enable_hyperspace()
        sessions[mode] = session
        log("phase", name=f"build_mesh_{mode}", builds=builds)
    for name, d_off in digests["off"].items():
        d_on = digests["on"][name]
        diff = sorted((b for b in set(d_off) | set(d_on)
                       if d_off.get(b) != d_on.get(b)), key=str)
        if diff:
            raise SmokeFailure(f"{name}: mesh build differs from the "
                               f"one-device build in buckets {diff[:10]}")
    log("phase", name="build_digests_equal",
        buckets={k: len(v) for k, v in digests["on"].items()})
    want = reference_answers(data, names=("join", "q3"))
    answers = {}
    for mode in ("on", "off"):
        got, info = run_in_session(sessions[mode], data,
                                   names=("join", "q3"))
        strategies = {
            "joins": [j.get("strategy") for e in info.values()
                      for j in e["stats"].get("joins", [])],
            "aggregates": [a.get("strategy") for e in info.values()
                           for a in e["stats"].get("aggregates", [])]}
        if mode == "on" and check_device and not all(
                "mesh" in str(s) for s in strategies["joins"]):
            raise SmokeFailure(f"mesh on: joins took {strategies}")
        for name in ("join", "q3"):
            compare(name, got[name], want[name])
        answers[mode] = got
        log("phase", name=f"queries_mesh_{mode}", ok=True,
            seconds={k: v["seconds"] for k, v in info.items()},
            strategies=strategies)
    for name in ("join", "q3"):
        compare(name, answers["on"][name], answers["off"][name])
    peaks = peak_device_bytes()
    log("device_path", per_device_peak_bytes_in_use=peaks)
    if check_device and not all(p and p > 0 for p in peaks):
        raise SmokeFailure(f"not every device held bytes: {peaks}")
    return {"buckets": {k: len(v) for k, v in digests["on"].items()},
            "peaks": peaks}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--seed", type=int, default=20260415)
    parser.add_argument("--four-chips", action="store_true",
                        help="run only the 4-device mesh path and its "
                             "one-device comparison")
    args = parser.parse_args(argv)
    try:
        device = require_tpu()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 2
    from hyperspace_tpu.utils.xla_cache import ensure_persistent_xla_cache

    # Before the first compile, or JAX leaves the persistent cache off.
    ensure_persistent_xla_cache()
    counter = CompileCounter()
    root = tempfile.mkdtemp(prefix="chip_smoke_")
    started = time.perf_counter()
    try:
        if args.four_chips:
            four_chips(root, args.seed)
        else:
            one_chip(root, args.seed)
    except Exception as e:  # noqa: BLE001 — any failed phase fails the run
        import traceback

        traceback.print_exc()
        print(f"chip_smoke: FAILED: {type(e).__name__}: {e}",
              file=sys.stderr, flush=True)
        return 1
    finally:
        log("bring_up_observation", compiles=counter.snapshot(),
            total_s=time.perf_counter() - started)
        shutil.rmtree(root, ignore_errors=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
