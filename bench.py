#!/usr/bin/env python
"""Headline benchmark: covering-index query acceleration, indexed vs full scan.

SF1-class TPC-H-shaped dataset (6M-row wide lineitem in 64 files, 1.5M-row
orders), mirroring `BASELINE.json` configs 2-5 plus the north-star query
shapes from BASELINE.md:

  - FilterIndexRule point lookup on lineitem(l_orderkey): the index path
    reads 1/numBuckets of the files (bucket pruning,
    FilterIndexRule.scala:62-68 analog) and only the covered columns.
  - JoinIndexRule orders ⋈ lineitem on orderkey: both sides rewritten to
    bucketed, column-pruned index scans (JoinIndexRule.scala:36-50 analog).
  - TPC-H Q3- and Q10-shaped queries: group-by over the indexed join with
    sum(l_extendedprice * (1 - l_discount)), sort + limit.
  - Hybrid Scan over a Delta table with appended files (BASELINE config 4).
  - Z-order two-column covering index, range query on the SECOND dimension
    (BASELINE config 5's Z-order shape).

The baseline is the same engine with hyperspace disabled (full scan), per
BASELINE.md: the reference publishes no numbers, so the baseline is
self-measured.  Every workload runs REPEATS times per mode; the headline
ratio uses MEDIANS and the detail records min/median/max so round-over-round
deltas are distinguishable from noise.

UN-LOSABLE BY CONSTRUCTION (the round-5 lesson: rc=124 erased a full run):
the bench runs as a sequence of SECTIONS under a global wall-clock budget.
Each completed section is checkpointed immediately — one JSON object
appended to ``HS_BENCH_RESULTS`` (default ``bench_results.jsonl``) and one
compact progress line streamed to stdout — so no later failure can erase
finished numbers.  Per-section runtime is capped by a soft deadline checked
between timing reps plus a hard ``signal.alarm`` guard; on budget
exhaustion, a section timeout, or SIGTERM the bench FINALIZES: remaining
sections are marked ``{"skipped": "<reason>"}`` and the headline JSON — the
same shape as BENCH_r04.json — still prints as the last stdout line, with
exit code 0.  Only a correctness-gate failure (indexed answer diverging
from the full scan) aborts without a headline: a wrong-answer bench must
fail loudly, and its completed sections are still in the results file.

HEADLINE RESILIENCE (the round-6 hardening: r05's rc=124 still lost the
headline because the driver's SIGKILL landed before the unwind reached
finalize): the SIGTERM handler now finalizes IN THE HANDLER — marks
everything unfinished, prints the headline computed from whatever
sections checkpointed (a partial sf1 geomean is labeled as such), and
``os._exit(0)``s — so the kill-grace window only needs to cover one
JSON print, not a Python unwind through C extensions.  And because a
SIGKILL can still land first, ``bench.py --finalize-from <results.jsonl>``
reconstructs and prints the headline post-hoc from the checkpoint file
alone (no jax, no re-run).

REGRESSION WATCHDOG: ``bench.py --compare <baseline.jsonl|auto>`` runs
the bench, then diffs the produced checkpoint file against a baseline
(a prior results JSONL, a headline-shaped BENCH_r0N artifact, or
``auto`` = the previous run's rotated ``<results>.prev``), prints a
per-metric report with a per-phase build attribution table for any
regressed build section, and exits 3 on regression — the CI compare
lane's contract.  ``--compare-only CURRENT`` diffs without running.
Thresholds: ``--compare-threshold-pct`` (default 25) and
``--compare-min-abs-s`` (default 0.5).  Logic in
hyperspace_tpu/telemetry/bench_compare.py.

Environment knobs:
  HS_BENCH_BUDGET       global wall-clock budget, seconds (default 6300)
  HS_BENCH_SECTION_CAP  per-section runtime cap, seconds (default 0 =
                        bounded by the remaining global budget only)
  HS_BENCH_RESULTS      per-section checkpoint file (JSONL; default
                        bench_results.jsonl, "" disables).  An existing
                        file rotates to <path>.prev at startup — the
                        ``--compare auto`` baseline.
  HS_BENCH_TRACE        span-trace sink (JSONL, one root span per bench
                        section / traced query; default
                        <HS_BENCH_RESULTS>.trace.jsonl, "" disables)
  HS_BENCH_LINEITEM / HS_BENCH_ORDERS / HS_BENCH_FILES / HS_BENCH_REPS
                        SF1 scale overrides (resilience tests shrink them)
  HS_BENCH_SF10 / HS_BENCH_SF100 / HS_BENCH_SF10_BUDGET /
  HS_BENCH_SF100_BUDGET / HS_BENCH_SF10_REPS   scale-step gates (as before)
  HS_BENCH_SF10_LINEITEM / HS_BENCH_SF10_ORDERS / HS_BENCH_SF10_FILES
                        SF10 scale overrides (resilience tests shrink the
                        sf10 build to exercise the kill-with-headline path)
"""

from __future__ import annotations

import json
import math
import os
import shutil
import signal
import sys
import tempfile
import time
from typing import Callable, Dict, Optional

N_ORDERS = int(os.environ.get("HS_BENCH_ORDERS", 1_500_000))
N_LINEITEM = int(os.environ.get("HS_BENCH_LINEITEM", 6_000_000))
N_FILES = int(os.environ.get("HS_BENCH_FILES", 64))
NUM_BUCKETS = 16
REPEATS = int(os.environ.get("HS_BENCH_REPS", 5))

def _enclosing_timeout_s() -> Optional[float]:
    """The wall-clock limit of the environment this bench runs UNDER,
    when discoverable: an explicit env hint
    (``HS_BENCH_TIMEOUT_S``), else a coreutils ``timeout`` ancestor's
    duration argument parsed from /proc.  BENCH_r05 died rc=124 with
    ``parsed: null`` because the default budget (6300 s) sat ABOVE the
    driver's wall — the external SIGKILL landed before the alarm-driven
    finalize ever armed.  Deriving the default from the enclosing
    timeout makes the in-process finalize fire first, whatever the
    driver chose."""
    raw = os.environ.get("HS_BENCH_TIMEOUT_S", "")
    if raw:
        try:
            return float(raw)
        except ValueError:
            pass
    pid = os.getppid()
    for _ in range(6):  # a few wrapper layers (sh -c, tee, env) at most
        if pid <= 1:
            return None
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                argv = [a.decode("utf-8", "replace")
                        for a in f.read().split(b"\0") if a]
            with open(f"/proc/{pid}/stat", "r", encoding="ascii",
                      errors="replace") as f:
                stat = f.read()
        except OSError:
            return None
        d = _timeout_duration_from_argv(argv)
        if d is not None:
            return d
        # ppid is the 4th field, counted after the parenthesized comm
        # (which may itself contain spaces).
        try:
            pid = int(stat.rpartition(")")[2].split()[1])
        except (ValueError, IndexError):
            return None
    return None


def _timeout_duration_from_argv(argv) -> Optional[float]:
    """The DURATION argument of a coreutils ``timeout`` command line, in
    seconds (suffixes s/m/h/d honored), or None."""
    if not argv or os.path.basename(argv[0]) != "timeout":
        return None
    takes_value = {"-k", "--kill-after", "-s", "--signal"}
    i = 1
    while i < len(argv):
        arg = argv[i]
        if arg in takes_value:
            i += 2
            continue
        if arg.startswith("--") or (arg.startswith("-") and len(arg) > 1
                                    and not arg[1].isdigit()):
            i += 1
            continue
        scale = {"s": 1.0, "m": 60.0, "h": 3600.0, "d": 86400.0}.get(
            arg[-1:], None)
        num = arg[:-1] if scale is not None else arg
        try:
            return float(num) * (scale if scale is not None else 1.0)
        except ValueError:
            return None
    return None


def _default_budget_s() -> float:
    """HS_BENCH_BUDGET when set; else derived from the enclosing timeout
    (minus finalize headroom: 10% of the limit, at least 30 s); else the
    historical 6300 s default.  0 disables."""
    raw = os.environ.get("HS_BENCH_BUDGET")
    if raw is not None:
        return float(raw)
    limit = _enclosing_timeout_s()
    if limit is not None and limit > 0:
        return max(30.0, limit - max(30.0, 0.1 * limit))
    return 6300.0


# Global wall-clock budget: comfortably under the driver's timeout so the
# bench finalizes ITSELF (r04's full run fit well inside this; r05 died at
# the driver's wall instead and lost everything — the default now derives
# from the enclosing `timeout` when HS_BENCH_BUDGET is unset).  0 disables.
BUDGET_S = _default_budget_s()
SECTION_CAP_S = float(os.environ.get("HS_BENCH_SECTION_CAP", "0"))
RESULTS_PATH = os.environ.get("HS_BENCH_RESULTS", "bench_results.jsonl")
TRACE_PATH = os.environ.get(
    "HS_BENCH_TRACE", (RESULTS_PATH + ".trace.jsonl") if RESULTS_PATH else "")

# Soft deadline for the CURRENT section (monotonic seconds): the timing
# helpers stop launching new reps once it passes, so a section winds down
# at a rep boundary instead of hitting the hard alarm mid-measurement.
_SOFT_DEADLINE: Optional[float] = None


def _deadline_passed() -> bool:
    return _SOFT_DEADLINE is not None and time.monotonic() > _SOFT_DEADLINE


def _gen_lineitem(rng, n: int) -> dict:
    """Wide lineitem rows (TPC-H has 16 columns): column pruning must
    matter.  Shared by the base tables and the hybrid-join appended file so
    their schemas cannot diverge."""
    import numpy as np

    li = {
        "l_orderkey": rng.integers(0, N_ORDERS, n),
        # Low-cardinality status column (l_linestatus analog): the
        # resident-aggregation workload groups by it so the device result
        # pull stays tiny.
        "l_status": rng.integers(0, 4, n),
        "l_quantity": rng.integers(1, 50, n).astype(np.float64),
        "l_extendedprice": rng.random(n) * 1e4,
        "l_discount": rng.random(n) * 0.1,
        # Time-correlated column (monotone across the dataset, so each file
        # covers a disjoint date range — the layout data skipping exploits).
        "l_shipdate": np.arange(n, dtype=np.int64),
    }
    for i in range(10):
        li[f"l_pad{i}"] = rng.random(n)
    return li


def _gen_data(root: str):
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    rng = np.random.default_rng(7)
    orders_dir = os.path.join(root, "orders")
    lineitem_dir = os.path.join(root, "lineitem")
    os.makedirs(orders_dir)
    os.makedirs(lineitem_dir)

    o_key = np.arange(N_ORDERS, dtype=np.int64)
    rng.shuffle(o_key)
    orders = {
        "o_orderkey": o_key,
        "o_custkey": rng.integers(0, 20_000, N_ORDERS),
        "o_totalprice": rng.random(N_ORDERS) * 1e5,
        "o_shippriority": rng.integers(0, 5, N_ORDERS),
    }
    li = _gen_lineitem(rng, N_LINEITEM)

    for name, data, out in (("orders", orders, orders_dir),
                            ("lineitem", li, lineitem_dir)):
        table = pa.table(data)
        n = table.num_rows
        step = -(-n // N_FILES)
        for f in range(N_FILES):
            part = table.slice(f * step, step)
            pq.write_table(part, os.path.join(out, f"part-{f:05d}.parquet"))
    return orders_dir, lineitem_dir


def _time(fn, repeats: int = REPEATS) -> dict:
    """{'median': s, 'min': s, 'max': s, 'reps': n} over timed runs.
    Stops early (>=1 rep kept) once the section's soft deadline passes."""
    import statistics

    times = []
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
        if _deadline_passed():
            break
    return {"median": statistics.median(times), "min": min(times),
            "max": max(times), "reps": len(times)}


def _kernel_microbench() -> dict:
    """Device-RESIDENT kernel throughput vs the host mirrors, transfer
    excluded: inputs are placed once with ``jax.device_put`` and timings
    cover kernel execution only (``block_until_ready``; outputs stay on
    device).  The two-phase kernels' intrinsic scalar sync (match count /
    group count) is included — it is part of the kernel design, not of
    column shipping.  This shows what the chip does once data is
    resident, independent of the host–device link."""
    import jax
    import numpy as np
    import pyarrow as pa

    from hyperspace_tpu.io import columnar
    from hyperspace_tpu.ops.aggregate import _group_sort, _segment_reduce
    from hyperspace_tpu.ops.hash import use_pallas
    from hyperspace_tpu.ops.join import (
        _join_pairs,
        _join_ranges,
        sorted_equi_join_np,
    )
    from hyperspace_tpu.ops.sort import (
        _bucket_sort_impl,
        bucket_sort_permutation_np,
    )
    from jax import enable_x64 as _enable_x64
    from hyperspace_tpu.utils.shapes import round_up_pow2

    n = 1 << 20
    rng = np.random.default_rng(5)
    keys = rng.integers(0, n, n).astype(np.int64)
    arr = pa.array(keys)
    words = np.asarray(columnar.to_hash_words(arr))
    order = np.asarray(columnar.to_order_words(arr))
    dev = jax.devices()[0]
    out = {"rows": n,
           "note": "device timings are warm, inputs device-resident, "
                   "outputs left on device (block_until_ready); host "
                   "mirrors run the same formulation in numpy/arrow"}

    def rate(stats):
        return round(n / max(stats["median"], 1e-9) / 1e6, 2)

    def stat(d):
        return {k: (round(v, 5) if isinstance(v, float) else v)
                for k, v in d.items()}

    # -- build hash+lexsort (ops/sort.py) --------------------------------
    w_d = jax.device_put(words, dev)
    o_d = jax.device_put(order, dev)
    pallas = use_pallas()

    def dev_build():
        _bucket_sort_impl((w_d,), (o_d,), n, 16, pallas).block_until_ready()

    dev_build()  # compile + warm
    b_dev = _time(dev_build, repeats=3)
    b_host = _time(lambda: bucket_sort_permutation_np([words], [order], 16),
                   repeats=3)
    out["build_hash_sort"] = {
        "device_s": stat(b_dev), "host_s": stat(b_host),
        "device_mrows_per_s": rate(b_dev), "host_mrows_per_s": rate(b_host),
        "resident_speedup": round(b_host["median"] / b_dev["median"], 3)}

    # -- sorted equi-join (ops/join.py) ----------------------------------
    lk = keys.astype(np.int32)
    rk = rng.permutation(keys).astype(np.int32)
    lk_d = jax.device_put(lk, dev)
    rk_d = jax.device_put(rk, dev)

    def dev_join():
        r_perm, lo, hi, total = _join_ranges(lk_d, rk_d, n, n)
        total = int(total)  # intrinsic scalar sync
        jax.block_until_ready(
            _join_pairs(r_perm, lo, hi, round_up_pow2(total)))

    dev_join()
    j_dev = _time(dev_join, repeats=3)
    j_host = _time(lambda: sorted_equi_join_np(lk, rk), repeats=3)
    out["sorted_join"] = {
        "device_s": stat(j_dev), "host_s": stat(j_host),
        "device_mrows_per_s": rate(j_dev), "host_mrows_per_s": rate(j_host),
        "resident_speedup": round(j_host["median"] / j_dev["median"], 3)}

    # -- segment aggregate (ops/aggregate.py) ----------------------------
    gk = (keys % 4096).astype(np.int64)
    kw = np.asarray(columnar.to_order_words(pa.array(gk)))
    vals = rng.random(n)
    tbl = pa.table({"k": gk, "v": vals})
    kw_d = jax.device_put(kw, dev)
    with _enable_x64():
        v_d = jax.device_put(vals, dev)

        def dev_agg():
            perm, boundaries, n_groups = _group_sort((kw_d,), n)
            g = int(n_groups)  # intrinsic scalar sync
            res = _segment_reduce(perm, boundaries, n, (v_d,),
                                  ops=("sum",), capacity=round_up_pow2(g))
            jax.block_until_ready(res)

        dev_agg()
        a_dev = _time(dev_agg, repeats=3)
    a_host = _time(
        lambda: tbl.group_by("k").aggregate([("v", "sum")]), repeats=3)
    out["segment_aggregate"] = {
        "device_s": stat(a_dev), "host_s": stat(a_host),
        "device_mrows_per_s": rate(a_dev), "host_mrows_per_s": rate(a_host),
        "resident_speedup": round(a_host["median"] / a_dev["median"], 3)}
    return out


N_ORDERS_SF10 = int(os.environ.get("HS_BENCH_SF10_ORDERS", 15_000_000))
N_LINEITEM_SF10 = int(os.environ.get("HS_BENCH_SF10_LINEITEM", 60_000_000))
SF10_FILES = int(os.environ.get("HS_BENCH_SF10_FILES", 64))
# Target reps (round-5 verdict: enough reps for <=±15% spreads); any
# workload whose first rep exceeds SF10_SLOW_REP_S adapts down to 2 reps
# with the actual count recorded — a 5x repeat of a multi-minute full
# scan would starve the SF100 step of its time budget.
SF10_REPEATS = int(os.environ.get("HS_BENCH_SF10_REPS", "5"))
SF10_SLOW_REP_S = 90.0

# SF100 (BASELINE.json north star: covering-index build seconds and
# Q3/Q10 wall-clock at SF100).  600M-row lineitem / 150M-row orders
# through the streaming spill build; queries repeat on the INDEXED path
# (>=3 reps) — full-scan baselines at this scale are measured once, for
# the point filter only (a 5-rep 25 GB scan per workload would say
# nothing new and cost the whole budget).
N_ORDERS_SF100 = 150_000_000
N_LINEITEM_SF100 = 600_000_000
SF100_FILES = 200
SF100_REPEATS = 3
SF100_TIME_BUDGET_S = float(os.environ.get("HS_BENCH_SF100_BUDGET",
                                           "6000"))
SF100_MIN_DISK_GB = 60.0
# The SF10 section self-skips when the SF1 portion already consumed this
# much wall-clock (a slow SF1 portion must not kill the whole bench).
SF10_TIME_BUDGET_S = float(os.environ.get("HS_BENCH_SF10_BUDGET", "2400"))


def _peak_rss_mb() -> float:
    import resource

    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def _time_adaptive(fn, target_reps: int, slow_s: float = SF10_SLOW_REP_S
                   ) -> dict:
    """Like _time, but if the FIRST rep is slow the remaining reps drop
    to one more (2 total) so multi-minute full scans don't burn the
    whole budget; the actual rep count and spread are recorded.  The
    section soft deadline also stops further reps."""
    import statistics

    times = []
    t0 = time.perf_counter()
    fn()
    times.append(time.perf_counter() - t0)
    reps = target_reps if times[0] <= slow_s else min(target_reps, 2)
    for _ in range(reps - 1):
        if _deadline_passed():
            break
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    med = statistics.median(times)
    return {"median": med, "min": min(times), "max": max(times),
            "reps": len(times),
            "spread_pct": round(100.0 * (max(times) - min(times))
                                / max(med, 1e-9), 1)}


def _sf10_section(session, hs, root: str, tables_equal) -> dict:
    """SF10-scale credibility step: a 60M-row, 15-column (TPC-H-width)
    lineitem through the streaming spill build, then the headline query
    shapes — filter, DS range, Q3/Q10, join-only, Z-order second-dim —
    with the same answer-equality gates as SF1.  Generation and reads
    are per-file so peak memory stays bounded; the spill build's peak
    RSS is recorded."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    from hyperspace_tpu import DataSkippingIndexConfig, IndexConfig, col

    out: dict = {"lineitem_rows": N_LINEITEM_SF10,
                 "orders_rows": N_ORDERS_SF10,
                 "files_per_table": SF10_FILES,
                 "lineitem_columns": 15,
                 "target_reps": SF10_REPEATS}
    li_dir = os.path.join(root, "sf10_lineitem")
    ord_dir = os.path.join(root, "sf10_orders")
    os.makedirs(li_dir)
    os.makedirs(ord_dir)

    t0 = time.perf_counter()
    rng = np.random.default_rng(17)
    per_li = -(-N_LINEITEM_SF10 // SF10_FILES)
    per_ord = -(-N_ORDERS_SF10 // SF10_FILES)
    for f in range(SF10_FILES):
        n = min(per_li, N_LINEITEM_SF10 - f * per_li)
        base = f * per_li
        cols = {
            "l_orderkey": rng.integers(0, N_ORDERS_SF10, n),
            "l_quantity": rng.integers(1, 50, n).astype(np.float64),
            "l_extendedprice": rng.random(n) * 1e4,
            "l_discount": rng.random(n) * 0.1,
            # Monotone across the dataset: per-file sketch ranges stay
            # narrow, like any time-correlated ingest.
            "l_shipdate": np.arange(base, base + n, dtype=np.int64),
            "l_status": rng.integers(0, 4, n),
        }
        # Pad out to TPC-H lineitem's 15-16 column width so column
        # pruning and scan costs are like-for-like with the SF1 table.
        for i in range(9):
            cols[f"l_pad{i}"] = rng.random(n)
        pq.write_table(pa.table(cols),
                       os.path.join(li_dir, f"part-{f:05d}.parquet"))
        n_o = min(per_ord, N_ORDERS_SF10 - f * per_ord)
        pq.write_table(pa.table({
            "o_orderkey": np.arange(f * per_ord, f * per_ord + n_o,
                                    dtype=np.int64),
            "o_custkey": rng.integers(0, 200_000, n_o),
            "o_totalprice": rng.random(n_o) * 1e5,
        }), os.path.join(ord_dir, f"part-{f:05d}.parquet"))
    out["datagen_s"] = round(time.perf_counter() - t0, 2)

    rss_before = _peak_rss_mb()
    t0 = time.perf_counter()
    phases_before = len(getattr(session, "build_stats_log", []))
    hs.create_index(session.read.parquet(li_dir),
                    IndexConfig("sf10_li", ["l_orderkey"],
                                ["l_quantity", "l_extendedprice",
                                 "l_discount", "l_shipdate"]))
    hs.create_index(session.read.parquet(ord_dir),
                    IndexConfig("sf10_ord", ["o_orderkey"],
                                ["o_custkey", "o_totalprice"]))
    hs.create_index(session.read.parquet(li_dir),
                    DataSkippingIndexConfig("sf10_ds", ["l_shipdate"]))
    # Z-order at SF10 (second-dimension pruning workload): one logical
    # bucket, global two-pass layout.
    saved_buckets = session.conf.num_buckets
    saved_max_rows = session.conf.index_max_rows_per_file
    try:
        session.conf.index_max_rows_per_file = N_LINEITEM_SF10 // 64
        session.conf.num_buckets = 1
        hs.create_index(session.read.parquet(li_dir),
                        IndexConfig("sf10_z",
                                    ["l_shipdate", "l_extendedprice"],
                                    ["l_quantity"], layout="zorder"))
    finally:
        # A failed z-order build must not leak num_buckets=1 into the
        # SF100 section's north-star builds.
        session.conf.num_buckets = saved_buckets
        session.conf.index_max_rows_per_file = saved_max_rows
    out["index_build_s"] = round(time.perf_counter() - t0, 2)
    out["build_phases"] = getattr(session, "build_stats_log",
                                  [])[phases_before:]
    out["build_peak_rss_mb"] = round(_peak_rss_mb(), 1)
    out["peak_rss_before_build_mb"] = round(rss_before, 1)

    probe_key = 1_234_567

    def q_filter():
        return (session.read.parquet(li_dir)
                .filter(col("l_orderkey") == probe_key)
                .select("l_orderkey", "l_quantity").collect())

    def q_ds_range():
        lo, hi = 3_000_000, 3_900_000
        return (session.read.parquet(li_dir)
                .filter((col("l_shipdate") >= lo) & (col("l_shipdate") < hi))
                .select("l_shipdate", "l_extendedprice").collect())

    def q_join():
        return (session.read.parquet(ord_dir)
                .filter(col("o_totalprice") < 200.0)
                .join(session.read.parquet(li_dir),
                      col("o_orderkey") == col("l_orderkey"))
                .select("o_orderkey", "o_totalprice", "l_quantity")
                .collect())

    def q_zorder():
        # Range on the SECOND Z-order dimension: only the Z-layout can
        # prune it (the row layout correlates shipdate, not price).
        return (session.read.parquet(li_dir)
                .filter((col("l_extendedprice") >= 9_990.0)
                        & (col("l_shipdate") >= 30_000_000)
                        & (col("l_shipdate") < 31_000_000))
                .select("l_shipdate", "l_extendedprice", "l_quantity")
                .collect())

    def q_q3():
        return (session.read.parquet(ord_dir)
                .filter(col("o_totalprice") < 2_000.0)
                .join(session.read.parquet(li_dir),
                      col("o_orderkey") == col("l_orderkey"))
                .group_by("o_custkey")
                .agg(revenue=(col("l_extendedprice")
                              * (1 - col("l_discount")), "sum"))
                .sort(("revenue", False)).limit(10).collect())

    def q_q10():
        return (session.read.parquet(li_dir)
                .filter((col("l_shipdate") >= 10_000_000)
                        & (col("l_shipdate") < 25_000_000))
                .join(session.read.parquet(ord_dir),
                      col("l_orderkey") == col("o_orderkey"))
                .group_by("o_custkey")
                .agg(revenue=(col("l_extendedprice")
                              * (1 - col("l_discount")), "sum"))
                .sort(("revenue", False)).limit(20).collect())

    speedups = {}
    for name, q in (("filter", q_filter), ("ds_range", q_ds_range),
                    ("join", q_join), ("zorder", q_zorder),
                    ("q3_shape", q_q3), ("q10_shape", q_q10)):
        session.disable_hyperspace()
        expected = q()
        base = _time_adaptive(q, SF10_REPEATS)
        session.enable_hyperspace()
        got = q()
        if not tables_equal(got, expected):
            raise SystemExit(f"sf10 {name}: indexed answer diverged")
        idx = _time_adaptive(q, SF10_REPEATS)
        out[f"{name}_scan_s"] = {k: round(v, 4) if isinstance(v, float)
                                 else v for k, v in base.items()}
        out[f"{name}_indexed_s"] = {k: round(v, 4) if isinstance(v, float)
                                    else v for k, v in idx.items()}
        speedups[name] = base["median"] / idx["median"]
        out[f"{name}_speedup"] = round(speedups[name], 3)
    out["geomean_speedup"] = round(math.exp(
        sum(math.log(s) for s in speedups.values()) / len(speedups)), 3)
    out["query_peak_rss_mb"] = round(_peak_rss_mb(), 1)
    return out


def _sf100_section(session, hs, root: str, tables_equal) -> dict:
    """SF100 — the BASELINE.json north-star metric verbatim: TPC-H
    SF100-scale covering-index build seconds and Q3/Q10 wall-clock.
    600M-row lineitem (narrow 5-column projection of the query columns;
    disk budget — noted in the output) through the streaming spill
    build.  Indexed queries repeat SF100_REPEATS times; the full-scan
    baseline is measured ONCE and only for the point filter.  The
    section deletes its data before returning so the bench root stays
    within disk."""
    import shutil as _shutil

    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    from hyperspace_tpu import IndexConfig, col

    free_gb = _shutil.disk_usage(root).free / 1e9
    if free_gb < SF100_MIN_DISK_GB:
        return {"skipped": f"only {free_gb:.0f} GB free < "
                           f"{SF100_MIN_DISK_GB:.0f} GB needed"}
    out: dict = {"lineitem_rows": N_LINEITEM_SF100,
                 "orders_rows": N_ORDERS_SF100,
                 "files_per_table": SF100_FILES,
                 "reps": SF100_REPEATS,
                 "note": "narrow 5-column lineitem (disk budget); build "
                         "is the full streaming spill path; scan "
                         "baseline measured once, filter only"}
    li_dir = os.path.join(root, "sf100_lineitem")
    ord_dir = os.path.join(root, "sf100_orders")
    os.makedirs(li_dir)
    os.makedirs(ord_dir)
    try:
        t0 = time.perf_counter()
        rng = np.random.default_rng(23)
        per_li = -(-N_LINEITEM_SF100 // SF100_FILES)
        per_ord = -(-N_ORDERS_SF100 // SF100_FILES)
        for f in range(SF100_FILES):
            n = min(per_li, N_LINEITEM_SF100 - f * per_li)
            base = f * per_li
            pq.write_table(pa.table({
                "l_orderkey": rng.integers(0, N_ORDERS_SF100, n),
                "l_quantity": rng.integers(1, 50, n).astype(np.float64),
                "l_extendedprice": rng.random(n) * 1e4,
                "l_discount": rng.random(n) * 0.1,
                "l_shipdate": np.arange(base, base + n, dtype=np.int64),
            }), os.path.join(li_dir, f"part-{f:05d}.parquet"))
            n_o = min(per_ord, N_ORDERS_SF100 - f * per_ord)
            pq.write_table(pa.table({
                "o_orderkey": np.arange(f * per_ord, f * per_ord + n_o,
                                        dtype=np.int64),
                "o_custkey": rng.integers(0, 2_000_000, n_o),
                "o_totalprice": rng.random(n_o) * 1e5,
            }), os.path.join(ord_dir, f"part-{f:05d}.parquet"))
        out["datagen_s"] = round(time.perf_counter() - t0, 2)

        from hyperspace_tpu import DataSkippingIndexConfig

        rss_before = _peak_rss_mb()
        phases_before = len(getattr(session, "build_stats_log", []))
        t0 = time.perf_counter()
        # l_shipdate is covered so Q10's range filter rewrites too —
        # otherwise its "indexed" reps would secretly be raw scans.
        hs.create_index(session.read.parquet(li_dir),
                        IndexConfig("sf100_li", ["l_orderkey"],
                                    ["l_quantity", "l_extendedprice",
                                     "l_discount", "l_shipdate"]))
        out["lineitem_build_s"] = round(time.perf_counter() - t0, 2)
        t0 = time.perf_counter()
        hs.create_index(session.read.parquet(ord_dir),
                        IndexConfig("sf100_ord", ["o_orderkey"],
                                    ["o_custkey", "o_totalprice"]))
        out["orders_build_s"] = round(time.perf_counter() - t0, 2)
        t0 = time.perf_counter()
        hs.create_index(session.read.parquet(li_dir),
                        DataSkippingIndexConfig("sf100_ds",
                                                ["l_shipdate"]))
        out["ds_build_s"] = round(time.perf_counter() - t0, 2)
        out["build_phases"] = getattr(session, "build_stats_log",
                                      [])[phases_before:]
        out["build_peak_rss_mb"] = round(_peak_rss_mb(), 1)
        out["peak_rss_before_build_mb"] = round(rss_before, 1)

        probe_key = 123_456_789

        def q_filter():
            return (session.read.parquet(li_dir)
                    .filter(col("l_orderkey") == probe_key)
                    .select("l_orderkey", "l_quantity").collect())

        def q_q3():
            return (session.read.parquet(ord_dir)
                    .filter(col("o_totalprice") < 1_000.0)
                    .join(session.read.parquet(li_dir),
                          col("o_orderkey") == col("l_orderkey"))
                    .group_by("o_custkey")
                    .agg(revenue=(col("l_extendedprice")
                                  * (1 - col("l_discount")), "sum"))
                    .sort(("revenue", False)).limit(10).collect())

        def q_q10():
            return (session.read.parquet(li_dir)
                    .filter((col("l_shipdate") >= 100_000_000)
                            & (col("l_shipdate") < 115_000_000))
                    .join(session.read.parquet(ord_dir),
                          col("l_orderkey") == col("o_orderkey"))
                    .group_by("o_custkey")
                    .agg(revenue=(col("l_extendedprice")
                                  * (1 - col("l_discount")), "sum"))
                    .sort(("revenue", False)).limit(20).collect())

        # One full-scan baseline rep per workload: it both verifies the
        # indexed answers and gives honest absolute context — repeating
        # 25 GB scans would add nothing.
        session.disable_hyperspace()
        t0 = time.perf_counter()
        expected_filter = q_filter()
        out["filter_scan_once_s"] = round(time.perf_counter() - t0, 2)
        t0 = time.perf_counter()
        expected_q3 = q_q3()
        out["q3_shape_scan_once_s"] = round(time.perf_counter() - t0, 2)
        t0 = time.perf_counter()
        expected_q10 = q_q10()
        out["q10_shape_scan_once_s"] = round(time.perf_counter() - t0, 2)
        session.enable_hyperspace()
        for name, q, expected in (
                ("filter", q_filter, expected_filter),
                ("q3_shape", q_q3, expected_q3),
                ("q10_shape", q_q10, expected_q10)):
            got = q()
            if not tables_equal(got, expected):
                raise SystemExit(f"sf100 {name}: indexed answer diverged")
            out[f"{name}_indexed_s"] = {
                k: round(v, 4) if isinstance(v, float) else v
                for k, v in _time(q, repeats=SF100_REPEATS).items()}
        out["filter_speedup_vs_single_scan"] = round(
            out["filter_scan_once_s"]
            / out["filter_indexed_s"]["median"], 3)
        out["query_peak_rss_mb"] = round(_peak_rss_mb(), 1)
    finally:
        session.disable_hyperspace()
        _shutil.rmtree(li_dir, ignore_errors=True)
        _shutil.rmtree(ord_dir, ignore_errors=True)
    return out


def _require_tpu() -> None:
    """The bench measures the TPU.  It runs on the CPU only when the
    caller pins ``JAX_PLATFORMS=cpu``; otherwise a missing TPU backend
    ends the run before any section, so no CPU number is printed under a
    device headline.  No child process probes the chip: this process is
    the one that holds it."""
    if os.environ.get("JAX_PLATFORMS", "").lower() == "cpu":
        return
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(
            f"bench: no TPU backend (JAX found {backend!r}); set "
            f"JAX_PLATFORMS=cpu to run the bench on the CPU")


class _SectionTimeout(Exception):
    """Raised by the SIGALRM handler: the current section blew its hard
    runtime cap."""


class _Finalize(Exception):
    """Raised by the SIGTERM handler: stop measuring, keep everything."""


class _SkipSection(Exception):
    """Raised by a section body to self-skip with a reason (env gates,
    missing prerequisites)."""


class _Harness:
    """Section runner: budget gates, per-section runtime caps, immediate
    checkpointing, and signal-safe finalization (module docstring has the
    full contract)."""

    def __init__(self, planned_sections=()) -> None:
        self.t0 = time.monotonic()
        self.detail: Dict[str, object] = {}
        self.sections: list = []
        self.stop_reason: Optional[str] = None
        self.finalizing = False
        self.finalized = False
        self._in_section = False
        self._current_section: Optional[str] = None
        # The full run plan, so an emergency (in-handler) finalize can
        # mark never-reached sections without unwinding back to main().
        self.planned_sections = list(planned_sections)
        # Set by main once setup built the session: () -> geomean or None
        # (falls back to a PARTIAL sf1 geomean when the full section never
        # finished), and the conf whose perf ledger section records append
        # to (None before setup / when the ledger is disabled).
        self.geomean_source = lambda: None
        self.ledger_conf_source = lambda: None
        self.cleanup_root: Optional[str] = None
        self.results_path = RESULTS_PATH
        self._results_broken = False
        if self.results_path:
            try:
                # Rotate, don't truncate: the previous run's checkpoints
                # become <path>.prev — the `--compare auto` baseline.
                if os.path.exists(self.results_path):
                    os.replace(self.results_path,
                               self.results_path + ".prev")
                with open(self.results_path, "w", encoding="utf-8") as f:
                    f.write(json.dumps(
                        {"bench": "hyperspace-tpu",
                         "budget_s": BUDGET_S,
                         "scale": {"lineitem_rows": N_LINEITEM,
                                   "orders_rows": N_ORDERS}}) + "\n")
            except OSError as e:
                self._results_broken = True
                print(f"bench: results file unwritable ({e}); "
                      "checkpoints go to stdout only", file=sys.stderr)
        if TRACE_PATH:
            # Span tracing for the whole run: each section (and every
            # query.collect inside it) lands as one root span in the
            # JSONL sink — the machine-readable trace the CI smoke step
            # greps for required span kinds (docs/16-observability.md).
            from hyperspace_tpu.telemetry import trace

            try:
                open(TRACE_PATH, "w").close()  # one file per run
                trace.add_sink(trace.JsonlTraceSink(TRACE_PATH))
                trace.enable_tracing()
                self.detail["trace_file"] = TRACE_PATH
            except OSError as e:
                print(f"bench: trace sink unwritable ({e}); tracing off",
                      file=sys.stderr)
        signal.signal(signal.SIGALRM, self._on_alarm)
        signal.signal(signal.SIGTERM, self._on_term)

    # -- signals ----------------------------------------------------------
    def _on_alarm(self, signum, frame) -> None:
        if self._in_section:
            raise _SectionTimeout()

    def _on_term(self, signum, frame) -> None:
        # Finalize IN the handler: unwinding a SIGTERM exception back
        # through a C-extension-heavy section (a multi-GB parquet write
        # in the sf10 build) can take longer than the killer's grace
        # window — r05 lost its headline exactly that way.  The handler
        # itself only needs to print JSON, which fits any grace window
        # in which Python gets to run at all.
        if self.finalizing:
            return
        self.finalizing = True
        self.stop_reason = "SIGTERM"
        try:
            if self._current_section is not None:
                self._mark(self._current_section, "skipped",
                           0.0, "SIGTERM mid-section")
            self.finalize(self.geomean_source())
        finally:
            root = self.cleanup_root
            if root is not None:
                # Best effort, strictly AFTER the headline is out: if the
                # follow-up SIGKILL lands mid-rmtree, nothing is lost.
                import shutil as _shutil

                _shutil.rmtree(root, ignore_errors=True)
            os._exit(0)

    # -- bookkeeping ------------------------------------------------------
    def elapsed(self) -> float:
        return time.monotonic() - self.t0

    def remaining(self) -> float:
        return math.inf if BUDGET_S <= 0 else BUDGET_S - self.elapsed()

    def _checkpoint(self, record: dict) -> None:
        if not self.results_path or self._results_broken:
            return
        try:
            with open(self.results_path, "a", encoding="utf-8") as f:
                f.write(json.dumps(record) + "\n")
                f.flush()
                os.fsync(f.fileno())
        except OSError as e:
            self._results_broken = True
            print(f"bench: results checkpoint failed ({e})",
                  file=sys.stderr)

    def _mark(self, name: str, status: str, elapsed_s: float,
              reason: str = "") -> None:
        line = {"section": name, "status": status,
                "elapsed_s": round(elapsed_s, 2)}
        if reason:
            line["reason"] = reason
        self.sections.append(line)
        if status != "ok":
            self.detail[name] = {"skipped": reason or status}
            self._checkpoint(line)
        print(json.dumps(line), flush=True)

    def section(self, name: str, fn: Callable[[], dict]) -> bool:
        """Run one section; ``fn`` returns the detail-dict updates it
        owns.  Completed sections checkpoint immediately; anything else
        becomes an explicit ``{"skipped": reason}`` marker.  Returns
        True when the section completed."""
        global _SOFT_DEADLINE
        if self.stop_reason is not None:
            self._mark(name, "skipped", 0.0, self.stop_reason)
            return False
        rem = self.remaining()
        if rem <= 0:
            self.stop_reason = (f"global budget {BUDGET_S:.0f}s exhausted "
                                f"after {self.elapsed():.0f}s")
            self._mark(name, "skipped", 0.0, self.stop_reason)
            return False
        cap = rem if SECTION_CAP_S <= 0 else min(rem, SECTION_CAP_S)
        t0 = time.perf_counter()
        try:
            _SOFT_DEADLINE = time.monotonic() + cap
            if cap is not math.inf:
                # Hard guard a few seconds past the soft deadline: reps
                # wind down softly; a single runaway op gets interrupted.
                signal.alarm(max(1, int(cap) + 5))
            self._in_section = True
            self._current_section = name
            from hyperspace_tpu.telemetry.trace import span as _span

            with _span(f"bench.{name}"):
                updates = fn()
            self._in_section = False
        except _SkipSection as e:
            self._mark(name, "skipped", time.perf_counter() - t0, str(e))
            return False
        except _SectionTimeout:
            self._mark(name, "skipped", time.perf_counter() - t0,
                       f"runtime cap {cap:.0f}s hit mid-section")
            if self.remaining() <= 0:
                self.stop_reason = (
                    f"global budget {BUDGET_S:.0f}s exhausted after "
                    f"{self.elapsed():.0f}s")
            return False
        except _Finalize:
            self.stop_reason = "SIGTERM"
            self._mark(name, "skipped", time.perf_counter() - t0,
                       "SIGTERM mid-section")
            return False
        except SystemExit:
            raise  # correctness gates must fail the whole bench
        except Exception as e:  # resource exhaustion must not
            self._mark(name, "skipped", time.perf_counter() - t0,
                       f"{type(e).__name__}: {e}")
            return False
        finally:
            signal.alarm(0)
            self._in_section = False
            self._current_section = None
            _SOFT_DEADLINE = None
        elapsed = time.perf_counter() - t0
        self.detail.update(updates)
        self._checkpoint({"section": name, "status": "ok",
                          "elapsed_s": round(elapsed, 2), **updates})
        self._ledger_append(name, elapsed, updates)
        self._mark(name, "ok", elapsed)
        return True

    def _ledger_append(self, name: str, elapsed: float,
                       updates: dict) -> None:
        """One compact perf-ledger record per completed section, through
        the LogStore seam of the bench session's systemPath (the same
        ledger the build actions append to).  Best-effort diagnostics."""
        conf = self.ledger_conf_source()
        if conf is None:
            return
        try:
            from hyperspace_tpu.telemetry import bench_compare, perf_ledger

            flat: Dict[str, float] = {}
            bench_compare._flatten("", {k: v for k, v in updates.items()
                                        if k not in ("scale",)}, flat)
            perf_ledger.append(conf, {
                "kind": "bench", "name": name, "outcome": "ok",
                "wall_s": round(elapsed, 3),
                "metrics": dict(sorted(flat.items())[:200]),
                "fingerprint": perf_ledger.fingerprint(conf)})
        except Exception:  # noqa: BLE001 — never fail a section for this
            pass

    def finalize(self, geomean: Optional[float]) -> None:
        """Print the headline line (BENCH_r04-compatible shape) and
        append it to the results file.  Always runs — and runs at most
        once (the SIGTERM handler and the normal path can race) — this
        is the 'cannot lose finished work' guarantee."""
        if self.finalized:
            return
        self.finalized = True
        self.finalizing = True
        # Planned sections the run never reached get explicit markers
        # (the contract test_bench_resilience checks: every section is
        # accounted for, completed numbers or a reasoned skip).
        for name in self.planned_sections:
            if name not in self.detail and not any(
                    s["section"] == name for s in self.sections):
                self._mark(name, "skipped", 0.0,
                           self.stop_reason or "not reached")
        self.detail["platform"] = _platform()
        # The budget the run ACTUALLY ran under — when HS_BENCH_BUDGET
        # was unset this is the value derived from the enclosing
        # timeout, so a post-mortem can see why sections were cut.
        self.detail["budget_s"] = round(BUDGET_S, 1)
        self.detail["bench_elapsed_s"] = round(self.elapsed(), 1)
        self.detail["sections_run"] = self.sections
        if self.results_path and not self._results_broken:
            self.detail["results_file"] = self.results_path
        value = None if geomean is None else round(geomean, 3)
        line = {
            "metric": "tpch_sf1_indexed_query_speedup_geomean",
            "value": value,
            "unit": "x",
            "vs_baseline": value,
            "detail": self.detail,
        }
        self._checkpoint({"headline": line})
        print(json.dumps(line), flush=True)


SECTION_NAMES = ("setup", "sf1_queries", "device_agg_probe",
                 "resident_agg", "warm_resident_join", "warm_q3",
                 "warm_q10", "window_bench", "kernel_bench",
                 "calibration", "telemetry_overhead", "advisor",
                 "integrity", "build_profile", "timeline",
                 "build_pipeline", "multichip", "multihost", "serving",
                 "flight_recorder", "alerts", "fleet_obs", "fleet",
                 "chaos", "ingest", "cdc", "sf10", "sf100")


def main() -> int:
    harness = _Harness(planned_sections=SECTION_NAMES)
    try:
        _require_tpu()
    except _Finalize:
        harness.stop_reason = "SIGTERM"
    root = tempfile.mkdtemp(prefix="hs_bench_")
    harness.cleanup_root = root
    ctx: dict = {}

    def _geomean_now() -> Optional[float]:
        """The headline value from whatever finished: the full sf1
        geomean, else a PARTIAL geomean over the workloads that timed
        before the run was cut short (labeled in the detail)."""
        if "geomean" in ctx:
            return ctx["geomean"]
        partial = ctx.get("partial_speedups") or {}
        if not partial:
            return None
        harness.detail["geomean_partial_workloads"] = sorted(partial)
        return math.exp(sum(math.log(s) for s in partial.values())
                        / len(partial))

    harness.geomean_source = _geomean_now
    harness.ledger_conf_source = \
        lambda: ctx["session"].conf if "session" in ctx else None
    try:
        try:
            harness.section("setup", lambda: _sec_setup(ctx, root))
            harness.section("sf1_queries", lambda: _sec_sf1_queries(ctx))
            harness.section("device_agg_probe",
                            lambda: _sec_device_agg_probe(ctx))
            harness.section("resident_agg", lambda: _sec_resident_agg(ctx))
            harness.section("warm_resident_join",
                            lambda: _sec_warm(ctx, "warm_resident_join"))
            harness.section("warm_q3", lambda: _sec_warm(ctx, "warm_q3"))
            harness.section("warm_q10", lambda: _sec_warm(ctx, "warm_q10"))
            harness.section("window_bench", lambda: _sec_window(ctx))
            harness.section("kernel_bench",
                            lambda: {"kernel_bench": _kernel_microbench()})
            harness.section("calibration", lambda: _sec_calibration())
            harness.section("telemetry_overhead",
                            lambda: _sec_telemetry_overhead(ctx))
            harness.section("advisor", lambda: _sec_advisor(ctx))
            harness.section("integrity", lambda: _sec_integrity(root))
            harness.section("build_profile",
                            lambda: _sec_build_profile(root))
            harness.section("timeline", lambda: _sec_timeline(root))
            harness.section("build_pipeline",
                            lambda: _sec_build_pipeline(root))
            harness.section("multichip", lambda: _sec_multichip(root))
            harness.section("multihost", lambda: _sec_multihost(root))
            harness.section("serving", lambda: _sec_serving(ctx))
            harness.section("flight_recorder",
                            lambda: _sec_flight_recorder(ctx))
            harness.section("alerts", lambda: _sec_alerts(ctx))
            harness.section("fleet_obs", lambda: _sec_fleet_obs(ctx))
            harness.section("fleet", lambda: _sec_fleet(ctx))
            harness.section("chaos", lambda: _sec_chaos())
            harness.section("ingest", lambda: _sec_ingest(root))
            harness.section("cdc", lambda: _sec_cdc(root))
            harness.section("sf10", lambda: _sec_sf10(ctx, root, harness))
            harness.section("sf100", lambda: _sec_sf100(ctx, root, harness))
        except _Finalize:
            # Legacy path (the handler now finalizes in-line and exits);
            # kept so an explicitly raised _Finalize still lands softly.
            harness.stop_reason = harness.stop_reason or "SIGTERM"
        harness.finalize(_geomean_now())
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return 0


# ---------------------------------------------------------------------------
# Sections.  Each takes the shared ctx (populated by setup) and returns the
# detail updates it owns; prerequisites missing => _SkipSection.
# ---------------------------------------------------------------------------
def _require(ctx: dict, *keys: str) -> None:
    for k in keys:
        if k not in ctx:
            raise _SkipSection("setup did not complete")


def _sec_setup(ctx: dict, root: str) -> dict:
    """Data generation, session, all SF1 index builds, the Delta/hybrid
    tables, and the query/equality closures every later section uses."""
    from hyperspace_tpu import (
        DataSkippingIndexConfig,
        Hyperspace,
        HyperspaceSession,
        IndexConfig,
        col,
    )

    orders_dir, lineitem_dir = _gen_data(root)
    session = HyperspaceSession(system_path=os.path.join(root, "indexes"))
    session.conf.num_buckets = NUM_BUCKETS
    hs = Hyperspace(session)

    t_build0 = time.perf_counter()
    hs.create_index(session.read.parquet(lineitem_dir),
                    IndexConfig("li_idx", ["l_orderkey"],
                                ["l_quantity", "l_extendedprice",
                                 "l_discount", "l_shipdate"]))
    hs.create_index(session.read.parquet(orders_dir),
                    IndexConfig("ord_idx", ["o_orderkey"],
                                ["o_totalprice", "o_custkey",
                                 "o_shippriority"]))
    hs.create_index(session.read.parquet(lineitem_dir),
                    DataSkippingIndexConfig("li_ds", ["l_shipdate"]))
    # Z-order over (shipdate, extendedprice): range queries on the
    # second dimension prune files (BASELINE config 5's shape).  One
    # bucket, ~64-file target along the Z-curve; the writer aligns file
    # cuts to Z-cell boundaries (io/parquet.zorder_split_chunks) so each
    # file stays narrow on BOTH dimensions.
    session.conf.index_max_rows_per_file = max(1, N_LINEITEM // 64)
    session.conf.num_buckets = 1
    hs.create_index(session.read.parquet(lineitem_dir),
                    IndexConfig("li_z", ["l_shipdate", "l_extendedprice"],
                                ["l_quantity"], layout="zorder"))
    session.conf.num_buckets = NUM_BUCKETS
    session.conf.index_max_rows_per_file = 0
    build_s = time.perf_counter() - t_build0

    # Delta table + index + append: the Hybrid Scan workload
    # (BASELINE config 4).
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    from hyperspace_tpu.sources.delta import write_delta

    delta_dir = os.path.join(root, "dorders")
    d_n = N_LINEITEM  # big enough that a full scan actually costs
    rng2 = np.random.default_rng(3)
    keys = np.arange(d_n)
    for part in range(8):  # multi-file table, like the parquet side
        sl = slice(part * d_n // 8, (part + 1) * d_n // 8)
        write_delta(pa.table({
            "o_orderkey": keys[sl],
            "o_totalprice": rng2.random(d_n // 8) * 1e5,
            "o_pad": rng2.random(d_n // 8),
        }), delta_dir, mode="append")
    hs.create_index(session.read.delta(delta_dir),
                    IndexConfig("dord_idx", ["o_orderkey"],
                                ["o_totalprice"]))
    write_delta(pa.table({
        "o_orderkey": np.arange(d_n, d_n + d_n // 20),
        "o_totalprice": rng2.random(d_n // 20) * 1e5,
        "o_pad": rng2.random(d_n // 20),
    }), delta_dir, mode="append")

    # Hybrid JOIN workload: lineitem copy with ~5% appended rows after
    # indexing; the join must execute bucket-aligned with the appended
    # rows routed into the index's bucket space (RuleUtils.scala:511-570).
    hj_li_dir = os.path.join(root, "hj_lineitem")
    os.makedirs(hj_li_dir)
    for f in os.listdir(lineitem_dir):
        os.link(os.path.join(lineitem_dir, f), os.path.join(hj_li_dir, f))
    hs.create_index(session.read.parquet(hj_li_dir),
                    IndexConfig("hj_li_idx", ["l_orderkey"],
                                ["l_quantity"]))
    pq.write_table(pa.table(_gen_lineitem(rng2, max(1, N_LINEITEM // 20))),
                   os.path.join(hj_li_dir, "appended-00000.parquet"))

    probe_key = 123_457

    def _tables_equal(a, b):
        """Full-content equality after canonical ordering.  Float
        columns compare with tolerance: aggregate sums accumulate in
        different orders on the indexed vs scan paths (per-bucket vs
        per-file), so last-ulp differences are expected — anything
        beyond ~1e-9 relative is a real bug."""
        if a.num_rows != b.num_rows or set(a.column_names) != set(b.column_names):
            return False
        import numpy as np
        import pyarrow as pa

        cols = sorted(a.column_names)
        keys = [(c, "ascending") for c in cols]
        a = a.select(cols).sort_by(keys)
        b = b.select(cols).sort_by(keys)
        for c in cols:
            ca, cb = a.column(c), b.column(c)
            if pa.types.is_floating(ca.type):
                va = ca.to_numpy(zero_copy_only=False)
                vb = cb.to_numpy(zero_copy_only=False)
                if not np.allclose(va, vb, rtol=1e-9, atol=1e-6,
                                   equal_nan=True):
                    return False
            elif not ca.equals(cb):
                return False
        return True

    def ds_filter():
        return (session.read.parquet(lineitem_dir)
                .filter(col("l_orderkey") == probe_key)
                .select("l_orderkey", "l_quantity"))

    def q_filter():
        return ds_filter().collect()

    def q_join():
        orders = session.read.parquet(orders_dir)
        lineitem = session.read.parquet(lineitem_dir)
        return (orders
                .join(lineitem, col("o_orderkey") == col("l_orderkey"))
                .select("o_orderkey", "o_totalprice", "l_quantity",
                        "l_extendedprice")
                .collect())

    def ds_zorder_second_dim():
        lo, hi = 2500.0, 3000.0
        return (session.read.parquet(lineitem_dir)
                .filter((col("l_extendedprice") >= lo)
                        & (col("l_extendedprice") < hi))
                .select("l_shipdate", "l_extendedprice", "l_quantity"))

    def q_zorder_second_dim():
        return ds_zorder_second_dim().collect()

    def ds_hybrid_delta():
        return (session.read.delta(delta_dir)
                .filter(col("o_orderkey") == probe_key)
                .select("o_orderkey", "o_totalprice"))

    def q_hybrid_delta():
        session.conf.hybrid_scan_enabled = True
        try:
            return ds_hybrid_delta().collect()
        finally:
            session.conf.hybrid_scan_enabled = False

    def ds_hybrid_join():
        orders = session.read.parquet(orders_dir)
        lineitem = session.read.parquet(hj_li_dir)
        return (orders
                .join(lineitem, col("o_orderkey") == col("l_orderkey"))
                .select("o_orderkey", "o_totalprice", "l_quantity"))

    def q_hybrid_join():
        session.conf.hybrid_scan_enabled = True
        try:
            return ds_hybrid_join().collect()
        finally:
            session.conf.hybrid_scan_enabled = False

    def ds_q3_shape():
        # TPC-H Q3 shape (BASELINE.md north-star): selective filter on
        # one side, indexed join, expression-aggregate revenue,
        # top-10 by revenue.
        orders = session.read.parquet(orders_dir)
        lineitem = session.read.parquet(lineitem_dir)
        return (orders
                .filter(col("o_totalprice") < 25_000.0)
                .join(lineitem, col("o_orderkey") == col("l_orderkey"))
                .group_by("o_orderkey", "o_shippriority")
                .agg(revenue=(col("l_extendedprice")
                              * (1 - col("l_discount")), "sum"))
                .sort(("revenue", False)).limit(10))

    def q_q3_shape():
        return ds_q3_shape().collect()

    def ds_q10_shape():
        # TPC-H Q10 shape: filtered lineitem side (date range, DS
        # sketch prunes), join, revenue per customer, top-20.
        orders = session.read.parquet(orders_dir)
        lineitem = session.read.parquet(lineitem_dir)
        return (lineitem
                .filter((col("l_shipdate") >= N_LINEITEM // 6)
                        & (col("l_shipdate") < N_LINEITEM * 5 // 12))
                .join(orders, col("l_orderkey") == col("o_orderkey"))
                .group_by("o_custkey")
                .agg(revenue=(col("l_extendedprice")
                              * (1 - col("l_discount")), "sum"))
                .sort(("revenue", False)).limit(20))

    def q_q10_shape():
        return ds_q10_shape().collect()

    def ds_ds_range():
        # BASELINE.json's data-skipping config: a date-range scan over
        # the wide table; min/max file pruning reads 1/8 of the files.
        lo, hi = N_LINEITEM // 20, N_LINEITEM * 13 // 200
        return (session.read.parquet(lineitem_dir)
                .filter((col("l_shipdate") >= lo) & (col("l_shipdate") < hi))
                .select("l_shipdate", "l_extendedprice", "l_discount"))

    def q_ds_range():
        return ds_ds_range().collect()

    ctx.update(
        session=session, hs=hs, col=col,
        orders_dir=orders_dir, lineitem_dir=lineitem_dir,
        tables_equal=_tables_equal,
        ds_builders={"filter": ds_filter, "q3_shape": ds_q3_shape,
                     "q10_shape": ds_q10_shape, "ds_range": ds_ds_range,
                     "zorder": ds_zorder_second_dim,
                     "hybrid": ds_hybrid_delta,
                     "hybrid_join": ds_hybrid_join},
        queries=[("filter", q_filter), ("join", q_join),
                 ("q3_shape", q_q3_shape), ("q10_shape", q_q10_shape),
                 ("ds_range", q_ds_range), ("zorder", q_zorder_second_dim),
                 ("hybrid", q_hybrid_delta),
                 ("hybrid_join", q_hybrid_join)],
    )
    return {"scale": {"lineitem_rows": N_LINEITEM,
                      "orders_rows": N_ORDERS,
                      "files_per_table": N_FILES,
                      "num_buckets": NUM_BUCKETS,
                      "reps": REPEATS},
            "index_build_s": round(build_s, 3),
            # Per-index, per-phase build attribution (read / kernel /
            # write / sketch seconds) — appended by every CreateActionBase
            # build.  Snapshot: later sections append their own.
            "index_build_phases": list(getattr(session, "build_stats_log",
                                               []))}


def _sec_sf1_queries(ctx: dict) -> dict:
    """The headline SF1 workloads, indexed vs full scan, with the answer
    and rewrite-fired correctness gates.  Sets ctx['geomean']."""
    _require(ctx, "session", "queries")
    session = ctx["session"]

    results = {}
    for name, q in ctx["queries"]:
        session.disable_hyperspace()
        expected = q()
        base_s = _time(q)
        session.enable_hyperspace()
        got = q()
        # Correctness gate: speedup only counts if answers match —
        # full content equality after canonical ordering, not just row
        # counts (a pruning bug can return the right COUNT of wrong rows).
        if not ctx["tables_equal"](got, expected):
            raise SystemExit(
                f"{name}: indexed answer differs from full scan "
                f"({got.num_rows} vs {expected.num_rows} rows)")
        idx_s = _time(q)
        results[name] = (base_s, idx_s)
        # Stream each workload's ratio into ctx as it lands: a run cut
        # short mid-section still finalizes with a PARTIAL geomean over
        # these instead of losing the headline value entirely.
        ctx.setdefault("partial_speedups", {})[name] = \
            base_s["median"] / idx_s["median"]

    # Verify EVERY workload's rewrite actually fired — a silent
    # scan-vs-scan measurement must fail, not report ~1x as valid.
    # Each check optimizes the SAME dataset builder the timing used,
    # under the SAME optimizer configuration (hybrid flag included).
    session.enable_hyperspace()
    ds_builders = ctx["ds_builders"]

    def assert_rewrites(name, ds):
        plan = ds.optimized_plan()
        used = [s for s in plan.leaf_relations()
                if s.relation.index_scan_of or s.relation.data_skipping_of]
        if not used:
            raise SystemExit(f"{name}: rewrite did not fire; bench invalid")

    for name in ("filter", "q3_shape", "q10_shape", "ds_range", "zorder"):
        assert_rewrites(name, ds_builders[name]())
    session.conf.hybrid_scan_enabled = True
    try:
        assert_rewrites("hybrid", ds_builders["hybrid"]())
        assert_rewrites("hybrid_join", ds_builders["hybrid_join"]())
        # The hybrid join must EXECUTE bucket-aligned, not degrade to a
        # full-table merge (the round-1 gap): re-run once and check the
        # recorded strategy.
        ds_builders["hybrid_join"]().collect()
        stats = session.last_execution_stats or {"joins": []}
        if not any(j.get("strategy") == "bucketed" and j.get("hybrid")
                   for j in stats["joins"]):
            raise SystemExit(
                "hybrid_join: bucket-aligned execution did not fire; "
                f"joins={stats['joins']}")
    finally:
        session.conf.hybrid_scan_enabled = False

    speedups = {k: b["median"] / i["median"]
                for k, (b, i) in results.items()}
    geomean = math.exp(sum(math.log(s) for s in speedups.values())
                       / len(speedups))
    ctx["geomean"] = geomean

    out: dict = {}
    for name, (base, idx) in results.items():
        out[f"{name}_scan_s"] = _stat(base)
        out[f"{name}_indexed_s"] = _stat(idx)
        out[f"{name}_speedup"] = round(speedups[name], 3)
    return out


def _stat(d: dict) -> dict:
    return {k: (round(v, 4) if isinstance(v, float) else v)
            for k, v in d.items()}


def _sec_device_agg_probe(ctx: dict) -> dict:
    """Device aggregation probe: the cost model may keep bench-scale
    GROUP BYs on host (deviceAggMinRows rationale in config.py), so the
    segment-reduction kernel is measured EXPLICITLY here — forced on,
    against the host path — and reported outside the
    headline geomean.  The input is materialized ONCE so the timings
    isolate the aggregation, not a shared table scan."""
    _require(ctx, "session")
    session, col = ctx["session"], ctx["col"]

    from hyperspace_tpu.dataset import Dataset
    from hyperspace_tpu.plan.nodes import InMemory

    probe_rows = min(1_000_000, N_LINEITEM)
    session.disable_hyperspace()
    slice_tbl = (session.read.parquet(ctx["lineitem_dir"])
                 .filter(col("l_shipdate") < probe_rows)
                 .select("l_orderkey", "l_quantity", "l_extendedprice")
                 .collect())

    def agg_probe():
        return (Dataset(InMemory(slice_tbl), session)
                .group_by("l_orderkey")
                .agg(qty=("l_quantity", "sum"),
                     hi=("l_extendedprice", "max"),
                     n=("", "count_all")))

    saved_agg_min = session.conf.device_agg_min_rows
    try:
        session.conf.device_agg_min_rows = 1
        dev_tbl = agg_probe().collect()
        dev_stats = session.last_execution_stats or {}
        if not any(a.get("strategy") == "device-segment"
                   for a in dev_stats.get("aggregates", [])):
            raise SystemExit("device aggregation probe did not take "
                             "the device path; probe invalid")
        dev_s = _time(lambda: agg_probe().collect(), repeats=2)
        session.conf.device_agg_min_rows = 1 << 60
        host_tbl = agg_probe().collect()
        host_s = _time(lambda: agg_probe().collect(), repeats=2)
    finally:
        session.conf.device_agg_min_rows = saved_agg_min
    if not ctx["tables_equal"](dev_tbl, host_tbl):
        raise SystemExit("device aggregation answer diverged from host")
    return {"device_agg_probe": {
        "rows": slice_tbl.num_rows,
        "groups": dev_tbl.num_rows,
        "device_s": _stat(dev_s),
        "host_s": _stat(host_s),
        "note": "kernel correctness+timing probe over an in-memory "
                "slice, outside the geomean, forced onto the device",
    }}


def _break_even_repeats(cold_s: float, host_s: float, warm_s: float):
    """Warm repeats k after which eager caching has paid for itself:
    ``cold + k*warm < (k+1)*host``.  None when the warm path never beats
    host on this machine (transfer-dominated); 0 when even the populate
    pass beat a host run (device-bound op).
    The break-even narrative lives in docs/11-optimize.md."""
    saving = host_s - warm_s
    if saving <= 0:
        return None
    return max(0, math.ceil((cold_s - host_s) / saving))


def _sec_resident_agg(ctx: dict) -> dict:
    """Warm-resident aggregation (round-3 verdict item 2): with the HBM
    cache's 'eager' policy, the FIRST group-by over the scan ships the
    columns; repeats run the segment kernel on resident data and route
    there ORGANICALLY via the resident threshold."""
    _require(ctx, "session")
    session, col = ctx["session"], ctx["col"]

    from hyperspace_tpu.execution.device_cache import global_cache

    def resident_q():
        return (session.read.parquet(ctx["lineitem_dir"])
                .group_by("l_status")
                .agg(qty=("l_quantity", "sum"),
                     hi=("l_extendedprice", "max"))
                .sort("l_status").collect())

    session.disable_hyperspace()
    saved_policy = session.conf.device_cache_policy
    saved_agg_thresh = session.conf.device_agg_min_rows
    try:
        session.conf.device_cache_policy = "off"
        session.conf.device_agg_min_rows = 1 << 60
        host_res_tbl = resident_q()
        host_res = _time(resident_q, repeats=3)
        session.conf.device_agg_min_rows = None  # back to calibrated
        session.conf.device_cache_policy = "eager"
        global_cache().clear()
        t0 = time.perf_counter()
        cold_tbl = resident_q()  # populates the cache
        cold_s = time.perf_counter() - t0
        cold_stats = session.last_execution_stats or {}
        warm_tbl = resident_q()
        warm_stats = session.last_execution_stats or {}
        aggs = warm_stats.get("aggregates", [])
        warm_fired = bool(aggs and aggs[-1]["strategy"]
                          == "device-segment" and aggs[-1]["resident"])
        warm_res = _time(resident_q, repeats=3)
    finally:
        session.conf.device_cache_policy = saved_policy
        session.conf.device_agg_min_rows = saved_agg_thresh
    for got, name in ((cold_tbl, "cold"), (warm_tbl, "warm")):
        if not ctx["tables_equal"](got, host_res_tbl):
            raise SystemExit(f"resident agg ({name}) diverged from host")
    return {"resident_agg": {
        "rows": N_LINEITEM,
        "groups": host_res_tbl.num_rows,
        "host_s": _stat(host_res),
        "cold_populate_s": round(cold_s, 4),
        "warm_resident_s": _stat(warm_res),
        "warm_speedup_vs_host": round(
            host_res["median"] / warm_res["median"], 3),
        # Repeats before eager caching pays on THIS machine:
        # cold + k*warm < (k+1)*host  =>  k > (cold-host)/(host-warm).
        # None = warm never beats host here (see docs/11-optimize.md).
        "warm_break_even_repeats": _break_even_repeats(
            cold_s, host_res["median"], warm_res["median"]),
        # True = the warm repeat was ROUTED to the resident device
        # path by the calibrated threshold itself, no forcing.  False
        # is honest too: this machine's measured latency says even
        # resident compute cannot repay the round trips at this scale.
        "warm_resident_fired_organically": warm_fired,
        "cache": global_cache().stats(),
        "cold_cache_stats": cold_stats.get("device_cache"),
        "note": "eager cache policy (explicit opt-in); routing itself "
                "is by the calibrated resident threshold",
    }}


def _sec_warm(ctx: dict, which: str) -> dict:
    """Warm-resident JOIN + fused join-aggregate (round-5 verdict item
    1): with the eager policy, the first run ships the referenced
    columns once; warm repeats run the device kernels on HBM-resident
    inputs, routed ORGANICALLY by the resident threshold.  warm_q3 /
    warm_q10 run the WHOLE pipeline on device (join match -> gather ->
    expression -> segment reduce -> top-N) with only the final groups
    crossing back."""
    _require(ctx, "session", "queries")
    session, col = ctx["session"], ctx["col"]

    from hyperspace_tpu.execution.device_cache import global_cache

    def _join_fired(st):
        ks = st.get("join_kernels", [])
        return bool(ks and ks[-1]["strategy"] == "device"
                    and ks[-1]["resident"])

    def _fused_fired(st):
        ag = st.get("aggregates", [])
        return bool(ag and ag[-1]["strategy"] == "device-join-agg"
                    and ag[-1]["resident"])

    def warm_join_q():
        return (session.read.parquet(ctx["orders_dir"])
                .filter(col("o_totalprice") < 2_000.0)
                .join(session.read.parquet(ctx["lineitem_dir"]),
                      col("o_orderkey") == col("l_orderkey"))
                .select("o_orderkey", "o_totalprice",
                        "l_quantity").collect())

    by_name = dict(ctx["queries"])
    make_q, fired_fn, enable_hs = {
        # The north-star shapes run warm with indexes ON so the fused
        # pipeline consumes the rewritten index scans.
        "warm_resident_join": (warm_join_q, _join_fired, False),
        "warm_q3": (by_name["q3_shape"], _fused_fired, True),
        "warm_q10": (by_name["q10_shape"], _fused_fired, True),
    }[which]

    saved_policy = session.conf.device_cache_policy
    saved_join_thresh = session.conf.device_join_min_rows
    if enable_hs:
        session.enable_hyperspace()
    else:
        session.disable_hyperspace()
    try:
        out = {}
        session.conf.device_cache_policy = "off"
        session.conf.device_join_min_rows = 1 << 60
        host_tbl = make_q()
        out["host_s"] = _stat(_time(make_q, repeats=3))
        session.conf.device_join_min_rows = None  # calibrated
        session.conf.device_cache_policy = "eager"
        global_cache().clear()
        t0 = time.perf_counter()
        cold_tbl = make_q()  # populate pass: pay the transfer once
        out["cold_populate_s"] = round(time.perf_counter() - t0, 4)
        warm_tbl = make_q()
        out["warm_fired_organically"] = fired_fn(
            session.last_execution_stats or {})
        out["warm_s"] = _stat(_time(make_q, repeats=3))
        out["warm_speedup_vs_host"] = round(
            out["host_s"]["median"] / out["warm_s"]["median"], 3)
        # Warm-path economics (round-5 verdict item 7): how many warm
        # repeats amortize the cold populate pass on this machine.
        out["warm_break_even_repeats"] = _break_even_repeats(
            out["cold_populate_s"], out["host_s"]["median"],
            out["warm_s"]["median"])
        for got, label in ((cold_tbl, "cold"), (warm_tbl, "warm")):
            if not ctx["tables_equal"](got, host_tbl):
                raise SystemExit(f"{which} ({label}) diverged from host")
        out["groups_or_rows"] = host_tbl.num_rows
        return {which: out}
    finally:
        session.disable_hyperspace()
        session.conf.device_cache_policy = saved_policy
        session.conf.device_join_min_rows = saved_join_thresh
        global_cache().clear()


def _sec_window(ctx: dict) -> dict:
    """Window engine (round-5 verdict item 7): the vectorized numpy
    segment kernels timed at bench scale, plus the whole-partition
    device path over resident columns (organic routing flag, like
    resident_agg)."""
    _require(ctx, "session")
    session = ctx["session"]

    from hyperspace_tpu.execution.device_cache import global_cache

    session.disable_hyperspace()
    saved_policy = session.conf.device_cache_policy
    saved_agg = session.conf.device_agg_min_rows
    try:
        session.conf.device_cache_policy = "off"
        # Host baselines must be HOST even on fast links whose
        # calibrated cold threshold would route windows on-device.
        session.conf.device_agg_min_rows = 1 << 60
        lineitem_dir = ctx["lineitem_dir"]

        def w_running():
            return (session.read.parquet(lineitem_dir)
                    .select("l_status", "l_shipdate",
                            "l_extendedprice")
                    .with_window("rs", "sum",
                                 partition_by=["l_status"],
                                 order_by=["l_shipdate"],
                                 value="l_extendedprice")
                    .collect())

        def w_rank():
            return (session.read.parquet(lineitem_dir)
                    .select("l_status", "l_extendedprice")
                    .with_window("rk", "rank",
                                 partition_by=["l_status"],
                                 order_by=[("l_extendedprice",
                                            False)])
                    .collect())

        def w_frame():
            return (session.read.parquet(lineitem_dir)
                    .select("l_status", "l_shipdate", "l_quantity")
                    .with_window("m", "sum",
                                 partition_by=["l_status"],
                                 order_by=["l_shipdate"],
                                 value="l_quantity",
                                 frame=(-6, 0))
                    .collect())

        def w_whole():
            return (session.read.parquet(lineitem_dir)
                    .with_window("t", "sum",
                                 partition_by=["l_status"],
                                 value="l_extendedprice")
                    .select("l_status", "t").collect())

        wb = {"rows": N_LINEITEM}
        for name, fn in (("running_sum", w_running),
                         ("rank", w_rank),
                         ("trailing7_frame", w_frame),
                         ("whole_partition_sum", w_whole)):
            stats = _time(fn, repeats=2)
            wb[f"{name}_s"] = _stat(stats)
            wb[f"{name}_mrows_per_s"] = round(
                N_LINEITEM / max(stats["median"], 1e-9) / 1e6, 2)
        # Warm-resident whole-partition window through the device
        # segment kernel (eager populate, organic routing).  The
        # host baseline above ran with the cache off; the first
        # eager run pays the transfer once.
        host_w = w_whole()
        session.conf.device_agg_min_rows = None  # back to calibrated
        session.conf.device_cache_policy = "eager"
        global_cache().clear()
        t0 = time.perf_counter()
        cold_w = w_whole()  # populate pass
        wb["whole_cold_populate_s"] = round(
            time.perf_counter() - t0, 4)
        warm_tbl = w_whole()
        st = session.last_execution_stats or {}
        ws = st.get("windows", [])
        wb["whole_warm_fired_organically"] = bool(
            ws and ws[-1]["strategy"] == "device-segment"
            and ws[-1]["resident"])
        wb["whole_warm_s"] = _stat(_time(w_whole, repeats=2))
        if not ctx["tables_equal"](warm_tbl, host_w) \
                or not ctx["tables_equal"](cold_w, host_w):
            raise SystemExit("window warm answers diverged from host")
        return {"window_bench": wb}
    finally:
        session.conf.device_cache_policy = saved_policy
        session.conf.device_agg_min_rows = saved_agg
        global_cache().clear()


def _sec_calibration() -> dict:
    """Measured host–device link physics + the thresholds the session
    derived from them (utils/calibrate.py) — on a fast link these
    route bench-scale work to the chip with no code changes."""
    from hyperspace_tpu.utils.calibrate import profile_summary

    return {"calibration": profile_summary()}


def _sec_telemetry_overhead(ctx: dict) -> dict:
    """The observability cost contract (docs/16-observability.md):
    tracing OFF must be unmeasurable on the hot path — the disabled
    ``span()`` is one module-global bool check returning a shared no-op,
    microbenched here and CORRECTNESS-GATED at < 10 µs/call (measured
    ~0.3 µs; the gate is loose only for noisy CI hosts) — and tracing ON
    is recorded as a percentage on a real indexed query (spans are
    file/operator-granular, so single-digit percent is the expectation,
    not a gate: a 2 ms query under a 5-span trace is dominated by
    timer noise)."""
    from hyperspace_tpu.telemetry import trace

    _require(ctx, "session", "queries")
    q = dict(ctx["queries"])["filter"]

    was_enabled = trace.tracing_enabled()
    try:
        # Disabled-path microbench: cost per span() call with tracing off.
        trace.disable_tracing()
        n = 200_000
        t0 = time.perf_counter()
        for _ in range(n):
            with trace.span("bench.noop"):
                pass
        disabled_ns = (time.perf_counter() - t0) / n * 1e9

        reps = max(3, REPEATS)
        q()  # warm: imports/JIT land outside the off-vs-on comparison
        t_off = _time(q, repeats=reps)
        trace.enable_tracing()
        t_on = _time(q, repeats=reps)
    finally:
        if was_enabled:
            trace.enable_tracing()
        else:
            trace.disable_tracing()
    overhead_pct = ((t_on["median"] - t_off["median"])
                    / t_off["median"] * 100.0)
    if disabled_ns > 10_000:
        # The "zero cost when disabled" contract broke: someone put real
        # work on the disabled span path.  Same policy as a diverged
        # query answer — fail the bench loudly.
        raise SystemExit(
            f"telemetry bench: disabled span() costs {disabled_ns:.0f} "
            f"ns/call (contract: unmeasurable, gate 10000 ns)")
    return {"telemetry_overhead": {
        "span_disabled_ns_per_call": round(disabled_ns, 1),
        "query_tracing_off_s": _stat(t_off),
        "query_tracing_on_s": _stat(t_on),
        "tracing_on_overhead_pct": round(overhead_pct, 2),
    }}


def _sec_advisor(ctx: dict) -> dict:
    """Index-advisor cost contract (docs/17-advisor.md): workload capture
    must be invisible on the query hot path — measured on the SF1 filter
    workload and CORRECTNESS-GATED at < 3% median overhead (the
    write-behind hit counter makes the steady state a plan walk plus a
    dict update; the gate tolerates sub-2ms absolute deltas so toy-scale
    CI runs measure timer noise, not policy) — and a 20-candidate
    what-if sweep is timed so the "which index should I build" loop has
    a recorded unit cost."""
    from hyperspace_tpu import IndexConfig
    from hyperspace_tpu.advisor import workload as wl
    from hyperspace_tpu.advisor.hypothetical import whatif

    _require(ctx, "session", "queries")
    session = ctx["session"]
    q = dict(ctx["queries"])["filter"]
    session.enable_hyperspace()
    reps = max(3, REPEATS)
    out: dict = {}
    try:
        session.conf.advisor_capture_enabled = False
        q()  # warm
        t_off = _time(q, repeats=reps)
        session.conf.advisor_capture_enabled = True
        for _ in range(4):
            q()  # seed the fingerprint record: first-sight flushes land
            # here, outside the timed reps.  FOUR seeds, deliberately:
            # the write-behind counter flushes at power-of-two totals
            # (1, 2, 4, ...), so with three seeds the 4th hit — the
            # first TIMED rep — would pay a store put + fsync inside
            # the measurement and fail the gate at REPS=1.
        t_on = _time(q, repeats=reps)
        overhead_pct = ((t_on["median"] - t_off["median"])
                        / t_off["median"] * 100.0)
        abs_ms = (t_on["median"] - t_off["median"]) * 1000.0
        out["capture_off_s"] = _stat(t_off)
        out["capture_on_s"] = _stat(t_on)
        out["capture_overhead_pct"] = round(overhead_pct, 2)
        out["capture_overhead_ms_per_query"] = round(abs_ms, 3)
        if overhead_pct > 3.0 and abs_ms > 2.0:
            # The "capture is invisible" contract broke: same policy as a
            # diverged answer — fail the bench loudly.
            raise SystemExit(
                f"advisor bench: capture overhead "
                f"{overhead_pct:.1f}% (> 3% and "
                f"{abs_ms:.2f} ms/query) on the filter workload")
        out["workload_entries"] = wl.workload_table(session.conf).num_rows

        # 20-candidate what-if sweep: the per-candidate planning cost of
        # the recommend loop, measured against the SF1 filter dataset.
        ds = ctx["ds_builders"]["filter"]()
        li = ctx["lineitem_dir"]
        cols = (["l_orderkey", "l_shipdate", "l_extendedprice",
                 "l_quantity", "l_discount", "l_status"]
                + [f"l_pad{i}" for i in range(10)])[:20]
        while len(cols) < 20:
            cols.append(cols[len(cols) % 6])
        candidates = [IndexConfig(f"adv_sweep_{i}", [c], ["l_quantity"])
                      if c != "l_quantity" else
                      IndexConfig(f"adv_sweep_{i}", [c], ["l_discount"])
                      for i, c in enumerate(cols)]
        t0 = time.perf_counter()
        used = 0
        for cand in candidates:
            report = whatif(session, ds, [cand])
            used += len(report.hypothetical_used)
        sweep_s = time.perf_counter() - t0
        out["whatif_candidates"] = len(candidates)
        out["whatif_rewrites_fired"] = used
        out["whatif_ms"] = round(sweep_s * 1000.0, 1)
        out["whatif_ms_per_candidate"] = round(
            sweep_s * 1000.0 / len(candidates), 2)
        if used == 0:
            raise SystemExit(
                "advisor bench: no what-if candidate matched the filter "
                "workload; the sweep measured nothing")
    finally:
        session.conf.advisor_capture_enabled = False
        try:
            wl.clear(session.conf)
            wl.reset_cache()
        except Exception:  # noqa: BLE001 — cleanup only
            pass
    return {"advisor": out}


def _sec_integrity(root: str) -> dict:
    """Integrity subsystem cost model (docs/15-integrity.md): what does
    digest-on-write cost the build, and how fast does a full scrub
    re-read + re-hash the index?  The same covering-index build runs with
    ``hyperspace.system.integrity.digestOnWrite`` off then on
    (``write_overhead_pct``), and ``verify_index(mode="full")`` over the
    built data gives ``scrub_mb_s``.  Self-contained (own source table,
    one throwaway session per build) so the shared SF1 session's indexes
    and caches are untouched."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    from hyperspace_tpu import Hyperspace, HyperspaceSession, IndexConfig

    n = max(10_000, N_LINEITEM // 10)
    files = 8
    src = os.path.join(root, "integrity_src")
    os.makedirs(src, exist_ok=True)
    rng = np.random.default_rng(17)
    table = pa.table({
        "k": pa.array(rng.integers(0, max(1, n // 4), size=n),
                      type=pa.int64()),
        "v1": rng.random(n),
        "v2": rng.random(n),
    })
    step = -(-n // files)
    for f in range(files):
        pq.write_table(table.slice(f * step, step),
                       os.path.join(src, f"part-{f:05d}.parquet"))

    seq = iter(range(1 << 20))
    last: dict = {}

    def build(digest_on: bool) -> None:
        s = HyperspaceSession(system_path=os.path.join(
            root, f"integrity_ix_{next(seq)}"))
        s.conf.num_buckets = NUM_BUCKETS
        s.conf.integrity_digest_on_write = digest_on
        hs = Hyperspace(s)
        hs.create_index(s.read.parquet(src),
                        IndexConfig("iix", ["k"], ["v1", "v2"]))
        last["session"], last["hs"] = s, hs

    reps = min(3, REPEATS)
    build(False)  # untimed warmup: JIT/import costs land here, not in
    # the off-median of a comparison measuring a few percent
    t_off = _time(lambda: build(False), repeats=reps)
    t_on = _time(lambda: build(True), repeats=reps)
    overhead_pct = ((t_on["median"] - t_off["median"])
                    / t_off["median"] * 100.0)

    # Scrub the last (digest-on) build: every file re-read and re-hashed.
    session, hs = last["session"], last["hs"]
    entry = session.index_collection_manager.get_index("iix")
    infos = entry.content.file_infos()
    index_mb = sum(f.size for f in infos) / 1e6
    report: dict = {}

    def scrub() -> None:
        report["table"] = hs.verify_index("iix", mode="full")

    t_scrub = _time(scrub, repeats=reps)
    statuses = report["table"].column("status").to_pylist()
    bad = sorted({st for st in statuses if st != "ok"})
    if bad:
        # A freshly built index scrubbing anything but clean means a
        # writer path skipped digest recording (or worse): correctness
        # gate, same policy as a diverged query answer.
        raise SystemExit(f"integrity bench: full scrub of a fresh build "
                         f"not clean: {bad}")
    return {"integrity": {
        "rows": n,
        "index_files": len(infos),
        "index_mb": round(index_mb, 2),
        "build_digest_off_s": _stat(t_off),
        "build_digest_on_s": _stat(t_on),
        "write_overhead_pct": round(overhead_pct, 2),
        "scrub_full_s": _stat(t_scrub),
        "scrub_mb_s": round(index_mb / t_scrub["median"], 1),
    }}


def _sec_build_profile(root: str) -> dict:
    """Build-pipeline profiler cost contract (docs/16-observability.md):
    the SAME covering-index build runs with
    ``hyperspace.system.buildProfiling.enabled`` off then on, and the
    delta is CORRECTNESS-GATED at < 3% (with a 50 ms absolute floor so
    toy-scale CI runs measure timer noise, not policy).  The profiled
    build's report is then audited against reality — per-phase seconds
    must sum to within 10% of the action wall time, and the
    bytes-written figure must match the bytes actually on disk — and a
    spill-forced build must attribute its run traffic.  Self-contained
    (own source, throwaway sessions), like the integrity section."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    from hyperspace_tpu import Hyperspace, HyperspaceSession, IndexConfig

    n = max(50_000, N_LINEITEM // 10)
    files = 8
    src = os.path.join(root, "buildprof_src")
    os.makedirs(src, exist_ok=True)
    rng = np.random.default_rng(29)
    table = pa.table({
        "k": pa.array(rng.integers(0, max(1, n // 4), size=n),
                      type=pa.int64()),
        "v1": rng.random(n),
        "v2": rng.random(n),
    })
    step = -(-n // files)
    for f in range(files):
        pq.write_table(table.slice(f * step, step),
                       os.path.join(src, f"part-{f:05d}.parquet"))

    seq = iter(range(1 << 20))
    last: dict = {}

    def build(profiling_on: bool, batch_rows: Optional[int] = None) -> None:
        s = HyperspaceSession(system_path=os.path.join(
            root, f"buildprof_ix_{next(seq)}"))
        s.conf.num_buckets = NUM_BUCKETS
        s.conf.build_profiling_enabled = profiling_on
        if batch_rows is not None:
            # The spill audit targets the single-chip EXTERNAL build —
            # a multi-device mesh would route to the distributed kernel,
            # which never spills.
            s.conf.device_batch_rows = batch_rows
            s.conf.parallel_build = "off"
        hs = Hyperspace(s)
        hs.create_index(s.read.parquet(src),
                        IndexConfig("bpix", ["k"], ["v1", "v2"]))
        last["session"], last["hs"] = s, hs

    reps = min(3, REPEATS)
    build(True)  # untimed warmup: JIT/import costs land here
    t_off = _time(lambda: build(False), repeats=reps)
    t_on = _time(lambda: build(True), repeats=reps)
    overhead_pct = ((t_on["median"] - t_off["median"])
                    / t_off["median"] * 100.0)
    abs_ms = (t_on["median"] - t_off["median"]) * 1000.0
    if overhead_pct > 3.0 and abs_ms > 50.0:
        # The "profiling is invisible" contract broke: same policy as a
        # diverged answer — fail the bench loudly.
        raise SystemExit(
            f"build_profile bench: profiling overhead {overhead_pct:.1f}% "
            f"(> 3% and {abs_ms:.1f} ms) on the covering-index build")

    # Audit the last (profiled) build's report against reality.
    session, hs = last["session"], last["hs"]
    report = hs.last_build_report()
    if report is None or report.action != "CreateAction":
        raise SystemExit("build_profile bench: no build report after a "
                         "profiled create_index")
    coverage = report.phase_total_s() / max(report.wall_s, 1e-9)
    if not 0.90 <= coverage <= 1.10:
        raise SystemExit(
            f"build_profile bench: per-phase seconds sum to "
            f"{coverage * 100:.1f}% of the action wall time "
            f"(contract: within 10%); phases={report.to_dict()['phases_s']}")
    entry = session.index_collection_manager.get_index("bpix")
    on_disk = sum(f.size for f in entry.content.file_infos())
    if report.bytes_written != on_disk:
        raise SystemExit(
            f"build_profile bench: report says {report.bytes_written} "
            f"index bytes written but {on_disk} are on disk")

    # Spill-forced build: the external path must attribute its run
    # traffic (spill_route/spill_finish phases + spill bytes/run counts).
    build(True, batch_rows=max(1024, n // 8))
    spill_report = last["hs"].last_build_report()
    if spill_report.spill_bytes <= 0 or spill_report.spill_runs <= 0:
        raise SystemExit("build_profile bench: spill-forced build "
                         "reported no spill traffic")
    ledger_rows = last["hs"].perf_history().num_rows

    return {"build_profile": {
        "rows": n,
        "build_profiling_off_s": _stat(t_off),
        "build_profiling_on_s": _stat(t_on),
        "profiling_overhead_pct": round(overhead_pct, 2),
        "profiling_overhead_ms": round(abs_ms, 2),
        "phase_coverage_pct": round(coverage * 100.0, 1),
        "report": report.to_dict(),
        "spill_report": spill_report.to_dict(),
        "perf_ledger_rows": ledger_rows,
    }}


def _sec_timeline(root: str) -> dict:
    """Timeline profiler cost contract + gap-analysis proof
    (docs/16-observability.md): the SAME covering-index build runs with
    ``hyperspace.system.timeline.enabled`` off then on (recorder +
    background memory sampler + kernel seams), and the delta is
    CORRECTNESS-GATED at < 3% (50 ms absolute floor, like
    build_profile).  A spill-forced build must then yield a busy-
    fraction matrix with the read and spill lanes present — the
    "read idle while spill busy" number ROADMAP item 2's prefetch
    rewrite is accepted against — and the Perfetto export + doctor()
    must produce a loadable trace and a graded health status.
    Self-contained (own source, throwaway sessions)."""
    import json as _json

    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    from hyperspace_tpu import Hyperspace, HyperspaceSession, IndexConfig
    from hyperspace_tpu.telemetry import timeline as _timeline

    n = max(50_000, N_LINEITEM // 10)
    files = 8
    src = os.path.join(root, "timeline_src")
    os.makedirs(src, exist_ok=True)
    rng = np.random.default_rng(31)
    table = pa.table({
        "k": pa.array(rng.integers(0, max(1, n // 4), size=n),
                      type=pa.int64()),
        "v1": rng.random(n),
        "v2": rng.random(n),
    })
    step = -(-n // files)
    for f in range(files):
        pq.write_table(table.slice(f * step, step),
                       os.path.join(src, f"part-{f:05d}.parquet"))

    seq = iter(range(1 << 20))
    last: dict = {}

    def build(timeline_on: bool, batch_rows: Optional[int] = None) -> None:
        if not timeline_on:
            # The enable flag is module-global and sticky (conf never
            # force-disables, same contract as tracing).
            _timeline.disable_timeline()
        s = HyperspaceSession(system_path=os.path.join(
            root, f"timeline_ix_{next(seq)}"))
        s.conf.num_buckets = NUM_BUCKETS
        s.conf.timeline_enabled = timeline_on
        if batch_rows is not None:
            s.conf.device_batch_rows = batch_rows
            s.conf.parallel_build = "off"  # the spill path is single-chip
        hs = Hyperspace(s)
        hs.create_index(s.read.parquet(src),
                        IndexConfig("tlix", ["k"], ["v1", "v2"]))
        last["session"], last["hs"] = s, hs

    reps = min(3, REPEATS)
    try:
        build(True)  # untimed warmup: JIT/import costs land here
        t_off = _time(lambda: build(False), repeats=reps)
        t_on = _time(lambda: build(True), repeats=reps)
        overhead_pct = ((t_on["median"] - t_off["median"])
                        / t_off["median"] * 100.0)
        abs_ms = (t_on["median"] - t_off["median"]) * 1000.0
        if overhead_pct > 3.0 and abs_ms > 50.0:
            raise SystemExit(
                f"timeline bench: recorder+sampler overhead "
                f"{overhead_pct:.1f}% (> 3% and {abs_ms:.1f} ms) on the "
                f"covering-index build")

        # Spill-forced build: the gap analysis must see the read and
        # spill lanes and produce the pairwise idle-while-busy matrix.
        build(True, batch_rows=max(1024, n // 8))
        hs = last["hs"]
        report = hs.last_build_report()
        lanes = report.lane_report()
        matrix = lanes.get("idle_while_busy", {})
        for lane_name in ("read", "spill_route"):
            if lane_name not in lanes.get("lanes", {}):
                raise SystemExit(
                    f"timeline bench: spill-forced build recorded no "
                    f"{lane_name!r} lane; lanes={sorted(lanes.get('lanes', {}))}")
        read_idle_while_spill = matrix["read"]["spill_route"]

        # Perfetto export must be loadable trace-event JSON.
        trace_path = os.path.join(root, "timeline_export.json")
        hs.export_timeline(trace_path)
        with open(trace_path, "r", encoding="utf-8") as f:
            payload = _json.load(f)
        events = payload.get("traceEvents", [])
        if not events:
            raise SystemExit("timeline bench: Perfetto export produced "
                             "no trace events")

        # Doctor: graded status over the section's own (healthy) tree.
        health = hs.doctor()
        if health.status not in ("ok", "warn", "crit"):
            raise SystemExit(
                f"timeline bench: doctor returned {health.status!r}")
    finally:
        # Later sections (serving, sf10) must not pay the recorder.
        _timeline.disable_timeline()
        _timeline.reset()

    return {"timeline": {
        "rows": n,
        "timeline_off_s": _stat(t_off),
        "timeline_on_s": _stat(t_on),
        "overhead_pct": round(overhead_pct, 2),
        "overhead_ms": round(abs_ms, 2),
        "busy_fractions": {lane: stats["busy_fraction"]
                           for lane, stats in lanes["lanes"].items()},
        "read_idle_while_spill": read_idle_while_spill,
        "memory_samples": len(report.memory_samples),
        "phase_peak_rss_mb": report.phase_memory_mb(),
        "trace_events": len(events),
        "doctor_status": health.status,
    }}


def _sec_build_pipeline(root: str) -> dict:
    """Overlapped-builder acceptance (docs/13-benchmarking.md): the
    SAME spill-forced covering-index build runs with
    ``hyperspace.index.build.pipeline.enabled`` off (the forced-serial
    reference: inline reads, inline routing, sequential finalize) then
    on (prefetch + route workers + streaming bucket-group finalize).
    The two index trees must be BIT-equal — the pipeline may change
    scheduling, never layout; a divergence aborts the bench like a
    wrong answer.  On multi-core hosts the overlapped build is
    correctness-gated >= 1.5x the serial one; single-core hosts record
    the ratio without gating (thread overlap has nothing to overlap ON
    there — the fused-kernel and spill-format wins land in BOTH
    timings).  A final timeline-enabled pipelined run records the busy
    matrix (``read_idle_while_spill``) and the two stall phases, so
    ``--compare`` can watch the overlap itself regress, not just wall
    clock.  Self-contained (own source, throwaway sessions)."""
    import hashlib
    from collections import defaultdict

    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    from hyperspace_tpu import Hyperspace, HyperspaceSession, IndexConfig
    from hyperspace_tpu.io.parquet import bucket_id_of_file
    from hyperspace_tpu.telemetry import timeline as _timeline

    n = max(50_000, N_LINEITEM // 10)
    files = 8
    src = os.path.join(root, "buildpipe_src")
    os.makedirs(src, exist_ok=True)
    rng = np.random.default_rng(37)
    table = pa.table({
        "k": pa.array(rng.integers(0, max(1, n // 4), size=n),
                      type=pa.int64()),
        "v1": rng.random(n),
        "v2": rng.random(n),
    })
    step = -(-n // files)
    for f in range(files):
        pq.write_table(table.slice(f * step, step),
                       os.path.join(src, f"part-{f:05d}.parquet"))

    seq = iter(range(1 << 20))
    last: dict = {}

    def build(pipelined: bool, timeline_on: bool = False) -> None:
        s = HyperspaceSession(system_path=os.path.join(
            root, f"buildpipe_ix_{next(seq)}"))
        s.conf.num_buckets = NUM_BUCKETS
        s.conf.device_batch_rows = max(1024, n // 8)  # force the spill
        s.conf.parallel_build = "off"  # the spill path is single-chip
        s.conf.build_pipeline_enabled = pipelined
        if timeline_on:
            s.conf.timeline_enabled = True
        hs = Hyperspace(s)
        hs.create_index(s.read.parquet(src),
                        IndexConfig("bpx", ["k"], ["v1", "v2"]))
        last["session"], last["hs"] = s, hs

    def digests() -> dict:
        entry = last["session"].index_collection_manager.get_index("bpx")
        out = defaultdict(list)
        for f in entry.content.file_infos():
            with open(f.name, "rb") as fh:
                out[bucket_id_of_file(f.name)].append(
                    hashlib.sha256(fh.read()).hexdigest())
        return {b: sorted(d) for b, d in out.items()}

    reps = min(3, REPEATS)
    build(True)  # untimed warmup: JIT/import costs land here
    t_serial = _time(lambda: build(False), repeats=reps)
    serial_digests = digests()
    t_piped = _time(lambda: build(True), repeats=reps)
    piped_digests = digests()
    if serial_digests != piped_digests:
        raise SystemExit(
            "build_pipeline bench: the overlapped builder's index tree "
            "diverged from the forced-serial reference — the pipeline "
            "may change scheduling, never layout")
    speedup = t_serial["median"] / max(t_piped["median"], 1e-9)
    cores = os.cpu_count() or 1
    gated = cores >= 2
    if gated and speedup < 1.5:
        raise SystemExit(
            f"build_pipeline bench: overlapped build only {speedup:.2f}x "
            f"the serial reference on a {cores}-core host "
            f"(correctness gate: >= 1.5x)")

    # Busy matrix + stall phases off one timeline-enabled pipelined run.
    try:
        build(True, timeline_on=True)
        report = last["hs"].last_build_report()
        lanes = report.lane_report()
        matrix = lanes.get("idle_while_busy", {})
        read_idle_while_spill = \
            matrix.get("read", {}).get("spill_route", None)
    finally:
        # Later sections (serving, sf10) must not pay the recorder.
        _timeline.disable_timeline()
        _timeline.reset()

    return {"build_pipeline": {
        "rows": n,
        "cores": cores,
        "serial_build_s": _stat(t_serial),
        "pipelined_build_s": _stat(t_piped),
        "pipeline_speedup_x": round(speedup, 3),
        "speedup_gated": gated,
        "bit_equal": True,
        "read_idle_while_spill": read_idle_while_spill,
        "busy_fractions": {lane: stats["busy_fraction"]
                           for lane, stats in
                           lanes.get("lanes", {}).items()},
        "prefetch_stall_s": round(
            report.phases.get("prefetch", 0.0), 4),
        "finalize_tail_s": round(
            report.phases.get("finalize", 0.0), 4),
        "spill_route_s": round(report.phases.get("spill_route", 0.0), 4),
        "spill_finish_s": round(
            report.phases.get("spill_finish", 0.0), 4),
    }}


def _multichip_worker() -> None:
    """Subprocess body of the ``multichip`` section: one device count per
    process (jax locks the virtual device count at backend init, so 1-
    vs 8-device legs cannot share an interpreter).  Reads the spec from
    ``HS_MULTICHIP_SPEC``, builds the sf-scaled index (spill-forced, so
    the mesh-sharded route is what scales), runs the hybrid-join+agg
    query, and writes timings + per-bucket sha256 digests + the
    canonicalized answer digest to the spec's ``out`` file."""
    import hashlib
    import json as _json
    from collections import defaultdict

    spec = _json.loads(os.environ["HS_MULTICHIP_SPEC"])
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from hyperspace_tpu import Hyperspace, HyperspaceSession, IndexConfig, col
    from hyperspace_tpu.io.parquet import bucket_id_of_file

    assert len(jax.devices()) == int(spec["devices"]), \
        (len(jax.devices()), spec["devices"])

    def make_session(tag: str):
        s = HyperspaceSession(system_path=os.path.join(
            spec["root"], f"ix_{spec['devices']}_{tag}"))
        s.conf.num_buckets = int(spec["num_buckets"])
        s.conf.device_batch_rows = int(spec["batch_rows"])
        s.conf.device_build_min_rows = 0   # force the device/mesh route
        s.conf.mesh_enabled = "auto"       # 1 device => no mesh (gate)
        s.conf.mesh_join_min_rows = 1
        s.conf.mesh_agg_min_rows = 1
        return s

    build_s = []
    last = {}
    for rep in range(int(spec["reps"])):
        s = make_session(str(rep))
        hs = Hyperspace(s)
        t0 = time.perf_counter()
        hs.create_index(s.read.parquet(spec["fact"]),
                        IndexConfig("mf", ["k"], ["g", "v"]))
        build_s.append(time.perf_counter() - t0)
        last["session"], last["hs"] = s, hs
    s, hs = last["session"], last["hs"]
    report = hs.last_build_report()
    digests = defaultdict(list)
    entry = s.index_collection_manager.get_index("mf")
    for f in entry.content.file_infos():
        with open(f.name, "rb") as fh:
            digests[str(bucket_id_of_file(f.name))].append(
                hashlib.sha256(fh.read()).hexdigest())

    hs.create_index(s.read.parquet(spec["dims"]),
                    IndexConfig("md", ["k"], ["w"]))
    s.enable_hyperspace()
    fact = s.read.parquet(spec["fact"])
    dims = s.read.parquet(spec["dims"])

    def query():
        return (fact.join(dims, col("k") == col("k"))
                .group_by("g")
                .agg(sv=("v", "sum"), sw=("w", "sum"), c=("", "count_all"))
                .collect())

    out_table = query()  # warm (plan cache, jit)
    query_s = []
    for _ in range(int(spec["reps"])):
        t0 = time.perf_counter()
        out_table = query()
        query_s.append(time.perf_counter() - t0)
    strategies = [j["strategy"] for j in s.last_execution_stats["joins"]]
    rows = out_table.sort_by([("g", "ascending")]).to_pydict()
    answer_sha = hashlib.sha256(_json.dumps(
        rows, sort_keys=True, default=str).encode()).hexdigest()
    payload = {
        "devices": int(spec["devices"]),
        "build_s": build_s,
        "query_s": query_s,
        "bucket_digests": {b: sorted(d) for b, d in digests.items()},
        "answer_sha": answer_sha,
        "join_strategies": strategies,
        "mesh_devices": report.mesh_devices,
        "spill_bytes": report.spill_bytes,
    }
    # hslint: allow[io-seam] worker->parent result handoff, not index data
    with open(spec["out"], "w", encoding="utf-8") as f:
        _json.dump(payload, f)


def _sec_multichip(root: str) -> dict:
    """Mesh scale-out acceptance (ROADMAP item 1): the SAME sf-scaled
    spill build and hybrid-join+aggregate query run at 1 device and at 8
    virtual CPU devices (``--xla_force_host_platform_device_count`` in a
    fresh subprocess per leg — jax locks the device count at backend
    init), recording per-device-count medians and the speedup ratios for
    ``--compare``.  Correctness-gated: the 8-device index tree must be
    BYTE-identical to the 1-device one (per-bucket sha256) and the query
    answers must match exactly — a divergence aborts the bench like a
    wrong answer.  The speedup itself is gated only on hosts with >= 8
    cores (virtual devices share cores below that; the ratio is still
    recorded for trend watching)."""
    import json as _json
    import statistics
    import subprocess

    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    n = max(20_000, N_LINEITEM // 10)
    files = 4
    src_root = os.path.join(root, "multichip")
    fact_dir = os.path.join(src_root, "fact")
    dims_dir = os.path.join(src_root, "dims")
    os.makedirs(fact_dir, exist_ok=True)
    os.makedirs(dims_dir, exist_ok=True)
    rng = np.random.default_rng(41)
    n_keys = max(64, n // 16)
    fact = pa.table({
        "k": pa.array(rng.integers(0, n_keys, size=n), type=pa.int64()),
        "g": pa.array(rng.integers(0, 11, size=n), type=pa.int64()),
        "v": pa.array(rng.integers(0, 1000, size=n), type=pa.int64()),
    })
    step = -(-n // files)
    for f in range(files):
        pq.write_table(fact.slice(f * step, step),
                       os.path.join(fact_dir, f"part-{f:05d}.parquet"))
    pq.write_table(pa.table({
        "k": pa.array(np.arange(n_keys), type=pa.int64()),
        "w": pa.array(rng.integers(0, 100, size=n_keys),
                      type=pa.int64()),
    }), os.path.join(dims_dir, "dims.parquet"))

    bench_dir = os.path.dirname(os.path.abspath(__file__))
    reps = min(3, REPEATS)
    legs = {}
    for ndev in (1, 8):
        out_path = os.path.join(src_root, f"result_{ndev}.json")
        env = dict(os.environ)
        env.pop("HS_BENCH_BUDGET", None)  # the child is not a bench run
        flags = env.get("XLA_FLAGS", "")
        flag = f"--xla_force_host_platform_device_count={ndev}"
        if "xla_force_host_platform_device_count" in flags:
            import re as _re

            flags = _re.sub(
                r"--xla_force_host_platform_device_count=\d+", flag, flags)
        else:
            flags = f"{flags} {flag}".strip()
        env.update(
            JAX_PLATFORMS="cpu", HS_XLA_CACHE="0", HS_CALIBRATE="0",
            XLA_FLAGS=flags,
            HS_MULTICHIP_SPEC=_json.dumps({
                "devices": ndev, "root": src_root, "fact": fact_dir,
                "dims": dims_dir, "reps": reps, "num_buckets": NUM_BUCKETS,
                "batch_rows": max(4096, n // 8), "out": out_path,
            }))
        proc = subprocess.run(
            [sys.executable, "-c",
             "import bench; bench._multichip_worker()"],
            cwd=bench_dir, env=env, capture_output=True, text=True,
            timeout=1800)
        if proc.returncode != 0:
            raise RuntimeError(
                f"multichip worker ({ndev} devices) failed:\n"
                + proc.stderr[-3000:])
        with open(out_path, "r", encoding="utf-8") as f:
            legs[ndev] = _json.load(f)

    if legs[1]["bucket_digests"] != legs[8]["bucket_digests"]:
        raise SystemExit(
            "multichip bench: the 8-device index tree diverged from the "
            "1-device one — the mesh may change where work runs, never "
            "the layout")
    if legs[1]["answer_sha"] != legs[8]["answer_sha"]:
        raise SystemExit(
            "multichip bench: the 8-device query answer diverged from "
            "the 1-device one")
    if "bucketed-mesh" not in legs[8]["join_strategies"] \
            and not any("mesh" in st for st in legs[8]["join_strategies"]):
        raise SystemExit(
            f"multichip bench: the 8-device leg never dispatched a mesh "
            f"join (strategies: {legs[8]['join_strategies']})")

    med = statistics.median
    build_speedup = med(legs[1]["build_s"]) / max(
        med(legs[8]["build_s"]), 1e-9)
    query_speedup = med(legs[1]["query_s"]) / max(
        med(legs[8]["query_s"]), 1e-9)
    cores = os.cpu_count() or 1
    gated = cores >= 8
    if gated and build_speedup < 1.5:
        raise SystemExit(
            f"multichip bench: 8-device build only {build_speedup:.2f}x "
            f"the 1-device build on a {cores}-core host "
            f"(correctness gate: >= 1.5x)")
    return {"multichip": {
        "rows": n,
        "cores": cores,
        "reps": reps,
        "build_s_1dev": round(med(legs[1]["build_s"]), 4),
        "build_s_8dev": round(med(legs[8]["build_s"]), 4),
        "query_s_1dev": round(med(legs[1]["query_s"]), 4),
        "query_s_8dev": round(med(legs[8]["query_s"]), 4),
        "build_speedup_x": round(build_speedup, 3),
        "query_speedup_x": round(query_speedup, 3),
        "speedup_gated": gated,
        "bit_equal": True,
        "mesh_devices_8dev": legs[8]["mesh_devices"],
        "join_strategies_8dev": legs[8]["join_strategies"],
    }}


def _sec_multihost(root: str) -> dict:
    """Fault-tolerant multi-host build acceptance (docs/21): the SAME
    source builds at hosts=1 and hosts=2 through the claim pipeline,
    recording per-phase claim-span medians (route / finalize — measured
    from the claim records themselves, so subprocess interpreter spin-up
    never pollutes the ratio) and the route speedup for ``--compare``.
    Correctness-gated: every leg's index tree must be BYTE-identical to
    the ordinary single-process build (per-bucket sha256).  The speedup
    is gated only on hosts with >= 4 cores (two hosts + coordinator
    share cores below that; the ratio is still recorded).  Ends with a
    recovery drill — one host SIGKILLed once the claim table is live —
    whose survivor must finish bit-equal with exactly ONE journaled
    commit."""
    import hashlib
    import statistics
    import threading

    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    from hyperspace_tpu import Hyperspace, HyperspaceSession, IndexConfig
    from hyperspace_tpu.io.parquet import bucket_id_of_file
    from hyperspace_tpu.lifecycle import journal as lifecycle_journal
    from hyperspace_tpu.lifecycle.lease import WorkClaims
    from hyperspace_tpu.parallel import multihost_build

    n = max(24_000, N_LINEITEM // 60)
    files = 4
    mh_root = os.path.join(root, "multihost")
    src = os.path.join(mh_root, "src")
    os.makedirs(src, exist_ok=True)
    rng = np.random.default_rng(43)
    table = pa.table({
        "k": pa.array(rng.integers(0, max(64, n // 16), size=n),
                      type=pa.int64()),
        "g": pa.array(rng.integers(0, 11, size=n), type=pa.int64()),
        "v": pa.array(rng.integers(0, 1000, size=n), type=pa.int64()),
    })
    step = -(-n // files)
    for f in range(files):
        pq.write_table(table.slice(f * step, step),
                       os.path.join(src, f"part-{f:05d}.parquet"))

    def build(tag: str, hosts: int):
        sess = HyperspaceSession(
            system_path=os.path.join(mh_root, f"ix_{tag}"))
        sess.conf.num_buckets = NUM_BUCKETS
        sess.conf.device_batch_rows = max(4096, n // 12)
        sess.conf.device_build_min_rows = 0
        sess.conf.multihost_build_hosts = hosts
        sess.conf.multihost_build_claim_ttl_s = 2.0
        sess.conf.multihost_build_poll_s = 0.02
        hs = Hyperspace(sess)
        t0 = time.perf_counter()
        hs.create_index(sess.read.parquet(src),
                        IndexConfig("mh", ["k"], ["g", "v"]))
        return sess, hs, time.perf_counter() - t0

    def digests(sess):
        entry = sess.index_collection_manager.get_index("mh")
        out: dict = {}
        for fi in entry.content.file_infos():
            with open(fi.name, "rb") as fh:
                out.setdefault(bucket_id_of_file(fi.name), []).append(
                    hashlib.sha256(fh.read()).hexdigest())
        return {b: sorted(v) for b, v in out.items()}

    base_sess, _base_hs, base_wall = build("base", 0)
    want = digests(base_sess)

    reps = min(3, REPEATS)
    walls: dict = {h: {"route": [], "finalize": [], "total": []}
                   for h in (1, 2)}
    for hosts in (1, 2):
        for rep in range(reps):
            sess, hs, _wall = build(f"{hosts}h_{rep}", hosts)
            if digests(sess) != want:
                raise SystemExit(
                    f"multihost bench: the {hosts}-host index tree "
                    "diverged from the single-process one — claims move "
                    "work between hosts, never the layout")
            props = hs.last_build_report().properties
            walls[hosts]["route"].append(
                float(props["multihost_route_wall_s"]))
            walls[hosts]["finalize"].append(
                float(props["multihost_finalize_wall_s"]))
            walls[hosts]["total"].append(
                float(props["multihost_total_wall_s"]))

    med = statistics.median
    route_speedup = med(walls[1]["route"]) / max(
        med(walls[2]["route"]), 1e-9)
    cores = os.cpu_count() or 1
    gated = cores >= 4
    if gated and route_speedup < 1.4:
        raise SystemExit(
            f"multihost bench: 2-host route only {route_speedup:.2f}x "
            f"the 1-host route on a {cores}-core host "
            f"(scaling gate: >= 1.4x)")

    # Recovery drill: SIGKILL host 0 once the claim table is live; the
    # survivor must reclaim its expired work and finish bit-equal, and
    # the coordinator must commit exactly once (journal-proven).
    killed: dict = {}
    orig = multihost_build.spawn_hosts

    def spawn_and_kill(conf, build_id, n_hosts):
        procs = orig(conf, build_id, n_hosts)
        store = multihost_build._store(conf, build_id)

        def reaper():
            deadline = time.monotonic() + 30.0
            while time.monotonic() < deadline:
                if store.list_keys(WorkClaims.PREFIX):
                    break
                time.sleep(0.02)
            p = procs[0]
            if p.poll() is None:
                try:
                    os.kill(p.pid, signal.SIGKILL)
                except OSError:
                    pass
            killed["pid"] = p.pid

        threading.Thread(target=reaper, daemon=True).start()
        return procs

    multihost_build.spawn_hosts = spawn_and_kill
    try:
        t0 = time.perf_counter()
        drill_sess, _drill_hs, _ = build("drill", 2)
        recovery_wall = time.perf_counter() - t0
    finally:
        multihost_build.spawn_hosts = orig
    if not killed:
        raise SystemExit(
            "multihost bench: the recovery drill never killed a host")
    if digests(drill_sess) != want:
        raise SystemExit(
            "multihost bench: the post-SIGKILL survivor build diverged "
            "from the single-process one — recovery must be bit-exact")
    commits = sum(
        1 for r in lifecycle_journal.records(drill_sess.conf)
        if r.get("decision") == "claim" and r.get("mode") == "commit")
    if commits != 1:
        raise SystemExit(
            f"multihost bench: expected exactly one journaled commit "
            f"after the recovery drill, saw {commits}")

    return {"multihost": {
        "rows": n,
        "cores": cores,
        "reps": reps,
        "build_s_inprocess": round(base_wall, 4),
        "route_s_1host": round(med(walls[1]["route"]), 4),
        "route_s_2host": round(med(walls[2]["route"]), 4),
        "finalize_s_1host": round(med(walls[1]["finalize"]), 4),
        "finalize_s_2host": round(med(walls[2]["finalize"]), 4),
        "total_s_2host": round(med(walls[2]["total"]), 4),
        "route_speedup_x": round(route_speedup, 3),
        "speedup_gated": gated,
        "bit_equal": True,
        "recovery_wall_s": round(recovery_wall, 4),
        "recovery_commits": commits,
    }}


def _sec_serving(ctx: dict) -> dict:
    """Serving layer under concurrent clients (docs/07-interop.md;
    ROADMAP item 2 acceptance): N clients drive a mixed filter/join/agg
    workload through the admission-controlled QueryServer — sustained
    QPS, p50/p99 latency, plan-cache hit rate — then a deliberate
    overload burst against a 1-worker/1-slot server records the shed
    rate.  Correctness-gated: every accepted answer must match direct
    execution, the repeat-heavy mix must HIT the plan cache, and the
    overload burst must shed with retryable BUSY errors rather than
    hang."""
    import threading

    from hyperspace_tpu.interop.server import (
        QueryClient,
        QueryServer,
        ServerBusyError,
        request_query,
    )
    from hyperspace_tpu.telemetry import metrics as _metrics

    _require(ctx, "session", "lineitem_dir", "orders_dir")
    session = ctx["session"]
    session.enable_hyperspace()
    li, orders = ctx["lineitem_dir"], ctx["orders_dir"]
    keys = [N_ORDERS // 7, N_ORDERS // 3, N_ORDERS // 2]
    templates = [
        *({"source": {"format": "parquet", "path": li},
           "filter": {"op": "==", "col": "l_orderkey", "value": k},
           "select": ["l_orderkey", "l_quantity"]} for k in keys),
        {"source": {"format": "parquet", "path": li},
         "group_by": ["l_status"],
         "aggs": {"q": ["l_quantity", "sum"]}},
        {"source": {"format": "parquet", "path": orders},
         "filter": {"op": "<", "col": "o_totalprice", "value": 5000.0},
         "join": {"source": {"format": "parquet", "path": li},
                  "on": {"op": "==", "col": "o_orderkey",
                         "right_col": "l_orderkey"}},
         "group_by": ["o_shippriority"],
         "aggs": {"q": ["l_quantity", "sum"]}},
    ]
    from hyperspace_tpu.interop import dataset_from_spec

    expected_rows = [dataset_from_spec(session, dict(t)).collect().num_rows
                     for t in templates]

    n_clients, reqs_per_client = 6, 12
    latencies: list = []
    errors: list = []
    lock = threading.Lock()

    def snap(*names):
        return {n: _metrics.registry().counter(n) for n in names}

    before = snap("serve.plan_cache.hits", "serve.plan_cache.misses",
                  "serve.shed")

    def client(ci: int, address) -> None:
        try:
            with QueryClient(address) as qc:
                for r in range(reqs_per_client):
                    ti = (ci + r) % len(templates)
                    t0 = time.perf_counter()
                    out = qc.query(dict(templates[ti]))
                    dt = time.perf_counter() - t0
                    with lock:
                        latencies.append(dt)
                        if out.num_rows != expected_rows[ti]:
                            errors.append(
                                f"client {ci} req {r}: {out.num_rows} rows"
                                f" != {expected_rows[ti]}")
        except Exception as e:  # noqa: BLE001 — gate below reports it
            with lock:
                errors.append(f"client {ci}: {type(e).__name__}: {e}")

    wall0 = time.perf_counter()
    with QueryServer(session) as server:
        threads = [threading.Thread(target=client,
                                    args=(i, server.address))
                   for i in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=max(60.0, SECTION_CAP_S or 120.0))
        hung = [t for t in threads if t.is_alive()]
    wall = time.perf_counter() - wall0
    if hung:
        raise SystemExit("serving bench: client threads hung — the "
                         "no-hang contract is broken")
    if errors:
        raise SystemExit(f"serving bench: diverged answers/errors under "
                         f"concurrency: {errors[:5]}")
    after = snap("serve.plan_cache.hits", "serve.plan_cache.misses",
                 "serve.shed")
    hits = after["serve.plan_cache.hits"] - before["serve.plan_cache.hits"]
    misses = (after["serve.plan_cache.misses"]
              - before["serve.plan_cache.misses"])
    if hits <= 0:
        raise SystemExit("serving bench: zero plan-cache hits on a "
                         "repeat-heavy workload")
    lat = sorted(latencies)

    def pct(p: float) -> float:
        return lat[min(len(lat) - 1, int(p * len(lat)))]

    # Overload burst: clients ≫ workers + queue — the robustness
    # headline.  Shed requests come back fast with retryable BUSY; the
    # accepted ones still answer correctly.
    saved = (session.conf.serving_workers, session.conf.serving_queue_depth)
    session.conf.serving_workers = 1
    session.conf.serving_queue_depth = 1
    busy, ok_rows, burst_errors = [], [], []
    try:
        with QueryServer(session) as server2:
            def burst_client() -> None:
                try:
                    out = request_query(server2.address,
                                        dict(templates[-1]))
                    with lock:
                        ok_rows.append(out.num_rows)
                except ServerBusyError as e:
                    with lock:
                        busy.append(e)
                except Exception as e:  # noqa: BLE001
                    with lock:
                        burst_errors.append(f"{type(e).__name__}: {e}")

            burst = [threading.Thread(target=burst_client)
                     for _ in range(12)]
            for t in burst:
                t.start()
            for t in burst:
                t.join(timeout=120)
            if any(t.is_alive() for t in burst):
                raise SystemExit("serving bench: overload burst hung")
    finally:
        session.conf.serving_workers, session.conf.serving_queue_depth = \
            saved
    if burst_errors:
        raise SystemExit(f"serving bench: overload burst saw non-BUSY "
                         f"failures: {burst_errors[:5]}")
    if not busy:
        raise SystemExit("serving bench: 12 concurrent clients against a "
                         "1-worker/1-slot server shed nothing")
    if any(r != expected_rows[-1] for r in ok_rows):
        raise SystemExit("serving bench: overload burst returned a torn "
                         "or wrong frame")
    total = len(latencies)
    return {"serving": {
        "clients": n_clients,
        "requests": total,
        "sustained_qps": round(total / wall, 2),
        "latency_p50_ms": round(pct(0.50) * 1000.0, 2),
        "latency_p99_ms": round(pct(0.99) * 1000.0, 2),
        "plan_cache_hit_rate": round(hits / max(1, hits + misses), 4),
        "overload_burst_clients": 12,
        "overload_shed": len(busy),
        "overload_served": len(ok_rows),
        "shed_rate": round(len(busy) / 12.0, 4),
    }}


def _sec_flight_recorder(ctx: dict) -> dict:
    """Flight-recorder cost contract (docs/16-observability.md): the
    tail-sampled request recorder must be invisible on the serving hot
    path — the offer decision is a few conf reads and a counter, with
    serialization paid only for retained records.  Measured on the
    serving workload and CORRECTNESS-GATED at < 3% median overhead
    (same 2ms absolute noise floor as the advisor capture gate), then
    the retention + diagnostics loop is proven: a slow request lands in
    ``slow_queries()`` addressable by its echoed trace id, and
    ``dump_diagnostics`` leaves a bundle readable back."""
    import json as _json

    from hyperspace_tpu.interop.server import QueryClient, QueryServer
    from hyperspace_tpu.telemetry import flight_recorder
    from hyperspace_tpu.telemetry import metrics as _metrics

    _require(ctx, "session", "lineitem_dir")
    session = ctx["session"]
    session.enable_hyperspace()
    li = ctx["lineitem_dir"]
    keys = [N_ORDERS // 11, N_ORDERS // 5, N_ORDERS // 2]
    templates = [
        {"source": {"format": "parquet", "path": li},
         "filter": {"op": "==", "col": "l_orderkey", "value": k},
         "select": ["l_orderkey", "l_quantity"]} for k in keys]
    reqs = 24
    reps = max(3, REPEATS)
    out: dict = {}
    saved = (session.conf.flight_recorder_enabled,
             session.conf.flight_recorder_slow_ms)
    try:
        with QueryServer(session) as server:
            def batch() -> None:
                with QueryClient(server.address) as qc:
                    for r in range(reqs):
                        qc.query(dict(templates[r % len(templates)]))

            batch()  # warm: plan cache, readers, sockets
            session.conf.flight_recorder_enabled = False
            t_off = _time(batch, repeats=reps)
            session.conf.flight_recorder_enabled = True
            t_on = _time(batch, repeats=reps)
            overhead_pct = ((t_on["median"] - t_off["median"])
                            / t_off["median"] * 100.0)
            abs_ms = ((t_on["median"] - t_off["median"])
                      * 1000.0 / reqs)
            out["recorder_off_s"] = _stat(t_off)
            out["recorder_on_s"] = _stat(t_on)
            out["requests_per_batch"] = reqs
            out["overhead_pct"] = round(overhead_pct, 2)
            out["overhead_ms_per_request"] = round(abs_ms, 3)
            if overhead_pct > 3.0 and abs_ms > 2.0:
                raise SystemExit(
                    f"flight_recorder bench: recorder overhead "
                    f"{overhead_pct:.1f}% (> 3% and {abs_ms:.2f} "
                    f"ms/request) on the serving workload")

            # Retention + surfacing: force one request into the slow
            # tail, fetch it back by its echoed trace id.
            retained0 = _metrics.registry().counter("flight.retained")
            session.conf.flight_recorder_slow_ms = 0.0001
            with QueryClient(server.address) as qc:
                qc.query(dict(templates[0]))
                tid = qc.last_trace_id
            deadline_at = time.monotonic() + 10
            rec = None
            while rec is None and time.monotonic() < deadline_at:
                rec = flight_recorder.recorder().find(tid)
            if rec is None or rec["outcome"] != "OK":
                raise SystemExit(
                    "flight_recorder bench: slow request was not "
                    "retained under its echoed trace id")
            out["retained_delta"] = int(
                _metrics.registry().counter("flight.retained")
                - retained0)
            with QueryClient(server.address) as qc:
                verb = qc.query({"verb": "trace", "id": tid})
            if _json.loads(
                    verb.column("record_json")[0].as_py()
                    )["trace_id"] != tid:
                raise SystemExit("flight_recorder bench: trace verb "
                                 "returned the wrong record")
        key = flight_recorder.dump_diagnostics(session.conf)
        got = flight_recorder.bundles(session.conf)
        if key is None or not any(b.get("key") == key for b in got):
            raise SystemExit("flight_recorder bench: diagnostics bundle "
                             "did not round-trip through the LogStore")
        out["bundle_records"] = len(got[-1].get("records", []))
    finally:
        (session.conf.flight_recorder_enabled,
         session.conf.flight_recorder_slow_ms) = saved
    return {"flight_recorder": out}


def _sec_alerts(ctx: dict) -> dict:
    """SLO alert engine cost + fire/resolve contract
    (docs/16-observability.md): the engine is a conf-gated sampler
    THREAD riding the heartbeat cadence — the serve path carries no
    alert hook at all — so enabling it must be invisible on the
    serving workload (correctness-gated at < 3% median overhead with
    the usual 2 ms absolute noise floor).  Then the full loop is
    proven end-to-end with the chaos alert drill: armed ``net.send``
    wire faults must FIRE the availability fast-burn alert, capture an
    incident bundle that reads back from the diagnostics store, and
    RESOLVE after disarm (emitting ``alert.evaluate`` /
    ``alert.capture`` spans along the way)."""
    from hyperspace_tpu.interop.chaos import _alert_drill
    from hyperspace_tpu.interop.server import QueryClient, QueryServer
    from hyperspace_tpu.telemetry import alerts as _alerts

    _require(ctx, "session", "lineitem_dir")
    session = ctx["session"]
    session.enable_hyperspace()
    li = ctx["lineitem_dir"]
    keys = [N_ORDERS // 11, N_ORDERS // 5, N_ORDERS // 2]
    templates = [
        {"source": {"format": "parquet", "path": li},
         "filter": {"op": "==", "col": "l_orderkey", "value": k},
         "select": ["l_orderkey", "l_quantity"]} for k in keys]
    reqs = 24
    reps = max(3, REPEATS)
    out: dict = {}
    engine = None
    try:
        with QueryServer(session) as server:
            def batch() -> None:
                with QueryClient(server.address) as qc:
                    for r in range(reqs):
                        qc.query(dict(templates[r % len(templates)]))

            batch()  # warm: plan cache, readers, sockets
            t_off = _time(batch, repeats=reps)  # engine disabled
            session.conf.set("hyperspace.alerts.enabled", True)
            session.conf.set("hyperspace.alerts.intervalS", 0.1)
            engine = _alerts.engine_for(session)
            engine.start()
            t_on = _time(batch, repeats=reps)
            overhead_pct = ((t_on["median"] - t_off["median"])
                            / t_off["median"] * 100.0)
            abs_ms = ((t_on["median"] - t_off["median"])
                      * 1000.0 / reqs)
            out["engine_off_s"] = _stat(t_off)
            out["engine_on_s"] = _stat(t_on)
            out["requests_per_batch"] = reqs
            out["overhead_pct"] = round(overhead_pct, 2)
            out["overhead_ratio"] = round(
                t_on["median"] / max(1e-9, t_off["median"]), 4)
            out["overhead_ms_per_request"] = round(abs_ms, 3)
            if overhead_pct > 3.0 and abs_ms > 2.0:
                raise SystemExit(
                    f"alerts bench: engine overhead {overhead_pct:.1f}% "
                    f"(> 3% and {abs_ms:.2f} ms/request) on the "
                    f"serving workload")
    finally:
        if engine is not None:
            engine.stop()
        session.conf.set("hyperspace.alerts.enabled", False)
        session.conf.set("hyperspace.alerts.intervalS", 0.0)

    drill = _alert_drill(session)
    if not drill.get("ok"):
        raise SystemExit(
            f"alerts bench: fire→bundle→resolve drill failed: "
            f"fired={drill.get('fired')} "
            f"bundle_ok={drill.get('bundle_ok')} "
            f"resolved={drill.get('resolved')}")
    out["drill_fired"] = int(bool(drill["fired"]))
    out["drill_resolved"] = int(bool(drill["resolved"]))
    out["drill_bundle_ok"] = int(bool(drill["bundle_ok"]))
    # Post-drill steady state: nothing left burning (lower-better for
    # bench_compare, like the alerts.firing gauge it mirrors).
    out["firing"] = len([
        a for a in _alerts.carried_alerts(session.conf)
        if a.get("state") == "firing"])
    return {"alerts": out}


def _sec_fleet_obs(ctx: dict) -> dict:
    """Fleet observability cost + federation contract
    (docs/16-observability.md): the heartbeat publisher must be
    invisible on the serving hot path — it runs on its own thread and
    writes one bounded snapshot per interval through the LogStore seam.
    Measured on the serving workload with THREE real subprocess
    publishers hammering the same fleet store and CORRECTNESS-GATED at
    < 3% median overhead (same 2 ms absolute noise floor as the advisor
    and flight-recorder gates).  Then federation is proven end to end:
    ``fleet_status`` lists every publisher fresh, merged counters equal
    the per-process sums, and a flight record minted in a SUBPROCESS
    resolves from this process by its trace id (the federated-trace
    round-trip)."""
    import subprocess as _subprocess

    from hyperspace_tpu.interop.server import QueryClient, QueryServer
    from hyperspace_tpu.telemetry import fleet
    from hyperspace_tpu.telemetry import metrics as _metrics

    _require(ctx, "session", "lineitem_dir")
    session = ctx["session"]
    session.enable_hyperspace()
    li = ctx["lineitem_dir"]
    keys = [N_ORDERS // 11, N_ORDERS // 5, N_ORDERS // 2]
    templates = [
        {"source": {"format": "parquet", "path": li},
         "filter": {"op": "==", "col": "l_orderkey", "value": k},
         "select": ["l_orderkey", "l_quantity"]} for k in keys]
    reqs = 24
    reps = max(3, REPEATS)
    out: dict = {}
    system_path = session.conf.system_path
    child_script = (
        "import json, os, sys, time\n"
        "from hyperspace_tpu import HyperspaceSession\n"
        "from hyperspace_tpu.interop.query import mint_trace_id\n"
        "from hyperspace_tpu.telemetry import fleet, flight_recorder\n"
        "from hyperspace_tpu.telemetry import metrics\n"
        "s = HyperspaceSession(system_path=sys.argv[1])\n"
        "s.conf.set('hyperspace.fleet.telemetry.enabled', True)\n"
        "s.conf.set('hyperspace.fleet.telemetry.publishIntervalS', 0.2)\n"
        "tid = mint_trace_id()\n"
        "metrics.inc('serve.requests', 7)\n"
        "flight_recorder.record(\n"
        "    s.conf, kind='spec', outcome='FAILED', latency_ms=1.0,\n"
        "    trace_id=tid, request_id=mint_trace_id(),\n"
        "    error='bench fleet seed')\n"
        "fleet.publisher_for(s).start()\n"
        "print(json.dumps({'process': fleet.process_identity(),\n"
        "                  'trace': tid}), flush=True)\n"
        "time.sleep(600)\n")
    saved = (session.conf.fleet_telemetry_enabled,
             session.conf.fleet_publish_interval_s,
             session.conf.fleet_stale_after_s)
    procs: list = []
    try:
        env_vars = dict(os.environ, JAX_PLATFORMS="cpu")
        for _ in range(3):
            procs.append(_subprocess.Popen(
                [sys.executable, "-c", child_script, system_path],
                stdout=_subprocess.PIPE, stderr=_subprocess.DEVNULL,
                text=True, env=env_vars))
        children = [json.loads(p.stdout.readline()) for p in procs]
        with QueryServer(session) as server:
            def batch() -> None:
                with QueryClient(server.address) as qc:
                    for r in range(reqs):
                        qc.query(dict(templates[r % len(templates)]))

            batch()  # warm: plan cache, readers, sockets
            session.conf.fleet_telemetry_enabled = False
            t_off = _time(batch, repeats=reps)
            session.conf.fleet_telemetry_enabled = True
            session.conf.fleet_publish_interval_s = 0.2
            fleet.publisher_for(session).start()
            pub0 = _metrics.registry().counter("fleet.publishes")
            wall0 = time.perf_counter()
            t_on = _time(batch, repeats=reps)
            on_wall = time.perf_counter() - wall0
            n_pub = _metrics.registry().counter("fleet.publishes") - pub0
        overhead_pct = ((t_on["median"] - t_off["median"])
                        / t_off["median"] * 100.0)
        abs_ms = (t_on["median"] - t_off["median"]) * 1000.0 / reqs
        # The A/B runs the publisher at a 25x-accelerated cadence (0.2s
        # vs the 5s default) so several publishes land inside the
        # measured window; the GATE is the steady-state cost at the
        # DEFAULT cadence — per-publish cost derived from the measured
        # delta and the observed publish count, amortized over the
        # default interval.
        frac = max(0.0, (t_on["median"] - t_off["median"])
                   / t_on["median"])
        rate = max(n_pub, 1) / max(on_wall, 1e-9)
        ms_per_publish = frac * 1000.0 / rate
        default_interval = 5.0
        steady_pct = ms_per_publish / (default_interval * 1000.0) * 100.0
        out["publisher_off_s"] = _stat(t_off)
        out["publisher_on_s"] = _stat(t_on)
        out["requests_per_batch"] = reqs
        out["publisher_overhead_pct"] = round(overhead_pct, 2)
        out["publisher_overhead_ms_per_request"] = round(abs_ms, 3)
        out["publisher_publishes_measured"] = int(n_pub)
        out["publisher_ms_per_publish"] = round(ms_per_publish, 3)
        out["publisher_steady_state_pct"] = round(steady_pct, 3)
        if steady_pct > 3.0 and abs_ms > 2.0:
            raise SystemExit(
                f"fleet_obs bench: publisher steady-state overhead "
                f"{steady_pct:.2f}% at the default cadence (> 3%, "
                f"{ms_per_publish:.1f} ms/publish) on the serving "
                f"workload")

        # Federation: every subprocess publisher fresh in fleet_status,
        # merged counters carrying the per-process sums, and the
        # federated-trace round-trip — a record minted in a child
        # process resolves HERE by its id.
        session.conf.fleet_stale_after_s = 10.0
        status = fleet.fleet_status_table(session.conf)
        fresh = {p: f for p, f in zip(
            status.column("process").to_pylist(),
            status.column("fresh").to_pylist())}
        missing = [c["process"] for c in children
                   if not fresh.get(c["process"])]
        if missing:
            raise SystemExit(
                f"fleet_obs bench: subprocess publisher(s) {missing} "
                f"not fresh in fleet_status()")
        merged = fleet.fleet_metrics(session.conf)
        child_sum = merged["counters"].get("serve.requests", 0.0)
        if child_sum < 3 * 7:
            raise SystemExit(
                f"fleet_obs bench: merged serve.requests {child_sum} "
                f"is below the 3-subprocess sum (21)")
        out["fleet_processes"] = len(merged["processes"])
        out["merged_serve_requests"] = int(child_sum)
        for child in children:
            rec = fleet.find_trace(session.conf, child["trace"])
            if rec is None or rec.get("process") != child["process"]:
                raise SystemExit(
                    f"fleet_obs bench: trace {child['trace']} minted in "
                    f"{child['process']} did not resolve via "
                    f"trace(id, fleet=True)")
        out["federated_trace_ok"] = True
    finally:
        (session.conf.fleet_telemetry_enabled,
         session.conf.fleet_publish_interval_s,
         session.conf.fleet_stale_after_s) = saved
        fleet.publisher_for(session).stop()
        for p in procs:
            p.kill()
        for p in procs:
            p.wait(timeout=30)
    return {"fleet_obs": out}


def _sec_fleet(ctx: dict) -> dict:
    """Serving fleet behind the front door (docs/20-fleet-serving.md):
    THREE subprocess servers over the shared index tree behind
    ``FleetQueryClient`` versus ONE server on the same concurrent
    workload — aggregate QPS and p99 through the same client machinery
    — then the failover drill: SIGKILL one server mid-burst.
    Correctness-gated three ways: the fleet must beat the single
    server's QPS at equal-or-better p99 (25% noise slack), the merged
    fleet counters must account for the served requests, and the drill
    must lose ZERO retryable requests — every post-kill answer
    bit-equal, the retries visible as ``client.retry.*`` /
    ``client.failover``."""
    import subprocess as _subprocess
    import threading

    from hyperspace_tpu.interop import dataset_from_spec
    from hyperspace_tpu.interop.server import FleetQueryClient
    from hyperspace_tpu.telemetry import fleet
    from hyperspace_tpu.telemetry import metrics as _metrics

    _require(ctx, "session", "lineitem_dir")
    session = ctx["session"]
    session.enable_hyperspace()
    li = ctx["lineitem_dir"]
    # A medium-weight template: enough server-side compute that the
    # fleet's extra processes matter, same answer every time so every
    # response is bit-equal-checkable.
    template = {"source": {"format": "parquet", "path": li},
                "group_by": ["l_status"],
                "aggs": {"q": ["l_quantity", "sum"],
                         "p": ["l_extendedprice", "mean"]}}
    expected = dataset_from_spec(session, dict(template)).collect()
    expected = expected.sort_by("l_status")
    n_clients = int(os.environ.get("HS_BENCH_FLEET_CLIENTS", 6))
    reqs_per_client = int(os.environ.get("HS_BENCH_FLEET_REQS", 8))
    system_path = session.conf.system_path
    child_script = (
        "import json, os, sys\n"
        "from hyperspace_tpu import HyperspaceSession\n"
        "from hyperspace_tpu.interop import QueryServer\n"
        "s = HyperspaceSession(system_path=sys.argv[1])\n"
        "s.conf.set('hyperspace.fleet.telemetry.enabled', True)\n"
        "s.conf.set('hyperspace.fleet.telemetry.publishIntervalS', 0.2)\n"
        "server = QueryServer(s).start()\n"
        "print(json.dumps({'port': server.address[1],\n"
        "                  'pid': os.getpid()}), flush=True)\n"
        "server.drained.wait()\n")
    saved_stale = session.conf.fleet_stale_after_s
    out: dict = {}
    procs: list = []
    try:
        env_vars = dict(os.environ, JAX_PLATFORMS="cpu")
        for _ in range(3):
            procs.append(_subprocess.Popen(
                [sys.executable, "-c", child_script, system_path],
                stdout=_subprocess.PIPE, stderr=_subprocess.DEVNULL,
                text=True, env=env_vars))
        children = [json.loads(p.stdout.readline()) for p in procs]
        endpoints = [("127.0.0.1", c["port"]) for c in children]
        # Warm every server before EITHER mode is timed: first contact
        # pays dataset-open + compile per process, and the comparison is
        # steady-state serving, not cold start.
        from hyperspace_tpu.interop import request_query
        for ep in endpoints:
            request_query(ep, dict(template))
            request_query(ep, dict(template))

        def run_mode(eps) -> dict:
            latencies: list = []
            errors: list = []
            lock = threading.Lock()
            with FleetQueryClient(eps, conf=session.conf) as fc:
                fc.query(dict(template))  # warm readers + routing

                def client(ci: int) -> None:
                    try:
                        for _ in range(reqs_per_client):
                            t0 = time.perf_counter()
                            got = fc.query(dict(template))
                            dt = time.perf_counter() - t0
                            ok = got.sort_by("l_status").equals(expected)
                            with lock:
                                latencies.append(dt)
                                if not ok:
                                    errors.append(f"client {ci}: diverged")
                    except Exception as e:  # noqa: BLE001 — gated below
                        with lock:
                            errors.append(f"client {ci}: "
                                          f"{type(e).__name__}: {e}")

                wall0 = time.perf_counter()
                threads = [threading.Thread(target=client, args=(i,))
                           for i in range(n_clients)]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(timeout=max(120.0, SECTION_CAP_S or 120.0))
                wall = time.perf_counter() - wall0
                if any(t.is_alive() for t in threads):
                    raise SystemExit("fleet bench: client threads hung")
            if errors:
                raise SystemExit(f"fleet bench: diverged answers/errors: "
                                 f"{errors[:5]}")
            lat = sorted(latencies)
            return {"qps": len(lat) / wall,
                    "p50_ms": lat[len(lat) // 2] * 1000.0,
                    "p99_ms": lat[min(len(lat) - 1,
                                      int(0.99 * len(lat)))] * 1000.0,
                    "requests": len(lat)}

        single = run_mode(endpoints[:1])
        fleet_run = run_mode(endpoints)
        out["single"] = {k: round(v, 2) for k, v in single.items()}
        out["fleet"] = {k: round(v, 2) for k, v in fleet_run.items()}
        out["qps_ratio"] = round(fleet_run["qps"] / single["qps"], 3)
        out["p99_ratio"] = round(fleet_run["p99_ms"] / single["p99_ms"], 3)
        # The scale-out gates only bind when server-side compute
        # dominates the request (the real bench scale) AND the host has
        # cores for 3 server processes to spread over — at toy scale
        # (resilience tests) a request is connection-overhead-bound,
        # and on a 1-core host 3 CPU-bound processes cannot beat 1
        # (same convention as the multichip / build_pipeline gates);
        # either way the ratios are recorded for --compare.
        gated = N_LINEITEM >= 1_000_000 and (os.cpu_count() or 1) >= 4
        out["scale_gated"] = gated
        if gated and fleet_run["qps"] <= single["qps"]:
            raise SystemExit(
                f"fleet bench: 3 servers behind the front door did not "
                f"beat one server's QPS ({fleet_run['qps']:.1f} vs "
                f"{single['qps']:.1f})")
        if gated and fleet_run["p99_ms"] > single["p99_ms"] * 1.25:
            raise SystemExit(
                f"fleet bench: fleet p99 {fleet_run['p99_ms']:.0f} ms "
                f"regressed past the single server's "
                f"{single['p99_ms']:.0f} ms (+25% slack)")
        # The merged fleet counters must account for the served load —
        # the aggregate-QPS claim is backed by fleet_metrics(), not just
        # client-side stopwatches.
        session.conf.fleet_stale_after_s = 10.0
        time.sleep(0.6)  # let the final 0.2s-interval heartbeats land
        merged = fleet.fleet_metrics(session.conf)
        served = merged["counters"].get("serve.ok", 0.0)
        expected_min = single["requests"] + fleet_run["requests"]
        if served < expected_min:
            raise SystemExit(
                f"fleet bench: merged serve.ok {served:.0f} below the "
                f"{expected_min} requests the client drove")
        out["merged_serve_ok"] = int(served)

        # Failover drill: SIGKILL one server, then a burst through the
        # front door — zero retryable requests lost, bit-equal answers.
        retry0 = _metrics.registry().counter("client.retry")
        fail0 = _metrics.registry().counter("client.failover")
        drill_reqs = 30
        with FleetQueryClient(endpoints, conf=session.conf) as fc:
            fc.query(dict(template))
            os.kill(children[0]["pid"], signal.SIGKILL)
            procs[0].wait(timeout=30)
            t0 = time.perf_counter()
            for _ in range(drill_reqs):
                got = fc.query(dict(template))
                if not got.sort_by("l_status").equals(expected):
                    raise SystemExit("fleet bench: failover drill "
                                     "returned a diverged answer")
            drill_wall = time.perf_counter() - t0
        retries = _metrics.registry().counter("client.retry") - retry0
        failovers = _metrics.registry().counter("client.failover") - fail0
        if failovers < 1:
            raise SystemExit(
                "fleet bench: SIGKILL mid-burst drove zero failovers — "
                "the drill never exercised the retry path")
        out["drill"] = {"requests": drill_reqs, "lost": 0,
                        "retries": int(retries),
                        "failovers": int(failovers),
                        "qps": round(drill_reqs / drill_wall, 2)}
    finally:
        session.conf.fleet_stale_after_s = saved_stale
        for p in procs:
            p.kill()
        for p in procs:
            p.wait(timeout=30)
    return {"fleet": out}


def _sec_chaos() -> dict:
    """Seeded wire-level chaos drill (interop/chaos.py): a reproducible
    randomized schedule of SIGKILL + restart, SIGSTOP/SIGCONT gray
    failures, and armed net faults against a 3-server fleet under
    sustained mixed load.  Gated on the drill's own invariants — zero
    lost requests, bit-equal answers, exactly-once maintenance,
    consistent client.* accounting — plus the feature tax: with hedging,
    breakers, and fault arming all DISABLED (the defaults), the client
    path's per-request overhead from this machinery must stay under 3%
    of the drill's clean p50."""
    from hyperspace_tpu.interop import chaos as _chaos
    from hyperspace_tpu.io import faults as _faults

    seed = int(os.environ.get("HS_BENCH_CHAOS_SEED", 6))
    duration = float(os.environ.get("HS_BENCH_CHAOS_DURATION_S", 6.0))
    # The schedule is a pure function of the seed: assert that here so
    # a chaos failure in CI reproduces from the printed seed alone.
    if _chaos.build_schedule(seed, duration, 3) != \
            _chaos.build_schedule(seed, duration, 3):
        raise SystemExit("chaos bench: schedule is not deterministic")
    report = _chaos.run_chaos(seed=seed, duration_s=duration, servers=3)
    if not report["ok"]:
        raise SystemExit(
            f"chaos bench (seed {seed}): invariants violated: "
            f"{report['violations']}")
    if report["hedge_sent"] < 1:
        raise SystemExit(
            f"chaos bench (seed {seed}): the drill never hedged — the "
            f"gray-failure path went unexercised")
    if report["breaker_opens"] < 1:
        raise SystemExit(
            f"chaos bench (seed {seed}): no breaker ever opened — the "
            f"schedule never drove consecutive failures")

    # Feature tax with everything off (the shipped defaults): the new
    # client-path work per request is the disarmed net.* checkpoints —
    # one plan-is-None check per seam crossing, ~5 crossings per request
    # (client connect amortized by pooling, then send + recv on the
    # client and accept + send on the server).
    reps = 200_000
    t0 = time.perf_counter()
    for _ in range(reps):
        _faults.net("net.send")
    per_seam_ms = (time.perf_counter() - t0) * 1000.0 / reps
    disabled_overhead_ms = per_seam_ms * 5
    clean_p50 = max(0.001, report["clean_p50_ms"])
    overhead_pct = 100.0 * disabled_overhead_ms / clean_p50
    if overhead_pct >= 3.0:
        raise SystemExit(
            f"chaos bench: disarmed seam checkpoints cost "
            f"{disabled_overhead_ms:.4f} ms/request, {overhead_pct:.2f}% "
            f"of the clean p50 ({clean_p50:.2f} ms) — over the 3% gate")
    return {"chaos": {
        "seed": seed,
        "events": len(report["schedule"]),
        "sent": report["sent"],
        "lost": report["lost"],
        "mismatch": report["mismatch"],
        "maintenance_refresh_done": report["maintenance_refresh_done"],
        "hedge_sent": int(report["hedge_sent"]),
        "hedge_wins": int(report["hedge_wins"]),
        "hedge_win_rate": report["hedge_win_rate"],
        "breaker_opens": int(report["breaker_opens"]),
        "breaker_closes": int(report["breaker_closes"]),
        "pool_evicted": int(report["pool_evicted"]),
        "clean_p50_ms": report["clean_p50_ms"],
        "clean_p99_ms": report["clean_p99_ms"],
        "fault_p99_ms": report["fault_p99_ms"],
        "disabled_overhead_ms": round(disabled_overhead_ms, 5),
        "disabled_overhead_pct": round(overhead_pct, 3),
    }}


def _sec_ingest(root: str) -> dict:
    """Continuous ingest + autonomous lifecycle (docs/19-lifecycle.md):
    the rolling-append workload.  Three proofs, all correctness-gated:

      1. incremental-vs-full refresh — append one small file to a built
         index's source and time ``refresh("incremental")`` vs
         ``refresh("full")`` from the same logical state; gated >= 5x
         (the subsystem's reason to exist).
      2. mid-refresh correctness — an appender thread appends +
         incrementally refreshes in a loop while this thread queries
         (hybrid scan on) and, whenever the source listing is stable
         across a collect, asserts BIT-EQUAL answers vs a direct
         pyarrow read of exactly those files.
      3. the daemon — capture on + byte budget, append one file, run
         one maintenance cycle: the journal must show the incremental
         refresh AND the advisor-recommended build, and read back from
         a fresh session (restart-proof).

    Self-contained (own sources, throwaway sessions), like integrity."""
    import glob as _glob
    import threading

    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    from hyperspace_tpu import Hyperspace, HyperspaceSession, IndexConfig, col

    # Big enough that a full rebuild is dominated by data volume, not
    # by the fixed per-action protocol cost incremental also pays —
    # the >= 5x gate measures the subsystem, not log-write latency.
    n = max(600_000, N_LINEITEM // 10)
    files = 8
    n_append = max(600, n // 500)
    src = os.path.join(root, "ingest_src")
    os.makedirs(src, exist_ok=True)
    rng = np.random.default_rng(43)
    next_k = [0]

    def append_file(count: int) -> str:
        t = pa.table({
            "k": pa.array(np.arange(next_k[0], next_k[0] + count,
                                    dtype=np.int64)),
            "v": rng.random(count),
            "w": rng.random(count),
        })
        next_k[0] += count
        path = os.path.join(src, f"part-{next_k[0]:010d}.parquet")
        pq.write_table(t, path)
        return path

    for _ in range(files):
        append_file(-(-n // files))

    session = HyperspaceSession(system_path=os.path.join(root,
                                                         "ingest_ix"))
    session.conf.num_buckets = 8
    session.conf.lineage_enabled = True
    session.conf.hybrid_scan_enabled = True
    # The refresh comparison measures DATA-VOLUME avoidance (index only
    # what changed), not mesh dispatch: the distributed build's fixed
    # shuffle/compile cost per action would dominate a 1200-row
    # incremental at toy scale (and the test suite's 8 virtual CPU
    # devices would route there), drowning the thing under test.
    session.conf.parallel_build = "off"
    hs = Hyperspace(session)
    hs.create_index(session.read.parquet(src),
                    IndexConfig("ingest_ix", ["k"], ["v"]))
    session.enable_hyperspace()

    def median(xs: list) -> float:
        return sorted(xs)[len(xs) // 2]

    # -- (1) incremental vs full, each from "fresh index + one appended
    # file" so both modes face the same logical work ----------------------
    reps = max(1, min(3, REPEATS))
    inc_s: list = []
    full_s: list = []
    append_file(n_append)  # untimed warmup: JIT/import costs land here
    hs.refresh_index("ingest_ix", "incremental")
    for _ in range(reps):
        append_file(n_append)
        t0 = time.perf_counter()
        summary = hs.refresh_index("ingest_ix", "incremental")
        inc_s.append(time.perf_counter() - t0)
        if summary.outcome != "ok" or summary.appended != 1:
            raise SystemExit(f"ingest bench: incremental refresh saw "
                             f"{summary!r}, expected 1 appended file")
    for _ in range(reps):
        append_file(n_append)
        t0 = time.perf_counter()
        hs.refresh_index("ingest_ix", "full")
        full_s.append(time.perf_counter() - t0)
    speedup = median(full_s) / max(1e-9, median(inc_s))
    if speedup < 5.0:
        raise SystemExit(
            f"ingest bench: incremental refresh only {speedup:.1f}x "
            f"faster than full rebuild on the rolling-append workload "
            f"(inc {median(inc_s):.3f}s vs full {median(full_s):.3f}s); "
            f"the acceptance bar is 5x")

    # -- (2) mid-refresh reader: bit-equal vs a host reference ------------
    stop = threading.Event()
    failures: list = []

    def appender() -> None:
        try:
            for _ in range(3):
                append_file(n_append)
                time.sleep(0.05)
                hs.refresh_index("ingest_ix", "incremental")
                time.sleep(0.05)
        except Exception as e:  # noqa: BLE001 — surfaced as a gate below
            failures.append(f"appender died: {e!r}")
        finally:
            stop.set()

    def listing() -> list:
        return sorted(_glob.glob(os.path.join(src, "*.parquet")))

    def reference(paths: list) -> list:
        t = pq.read_table(paths, columns=["k", "v"])
        return sorted(zip(t.column("k").to_pylist(),
                          t.column("v").to_pylist()))

    def one_read(require_stable: bool) -> bool:
        """One query; compares when the listing stayed stable across the
        collect (appends are create-only, so equal listings mean the
        collect saw exactly those files).  Returns True if compared."""
        l1 = listing()
        res = (session.read.parquet(src).filter(col("k") >= 0)
               .select("k", "v").collect())
        if listing() != l1:
            if require_stable:
                failures.append("final quiescent read saw an unstable "
                                "listing")
            return False
        got = sorted(zip(res.column("k").to_pylist(),
                         res.column("v").to_pylist()))
        if got != reference(l1):
            failures.append(
                f"mid-refresh divergence over {len(l1)} files: "
                f"{len(got)} rows vs host reference")
        return True

    reader_thread = threading.Thread(target=appender, daemon=True)
    reader_thread.start()
    reads = compares = 0
    while not stop.is_set() and not failures and reads < 200:
        compares += 1 if one_read(require_stable=False) else 0
        reads += 1
    reader_thread.join(timeout=120)
    if not failures:
        # Quiescent final read MUST compare (and pass): at least one
        # bit-equality proof per run even on a slow machine.
        compares += 1 if one_read(require_stable=True) else 0
    if failures:
        raise SystemExit(f"ingest bench: {failures[0]}")
    if compares == 0:
        raise SystemExit("ingest bench: no stable-window comparison "
                         "completed")

    # -- (3) the daemon: detect -> refresh -> advisor build, journaled ----
    n2 = 20_000
    src2 = os.path.join(root, "ingest_src2")
    os.makedirs(src2, exist_ok=True)
    t2 = pa.table({
        "a": pa.array(np.arange(n2, dtype=np.int64)),
        "d": pa.array(rng.integers(0, 50, n2), type=pa.int64()),
        "b": rng.random(n2),
    })
    for i in range(4):
        pq.write_table(t2.slice(i * (n2 // 4), n2 // 4),
                       os.path.join(src2, f"part-{i:03d}.parquet"))
    s2 = HyperspaceSession(system_path=os.path.join(root, "ingest_ix2"))
    s2.conf.num_buckets = 4
    s2.conf.lineage_enabled = True
    s2.conf.advisor_capture_enabled = True
    hs2 = Hyperspace(s2)
    hs2.create_index(s2.read.parquet(src2),
                     IndexConfig("ing2", ["a"], ["b"]))
    s2.enable_hyperspace()
    for _ in range(3):  # capture a workload the advisor can act on
        s2.read.parquet(src2).filter(col("d") == 7).select("d", "b") \
            .collect()
    entry = s2.index_collection_manager.get_index("ing2")
    index_bytes = sum(f.size for f in entry.content.file_infos())
    src_bytes = sum(os.path.getsize(p) for p in
                    _glob.glob(os.path.join(src2, "*.parquet")))
    s2.conf.lifecycle_byte_budget = index_bytes + 4 * src_bytes
    pq.write_table(t2.slice(0, 500),
                   os.path.join(src2, "part-appended.parquet"))
    t_append = time.time()
    recs = hs2.maintenance_cycle()
    staleness_s = time.time() - t_append
    decisions: dict = {}
    for r in recs:
        key = f"{r['decision']}:{r['outcome']}"
        decisions[key] = decisions.get(key, 0) + 1
    if not any(r["decision"] == "refresh" and r["outcome"] == "done"
               and r["mode"] == "incremental" for r in recs):
        raise SystemExit(f"ingest bench: daemon cycle journaled no "
                         f"incremental refresh: {decisions}")
    if not any(r["decision"] == "create" and r["outcome"] == "done"
               for r in recs):
        raise SystemExit(f"ingest bench: daemon cycle built no "
                         f"advisor-recommended index within the "
                         f"budget: {decisions}")
    fresh = HyperspaceSession(system_path=os.path.join(root,
                                                       "ingest_ix2"))
    if Hyperspace(fresh).lifecycle_history().num_rows < len(recs):
        raise SystemExit("ingest bench: lifecycle journal not readable "
                         "after restart")
    return {"ingest": {
        "rows": n,
        "append_rows": n_append,
        "incremental_refresh_s": round(median(inc_s), 4),
        "full_rebuild_s": round(median(full_s), 4),
        "incremental_vs_full_speedup": round(speedup, 2),
        "midrefresh_reads": reads,
        "midrefresh_compares": compares,
        "staleness_s": round(staleness_s, 3),
        "daemon_decisions": dict(sorted(decisions.items())),
        "journal_records": len(recs),
    }}


def _sec_cdc(root: str) -> dict:
    """Row-level CDC ingest (docs/19-lifecycle.md, the merge-on-read
    half of the lifecycle).  Four proofs, all correctness-gated:

      1. sustained upsert/delete stream through the Delta commit log
         with a CONCURRENT reader: every version-stable collect must be
         BIT-EQUAL to a pyarrow read of exactly that snapshot's live
         files, and the lifecycle journal must show the CDC quick
         (merge-on-read) refreshes riding the stream — row-level
         changes served without a rebuild.
      2. merge-on-read scan overhead — the overlaid query (delete
         vector + replaced rows applied at scan time) vs the same query
         after the debt-clearing incremental refresh; gated <= 10x so
         the overlay stays in the clean index's cost class.
      3. staleness under watch — a daemon on a 30s interval with the
         poll watcher must refresh an append in single-digit seconds:
         the wake event, not the interval, bounds staleness.
      4. autonomous compaction — a shredded index journals the
         optimize decision and the next cycle converges.

    Self-contained (own sources, throwaway sessions), like ingest."""
    import threading

    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    from hyperspace_tpu import Hyperspace, HyperspaceSession, IndexConfig, col
    from hyperspace_tpu.lifecycle import journal as lifecycle_journal
    from hyperspace_tpu.lifecycle.daemon import daemon_for
    from hyperspace_tpu.sources.delta import DeltaLog, write_delta
    from hyperspace_tpu.sources.delta.writer import (delete_rows_delta,
                                                     upsert_delta)

    rng = np.random.default_rng(47)

    def make(ids, tag: int = 0) -> pa.Table:
        ids = np.asarray(list(ids), dtype=np.int64)
        return pa.table({
            "id": pa.array(ids),
            "v": pa.array(ids * 10 + tag, type=pa.int64()),
            "w": rng.random(len(ids)),
        })

    # -- (1) the stream: 20-commit Delta table, upsert/delete cycles with
    # a concurrent reader comparing at version-stable points ---------------
    path = os.path.join(root, "cdc_delta")
    files = 20
    rows_per = 3_000
    for i in range(files):
        write_delta(make(range(i * rows_per, (i + 1) * rows_per)), path,
                    mode="append")
    session = HyperspaceSession(system_path=os.path.join(root, "cdc_ix"))
    session.conf.num_buckets = 4
    session.conf.lineage_enabled = True
    session.conf.hybrid_scan_enabled = True
    session.conf.lifecycle_cdc_enabled = True
    session.conf.lifecycle_cdc_merge_debt_ratio = 10.0  # stream never escalates
    session.conf.parallel_build = "off"
    hs = Hyperspace(session)
    hs.create_index(session.read.delta(path),
                    IndexConfig("cdc_ix", ["id"], ["v"]))
    session.enable_hyperspace()
    log = DeltaLog(path)

    stop = threading.Event()
    failures: list = []
    cycles = 4

    def writer() -> None:
        try:
            for i in range(cycles):
                upsert_delta(make([7 + i * 11, files * rows_per + i],
                                  tag=i + 1), path, "id")
                delete_rows_delta(path, "id", [501 + i * 13])
                recs = hs.maintenance_cycle()
                if not any(r["decision"] == "refresh" and r["mode"] == "quick"
                           and r["outcome"] == "done"
                           and "CDC merge-on-read" in r["reason"]
                           for r in recs):
                    failures.append(f"cycle {i}: no CDC quick refresh "
                                    f"journaled: {[(r['decision'], r.get('mode'), r['outcome']) for r in recs]}")
                    return
                time.sleep(0.02)
        except Exception as e:  # noqa: BLE001 — surfaced as a gate below
            failures.append(f"CDC writer died: {e!r}")
        finally:
            stop.set()

    def reference(version: int) -> list:
        t = pq.read_table([f.path for f in log.snapshot(version).files],
                          columns=["id", "v"])
        return sorted(zip(t.column("id").to_pylist(),
                          t.column("v").to_pylist()))

    def one_read(require_stable: bool) -> bool:
        """Compares when the Delta version stayed stable across the
        collect (rewritten part files are new paths — old snapshots
        stay physically intact, so the pinned-version reference reads
        exactly what the collect could see)."""
        v1 = log.latest_version()
        res = (session.read.delta(path).filter(col("id") >= 0)
               .select("id", "v").collect())
        if log.latest_version() != v1:
            if require_stable:
                failures.append("final quiescent read saw an unstable "
                                "version")
            return False
        got = sorted(zip(res.column("id").to_pylist(),
                         res.column("v").to_pylist()))
        if got != reference(v1):
            failures.append(f"CDC divergence at version {v1}: {len(got)} "
                            f"rows vs pinned-snapshot reference")
        return True

    writer_thread = threading.Thread(target=writer, daemon=True)
    writer_thread.start()
    reads = compares = 0
    while not stop.is_set() and not failures and reads < 200:
        compares += 1 if one_read(require_stable=False) else 0
        reads += 1
    writer_thread.join(timeout=120)
    if not failures:
        compares += 1 if one_read(require_stable=True) else 0
    if failures:
        raise SystemExit(f"cdc bench: {failures[0]}")
    if compares == 0:
        raise SystemExit("cdc bench: no version-stable comparison "
                         "completed")
    from hyperspace_tpu.lifecycle import cdc as cdc_mod

    entry = session.index_collection_manager.get_index("cdc_ix")
    debt = cdc_mod.merge_debt(entry)
    if debt.total_bytes <= 0 or not debt.readable:
        raise SystemExit(f"cdc bench: stream left no readable merge debt "
                         f"({debt.to_dict()})")

    # -- (2) merge-on-read overhead: overlaid scan vs debt-cleared scan ----
    def timed(reps: int = 5) -> float:
        xs = []
        for _ in range(reps):
            t0 = time.perf_counter()
            session.read.delta(path).filter(col("id") >= 0) \
                .select("id", "v").collect()
            xs.append(time.perf_counter() - t0)
        return sorted(xs)[len(xs) // 2]

    overlaid_s = timed()
    session.conf.lifecycle_cdc_merge_debt_ratio = 1e-9
    recs = hs.maintenance_cycle()
    if not any(r["decision"] == "refresh" and r["mode"] == "incremental"
               and r["outcome"] == "done" for r in recs):
        raise SystemExit(f"cdc bench: tightening the debt budget did not "
                         f"escalate to the incremental refresh: "
                         f"{[(r['decision'], r.get('mode'), r['outcome']) for r in recs]}")
    entry = session.index_collection_manager.get_index("cdc_ix")
    if cdc_mod.merge_debt(entry).total_bytes != 0:
        raise SystemExit("cdc bench: incremental refresh left merge debt")
    clean_s = timed()
    overhead = overlaid_s / max(1e-9, clean_s)
    if overhead > 10.0:
        raise SystemExit(
            f"cdc bench: merge-on-read overlay costs {overhead:.1f}x the "
            f"clean-index scan ({overlaid_s:.3f}s vs {clean_s:.3f}s); the "
            f"acceptance bar is 10x")

    # -- (3) staleness bounded by the watch event, not the interval --------
    src3 = os.path.join(root, "cdc_watch_src")
    os.makedirs(src3, exist_ok=True)
    pq.write_table(make(range(5_000)), os.path.join(src3, "p0.parquet"))
    s3 = HyperspaceSession(system_path=os.path.join(root, "cdc_watch_ix"))
    s3.conf.num_buckets = 4
    s3.conf.lineage_enabled = True
    s3.conf.lifecycle_enabled = True
    s3.conf.lifecycle_interval_s = 30.0   # the POLL bound
    s3.conf.watch_enabled = True
    s3.conf.watch_mode = "poll"
    s3.conf.watch_poll_interval_s = 0.05
    s3.conf.watch_debounce_ms = 10.0
    hs3 = Hyperspace(s3)
    hs3.create_index(s3.read.parquet(src3),
                     IndexConfig("cdc_wix", ["id"], ["v"]))
    hs3.start_maintenance()
    try:
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline:  # first cycle ran
            if lifecycle_journal.records(s3.conf):
                break
            time.sleep(0.05)
        else:
            raise SystemExit("cdc bench: daemon never completed its first "
                             "cycle")
        watch_mode = getattr(daemon_for(s3).watcher(), "mode", None)
        t_append = time.time()
        pq.write_table(make(range(5_000, 5_200)),
                       os.path.join(src3, "p1.parquet"))
        deadline = time.monotonic() + 20.0
        staleness_s = None
        while time.monotonic() < deadline:
            if any(r.get("decision") == "refresh"
                   and r.get("outcome") == "done"
                   for r in lifecycle_journal.records(s3.conf)):
                staleness_s = time.time() - t_append
                break
            time.sleep(0.05)
        if staleness_s is None or staleness_s >= 20.0:
            raise SystemExit(f"cdc bench: watch-bounded staleness "
                             f"{staleness_s}; the 30s interval never "
                             f"elapsed, the wake event must do this")
    finally:
        hs3.stop_maintenance()

    # -- (4) compaction: shredded index -> journaled optimize -> converges -
    src4 = os.path.join(root, "cdc_opt_src")
    os.makedirs(src4, exist_ok=True)
    pq.write_table(make(range(10_000)), os.path.join(src4, "p0.parquet"))
    s4 = HyperspaceSession(system_path=os.path.join(root, "cdc_opt_ix"))
    s4.conf.num_buckets = 2
    s4.conf.lineage_enabled = True
    s4.conf.parallel_build = "off"
    hs4 = Hyperspace(s4)
    hs4.create_index(s4.read.parquet(src4),
                     IndexConfig("cdc_oix", ["id"], ["v"]))
    for i in range(3):  # shred: one small file per bucket per refresh
        pq.write_table(make(range(20_000 + i * 500, 20_000 + i * 500 + 300)),
                       os.path.join(src4, f"p{i + 1}.parquet"))
        hs4.refresh_index("cdc_oix", "incremental")
    s4.conf.lifecycle_compaction_enabled = True
    s4.conf.lifecycle_compaction_min_small_files = 2
    recs4 = hs4.maintenance_cycle()
    opt = [r for r in recs4 if r["decision"] == "optimize"]
    if not opt or opt[0]["outcome"] != "done":
        raise SystemExit(f"cdc bench: compaction rung never fired on the "
                         f"shredded index: "
                         f"{[(r['decision'], r['outcome']) for r in recs4]}")
    recs4b = hs4.maintenance_cycle()
    if any(r["decision"] == "optimize" and r["outcome"] == "done"
           for r in recs4b):
        raise SystemExit("cdc bench: compaction did not converge — the "
                         "second cycle compacted again")
    s4.enable_hyperspace()
    t4 = s4.read.parquet(src4).filter(col("id") == 42).select("v").collect()
    if t4.column("v").to_pylist() != [420]:
        raise SystemExit(f"cdc bench: post-compaction read wrong: "
                         f"{t4.column('v').to_pylist()}")

    return {"cdc": {
        "stream_cycles": cycles,
        "stream_reads": reads,
        "stream_compares": compares,
        "merge_debt_ratio_peak": round(debt.ratio, 4),
        "overlaid_scan_s": round(overlaid_s, 4),
        "clean_scan_s": round(clean_s, 4),
        "merge_on_read_overhead_x": round(overhead, 2),
        "watch_mode": watch_mode,
        "watch_staleness_s": round(staleness_s, 3),
        "poll_bound_s": 30.0,
        "compaction_decisions": len(opt),
    }}


def _sec_sf10(ctx: dict, root: str, harness: "_Harness") -> dict:
    """SF10 scale step (round-3 verdict item 6): runs unless the SF1
    portion already burned the time budget or
    HS_BENCH_SF10=0."""
    _require(ctx, "session")
    if os.environ.get("HS_BENCH_SF10", "1") == "0":
        raise _SkipSection("HS_BENCH_SF10=0")
    elapsed = harness.elapsed()
    if elapsed > SF10_TIME_BUDGET_S:
        raise _SkipSection(f"SF1 portion took {elapsed:.0f}s > "
                           f"{SF10_TIME_BUDGET_S:.0f}s budget")
    return {"sf10": _sf10_section(ctx["session"], ctx["hs"], root,
                                  ctx["tables_equal"])}


def _sec_sf100(ctx: dict, root: str, harness: "_Harness") -> dict:
    """SF100 north-star step (round-5 verdict item 2), last: budget- and
    disk-gated.  The SF10 source data is spent — reclaim its disk
    first."""
    for spent in ("sf10_lineitem", "sf10_orders"):
        shutil.rmtree(os.path.join(root, spent), ignore_errors=True)
    _require(ctx, "session")
    if os.environ.get("HS_BENCH_SF100", "1") == "0":
        raise _SkipSection("HS_BENCH_SF100=0")
    elapsed = harness.elapsed()
    if elapsed > SF100_TIME_BUDGET_S:
        raise _SkipSection(f"earlier sections took {elapsed:.0f}s > "
                           f"{SF100_TIME_BUDGET_S:.0f}s budget")
    return {"sf100": _sf100_section(ctx["session"], ctx["hs"], root,
                                    ctx["tables_equal"])}


def _platform() -> str:
    try:
        import jax

        return jax.devices()[0].platform
    except Exception:  # pragma: no cover
        return "unknown"


# ---------------------------------------------------------------------------
# CLI: post-hoc finalize + the regression watchdog (no jax on these paths).
# ---------------------------------------------------------------------------
def _finalize_from(path: str) -> int:
    """Reconstruct and print the headline from a checkpoint file alone —
    the recovery path for a run SIGKILLed before even the in-handler
    finalize could run (rc=124 with a grace window the process never got).
    Exit 0 with a headline whenever the file holds any parseable record."""
    try:
        with open(path, encoding="utf-8") as f:
            lines = [ln for ln in f if ln.strip()]
    except OSError as e:
        print(f"bench: cannot read {path!r}: {e}", file=sys.stderr)
        return 2
    detail: Dict[str, object] = {}
    sections_run = []
    seen = set()
    scale = None
    for ln in lines:
        try:
            rec = json.loads(ln)
        except ValueError:
            continue  # a torn checkpoint line (the kill's last write)
        if not isinstance(rec, dict):
            continue
        if "headline" in rec and isinstance(rec["headline"], dict):
            # The run DID finalize; re-print its headline verbatim.
            print(json.dumps(rec["headline"]), flush=True)
            return 0
        if rec.get("bench"):
            scale = rec.get("scale")
            continue
        name = rec.get("section")
        if not name:
            continue
        seen.add(name)
        if rec.get("status") == "ok":
            updates = {k: v for k, v in rec.items()
                       if k not in ("section", "status", "elapsed_s")}
            detail.update(updates)
            sections_run.append({"section": name, "status": "ok",
                                 "elapsed_s": rec.get("elapsed_s", 0.0)})
        else:
            sections_run.append(dict(rec))
            detail.setdefault(name, {"skipped":
                                     rec.get("reason", rec.get("status"))})
    for name in SECTION_NAMES:
        if name not in seen:
            sections_run.append({"section": name, "status": "skipped",
                                 "elapsed_s": 0.0,
                                 "reason": "process killed before section"})
            detail.setdefault(name, {"skipped":
                                     "process killed before section"})
    # The headline value: completed sf1 speedups, full or partial.
    speedups = [v for k, v in detail.items()
                if k.endswith("_speedup") and isinstance(v, (int, float))
                and "." not in k]
    value = None
    if speedups:
        value = round(math.exp(sum(math.log(s) for s in speedups)
                               / len(speedups)), 3)
    detail["sections_run"] = sections_run
    detail["finalized_from"] = path
    if scale is not None:
        detail.setdefault("scale", scale)
    print(json.dumps({
        "metric": "tpch_sf1_indexed_query_speedup_geomean",
        "value": value, "unit": "x", "vs_baseline": value,
        "detail": detail}), flush=True)
    return 0


def _run_compare(current: str, baseline: str, threshold_pct: float,
                 min_abs_s: float) -> int:
    """Diff ``current`` vs ``baseline``; exit 0 (no regression),
    3 (regression), 2 (unreadable input)."""
    from hyperspace_tpu.telemetry import bench_compare

    if baseline == "auto":
        candidate = (RESULTS_PATH + ".prev") if RESULTS_PATH else ""
        if not candidate or not os.path.exists(candidate):
            print("bench compare: no baseline yet (auto found no "
                  f"{candidate or '<results>.prev'}); nothing to diff",
                  flush=True)
            return 0
        baseline = candidate
    try:
        result, report = bench_compare.compare_files(
            current, baseline, threshold_pct, min_abs_s)
    except bench_compare.BaselineError as e:
        print(f"bench compare: {e}", file=sys.stderr)
        return 2
    print(report, flush=True)
    return 0 if result.ok else 3


def _cli(argv) -> int:
    import argparse

    parser = argparse.ArgumentParser(
        prog="bench.py", description="hyperspace-tpu benchmark harness")
    parser.add_argument("--compare", metavar="BASELINE",
                        help="after the run (or with --compare-only, "
                             "instead of one), diff results against "
                             "BASELINE: a results JSONL, a headline "
                             "JSON, or 'auto' (= <results>.prev)")
    parser.add_argument("--compare-only", metavar="CURRENT",
                        help="skip the bench; diff CURRENT against "
                             "--compare's baseline")
    parser.add_argument("--compare-threshold-pct", type=float,
                        default=None, metavar="PCT",
                        help="regression threshold, percent (default 25)")
    parser.add_argument("--compare-min-abs-s", type=float, default=None,
                        metavar="S",
                        help="absolute floor for seconds metrics "
                             "(default 0.5)")
    parser.add_argument("--finalize-from", metavar="RESULTS",
                        help="print the headline reconstructed from a "
                             "prior run's checkpoint file, then exit")
    args = parser.parse_args(argv)

    from hyperspace_tpu.telemetry import bench_compare

    threshold = (bench_compare.DEFAULT_THRESHOLD_PCT
                 if args.compare_threshold_pct is None
                 else args.compare_threshold_pct)
    min_abs = (bench_compare.DEFAULT_MIN_ABS_S
               if args.compare_min_abs_s is None
               else args.compare_min_abs_s)
    if args.finalize_from:
        return _finalize_from(args.finalize_from)
    if args.compare_only:
        if not args.compare:
            parser.error("--compare-only requires --compare BASELINE")
        return _run_compare(args.compare_only, args.compare,
                            threshold, min_abs)
    rc = main()
    if args.compare:
        if not RESULTS_PATH:
            print("bench compare: HS_BENCH_RESULTS disabled; nothing "
                  "to diff", file=sys.stderr)
            return 2
        return _run_compare(RESULTS_PATH, args.compare, threshold, min_abs)
    return rc


if __name__ == "__main__":
    sys.exit(_cli(sys.argv[1:]))
