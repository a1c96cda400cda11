"""Physical execution: walk the logical plan, produce an arrow Table.

This plays the role of Spark's physical planning + execution for the tiny
operator set the rules target (§1 L2: FileSourceScanExec, SMJ,
BucketUnionExec).  The data plane routes to TPU kernels where the data is
numeric (predicates: ops/filter.py; equi-joins: ops/join.py) and falls back
to arrow/pandas host compute for variable-length data — mirroring how the
reference delegates string-heavy work to the JVM while we keep the MXU/VPU
fed with columnar numerics.

Scan semantics:
  - ``relation.file_paths`` overrides root-path listing (index scans and
    hybrid-scan subsets, RuleUtils.scala:255-286).
  - ``relation.prune_to_buckets`` drops index files whose bucket id (from
    the file name) is not needed — the bucket-pruning read
    (FilterIndexRule.scala:62-68).
"""

from __future__ import annotations

import os

from typing import Dict, List, Optional, Tuple

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from hyperspace_tpu.execution import sync_guard
from hyperspace_tpu.io import columnar
from hyperspace_tpu.telemetry import timeline
from hyperspace_tpu.telemetry.trace import span
from hyperspace_tpu.utils import deadline as _deadline
from hyperspace_tpu.utils.shapes import round_up_pow2
import jax
from jax import enable_x64 as _enable_x64
from hyperspace_tpu.io.files import list_data_files
from hyperspace_tpu.io.parquet import bucket_id_of_file, read_table
from hyperspace_tpu.plan.expr import (
    And,
    Arith,
    BinOp,
    Case,
    BucketIn,
    Cast,
    Col,
    Expr,
    Extract,
    IsIn,
    IsNull,
    Lit,
    Neg,
    Not,
    Or,
    StringFn,
    StringMatch,
)
from hyperspace_tpu.plan.nodes import (
    Aggregate,
    BucketUnion,
    Compute,
    Distinct,
    Filter,
    InMemory,
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


from hyperspace_tpu.sources.interfaces import LAKE_DATA_FORMATS, physical_read_format


class Executor:
    def __init__(self, session) -> None:
        self.session = session
        # Physical execution stats (PhysicalOperatorAnalyzer.scala:30-58
        # intent): per-join strategy, per-scan file counts.  Read back via
        # session.last_execution_stats after Dataset.collect().
        self.stats: Dict[str, list] = {"joins": [], "scans": []}
        # File-identity provenance of scan outputs within THIS query, for
        # the HBM-resident column cache (execution/device_cache.py):
        # id(table) -> (fingerprint, cacheable column names, table ref —
        # kept so the id can't be recycled mid-query).
        self._scan_fp: Dict[int, Tuple[str, frozenset, pa.Table]] = {}
        # (fingerprint, bytes) of each scanned file list, from one stat
        # per file per query: a join side's identity resolved from its
        # plan and the scan that may decode it share the pass.
        self._file_stats: Dict[Tuple[str, ...],
                               Tuple[Optional[str], int]] = {}
        # Engine mesh, resolved once per executor (= per collect):
        # ``hyperspace.parallel.mesh.enabled`` gates every sharded
        # dispatch below; None keeps the bit-equal single-device paths.
        self._mesh_cache: Tuple[bool, object] = (False, None)

    def _active_mesh(self):
        probed, mesh = self._mesh_cache
        if not probed:
            from hyperspace_tpu.parallel.mesh import active_mesh

            mesh = active_mesh(self.session.conf)
            self._mesh_cache = (True, mesh)
        return mesh

    # -- HBM-resident column cache ------------------------------------------
    def _cache_on(self) -> bool:
        conf = self.session.conf
        return conf.device_cache_policy != "off" \
            and conf.device_cache_bytes > 0

    def _stat_files(self, paths: List[str]) -> Tuple[Optional[str], int]:
        key = tuple(paths)
        got = self._file_stats.get(key)
        if got is None:
            from hyperspace_tpu.execution.device_cache import stat_files

            got = self._file_stats[key] = stat_files(paths)
        return got

    def _register_scan_identity(self, table: pa.Table,
                                fp: Optional[str]) -> None:
        if fp and self._cache_on():
            self._scan_fp[id(table)] = (
                fp, frozenset(table.column_names), table)

    def _scan_identity(self, table: pa.Table) -> Optional[Tuple[str, frozenset]]:
        entry = self._scan_fp.get(id(table))
        return (entry[0], entry[1]) if entry is not None else None

    def _register_derived_identity(self, out: pa.Table, parent_identity,
                                   transform: str) -> None:
        """Content identity for a table DERIVED from an identified scan by
        a deterministic transform (a filter predicate): the derived
        fingerprint hashes the parent fingerprint with the transform's
        stable repr, so a warm repeat of the same query over the same
        files addresses the same cached device arrays — the bridge that
        lets filtered join inputs go HBM-resident.  A different predicate
        or a changed file set changes the fingerprint; stale serving is
        impossible."""
        if parent_identity is None or out is None:
            return
        from hyperspace_tpu.execution.device_cache import derive_fingerprint

        fp, cacheable = parent_identity
        self._scan_fp[id(out)] = (
            derive_fingerprint(fp, transform),
            cacheable & frozenset(out.column_names), out)

    def _propagate_identity(self, out: pa.Table, parent: pa.Table) -> None:
        """Row-preserving transforms (column selection) keep the parent's
        fingerprint: the surviving columns are the same arrays, so cache
        entries stay addressable under the same keys."""
        entry = self._scan_fp.get(id(parent))
        if entry is None or out is None:
            return
        fp, cacheable, _ref = entry
        self._scan_fp[id(out)] = (
            fp, cacheable & frozenset(out.column_names), out)

    def _cache_key(self, identity, column: str, kind: str):
        if identity is None:
            return None
        fp, cacheable = identity
        return (fp, column, kind) if column in cacheable else None

    def _all_resident(self, identity, pairs) -> bool:
        """True when every (column, kind) pair is already cached for this
        scan identity."""
        from hyperspace_tpu.execution.device_cache import global_cache

        cache = global_cache()
        keys = [self._cache_key(identity, c, k) for c, k in pairs]
        return bool(keys) and all(k is not None and cache.contains(k)
                                  for k in keys)

    def _device_column(self, table: pa.Table, column: str, identity,
                       kind: str):
        """The column in its device domain — from the resident cache when
        this scan's file identity is known (hit: zero transfer; miss:
        convert, place on device, and cache), host numpy otherwise."""
        key = self._cache_key(identity, column, kind)
        convert = (columnar.to_order_words if kind == "order"
                   else columnar.to_device_numeric)
        if key is None:
            return convert(table.column(column))
        from hyperspace_tpu.execution.device_cache import global_cache

        cache = global_cache()
        counters = self.stats.setdefault(
            "device_cache", {"hits": 0, "misses": 0})
        arr = cache.get(key)
        if arr is not None:
            counters["hits"] += 1
            return arr
        host = convert(table.column(column))
        with _enable_x64():  # int64 columns must keep full width
            dev = jax.device_put(np.asarray(host))
        timeline.record_transfer("h2d", int(getattr(dev, "nbytes", 0)))
        cache.put(key, dev, self.session.conf.device_cache_bytes)
        counters["misses"] += 1
        return dev

    def _cache_aware_min_rows(self, identity, pairs, kind: str) -> int:
        """The effective routing threshold: the cold-transfer break-even
        normally, the latency-only resident break-even when every input
        (column, kind) pair is already cached for this scan (or will be
        under the 'eager' populate policy)."""
        conf = self.session.conf
        min_rows = conf.device_min_rows(kind)
        if identity is None:
            return min_rows
        # Eager lowers the threshold only when every input is CACHEABLE
        # (computed hidden columns never are, and neither are columns the
        # cache already rejected for exceeding the byte budget —
        # re-shipping them per query would pay the transfer forever, not
        # once).
        from hyperspace_tpu.execution.device_cache import global_cache

        cache = global_cache()
        keys = [self._cache_key(identity, c, k) for c, k in pairs]
        eager_all_cacheable = (
            conf.device_cache_policy == "eager"
            and all(k is not None and not cache.was_rejected(k)
                    for k in keys))
        if eager_all_cacheable or self._all_resident(identity, pairs):
            return min(min_rows, conf.resident_min_rows(kind))
        return min_rows

    def finalize_stats(self) -> None:
        """Close out one query's stats: sample the lightweight memory
        gauges (one getrusage call; live device-buffer bytes only when a
        device cache/kernel actually ran this query — walking live arrays
        is not free) into ``stats["memory"]`` and the process registry
        (``mem.host.peak_rss_mb`` / ``mem.device.live_bytes``).  Called
        once per collect(), never per operator."""
        from hyperspace_tpu.telemetry import metrics

        mem: Dict[str, float] = {}
        try:
            import resource

            mem["peak_rss_mb"] = round(
                resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
                / 1024.0, 1)
            metrics.set_gauge("mem.host.peak_rss_mb", mem["peak_rss_mb"])
        except Exception:  # noqa: BLE001 — non-POSIX platform
            pass
        touched_device = bool(
            self.stats.get("device_cache")
            or any(j.get("strategy") == "device"
                   for j in self.stats.get("join_kernels", []))
            or any(a.get("strategy", "").startswith("device")
                   for a in self.stats.get("aggregates", [])))
        if touched_device:
            import sys

            jax = sys.modules.get("jax")
            if jax is not None:
                try:
                    live = int(sum(int(getattr(a, "nbytes", 0))
                                   for a in jax.live_arrays()))
                    mem["device_live_bytes"] = live
                    metrics.set_gauge("mem.device.live_bytes", live)
                except Exception:  # noqa: BLE001
                    pass
        if mem:
            self.stats["memory"] = mem

    def execute(self, plan: LogicalPlan) -> pa.Table:
        # Per-request deadline (utils/deadline.py): operator ENTRY and
        # EXIT are both phase boundaries.  Entry alone is not enough —
        # the recursion checks every node on the way DOWN (all within
        # microseconds of each other), so a deadline that expires inside
        # a long scan would never abort the aggregation/sort/join work
        # stacked above it.  The exit check fires right after the child
        # work that consumed the budget, before the parent spends more.
        # One contextvar read each when no deadline is set.
        # Timeline (telemetry/timeline.py, conf-gated): each operator
        # dispatch lands as one interval on the "exec" lane, so the
        # Perfetto export shows operator time against the device and
        # build lanes.  Disabled cost: one bool check.
        t0 = timeline.op_begin()
        out = self._execute_node(plan)
        timeline.op_end("exec", type(plan).__name__, t0)
        _deadline.check(type(plan).__name__)
        return out

    def _execute_node(self, plan: LogicalPlan) -> pa.Table:
        _deadline.check(type(plan).__name__)
        if isinstance(plan, InMemory):
            return plan.table
        if isinstance(plan, Scan):
            return self._scan(plan)
        if isinstance(plan, Filter):
            return self._filter(plan)
        if isinstance(plan, Project):
            if isinstance(plan.child, Scan):
                # Scan pushdown: read only the projected columns from disk
                # (the payoff of plan/pruning.py).
                return self._scan(plan.child, columns=plan.columns)
            table = self.execute(plan.child)
            out = table.select(plan.columns)
            # Selection keeps rows (same arrays): identity carries over.
            self._propagate_identity(out, table)
            return out
        if isinstance(plan, Compute):
            table = self.execute(plan.child)
            data = {name: _eval_column(e, table) for name, e in plan.exprs}
            return pa.table(data)
        if isinstance(plan, WithColumns):
            table = self.execute(plan.child)
            for name, e in plan.exprs:
                arr = _eval_column(e, table)
                if name in table.column_names:
                    table = table.set_column(
                        table.column_names.index(name), name, arr)
                else:
                    table = table.append_column(name, arr)
            return table
        if isinstance(plan, Join):
            return self._join(plan)
        if isinstance(plan, Window):
            table = self.execute(plan.child)
            dev = self._try_device_window(table, plan)
            out = dev if dev is not None else _window(table, plan)
            # Appending an analytic column preserves rows and the source
            # arrays: identity carries so a SECOND window (or any
            # downstream op) still routes by residency.
            self._propagate_identity(out, table)
            return out
        if isinstance(plan, Aggregate):
            return self._aggregate(plan)
        if isinstance(plan, Distinct):
            table = self.execute(plan.child)
            names = table.column_names
            if len(set(names)) != len(names):
                raise ValueError(
                    f"distinct() needs unique column names, got {names}; "
                    f"project/rename the duplicates first")
            if table.num_rows == 0:
                return table
            return table.group_by(names).aggregate([]).select(names)
        if isinstance(plan, Sort):
            table = self.execute(plan.child)
            return _sorted_table(table, plan.keys)
        if isinstance(plan, Limit):
            if (isinstance(plan.child, Sort) and plan.n > 0
                    and isinstance(plan.child.child, Aggregate)):
                fused = self._topn_join_aggregate(
                    plan.child.child, plan.child, plan.n)
                if fused is not None:
                    return fused
            if isinstance(plan.child, Sort) and plan.n > 0:
                # Top-N fusion: O(n log k) partial selection instead of a
                # full sort + slice.  "Unstable" only affects tie order,
                # which LIMIT over ORDER BY leaves unspecified anyway.
                # select_k has no null-placement control, so null-bearing
                # keys take the full sort (Spark null order preserved).
                sort = plan.child
                table = self.execute(sort.child)
                if table.num_rows == 0:
                    return table  # select_k rejects zero-row input
                if any(table.column(c).null_count > 0 for c, _ in sort.keys):
                    return _sorted_table(table, sort.keys).slice(0, plan.n)
                idx = pc.select_k_unstable(
                    table, k=min(plan.n, table.num_rows),
                    sort_keys=[(c, "ascending" if asc else "descending")
                               for c, asc in sort.keys])
                return table.take(idx)
            table = self.execute(plan.child)
            return table.slice(0, plan.n)
        if isinstance(plan, SetOp):
            return self._set_op(plan)
        if isinstance(plan, (BucketUnion, Union)):
            tables = [self._execute_appended(c) if _is_appended_branch(c)
                      else self.execute(c) for c in plan.children]
            # Public Union: "permissive" widens same-named numeric columns
            # of different widths (int32 ∪ int64 -> int64, int ∪ float ->
            # double) like Spark's unionByName.  BucketUnion merges an
            # INDEX with its own source's appended rows — a width mismatch
            # there is index/source schema drift that must stay LOUD (a
            # silent int64 ∪ float64 -> double promotion would corrupt
            # >2^53 keys), so it keeps strict-by-name promotion.
            promote = "permissive" if isinstance(plan, Union) \
                and not plan.strict else "default"
            return pa.concat_tables(tables, promote_options=promote)
        raise ValueError(f"Unknown plan node: {type(plan).__name__}")

    # -- set operations -----------------------------------------------------
    def _set_op(self, plan: SetOp) -> pa.Table:
        """INTERSECT/EXCEPT with SQL null-safe row equality: both sides
        stack into one promoted table, every row gets a dense null-safe
        group code (the window engine's encoder), and membership is one
        vectorized isin — no hashing of Python tuples, no join-predicate
        null semantics leaking in."""
        from hyperspace_tpu.ops.window import partition_codes

        left = self.execute(plan.left)
        right = self.execute(plan.right)
        if len(left.column_names) != len(right.column_names):
            raise ValueError(
                f"{plan.kind.upper()} needs equal column counts: "
                f"{left.column_names} vs {right.column_names}")
        r_renamed = right.rename_columns(left.column_names)
        stacked = pa.concat_tables([left, r_renamed],
                                   promote_options="permissive")
        if stacked.num_rows == 0:
            return stacked
        codes = partition_codes(stacked, stacked.column_names)
        ca = codes[:left.num_rows]
        cb = codes[left.num_rows:]
        in_b = np.isin(ca, cb)
        keep = in_b if plan.kind == "intersect" else ~in_b
        kept_rows = np.flatnonzero(keep)
        if kept_rows.size == 0:
            return stacked.slice(0, 0)
        # Distinct: first occurrence per code, in left-row order.
        _uniq, first = np.unique(ca[kept_rows], return_index=True)
        rows = np.sort(kept_rows[first])
        return stacked.take(pa.array(rows))

    # -- aggregate ----------------------------------------------------------
    def _aggregate(self, plan: Aggregate) -> pa.Table:
        with span("exec.aggregate", groups=len(plan.group_by)) as sp:
            attempt = self._try_join_aggregate(plan)
            if attempt is not None:
                kind, payload = attempt
                if kind == "done":
                    sp.set(strategy="fused_join_agg", rows=payload.num_rows)
                    return payload
                # Sides were materialized for the attempt; joined on host.
                out = self._aggregate_on_table(plan, payload)
            else:
                out = self._aggregate_on_table(plan, self.execute(plan.child))
            sp.set(rows=out.num_rows)
            return out

    def _aggregate_on_table(self, plan: Aggregate,
                            table: pa.Table) -> pa.Table:
        # Scan provenance survives the hidden-column appends below (the
        # appended table is a new object); only the ORIGINAL columns stay
        # cacheable — computed inputs are query-specific.
        identity = self._scan_identity(table)
        # Expression inputs (sum(price * (1 - discount))) materialize as
        # hidden columns first; the reduction then sees plain columns.
        agg_inputs: List = []
        for i, (func, agg_in, _out) in enumerate(plan.aggs):
            if isinstance(agg_in, Expr) and not isinstance(agg_in, Col):
                name = f"__agg_in_{i}"
                while name in table.column_names:
                    name += "_"
                table = table.append_column(name, _eval_column(agg_in, table))
                agg_inputs.append(name)
            elif isinstance(agg_in, Col):
                agg_inputs.append(agg_in.name)
            else:
                agg_inputs.append(agg_in)
        specs = [([] if func == "count_all" else agg_inputs[i], func)
                 for i, (func, _in, _out) in enumerate(plan.aggs)]
        if plan.group_by:
            device = self._try_device_aggregate(table, plan, agg_inputs,
                                                identity)
            if device is not None:
                return device
            keys = list(plan.group_by)
            out = table.group_by(keys).aggregate(specs)
            # Map output columns POSITIONALLY from arrow's documented
            # layout: the key block is contiguous at the front (pyarrow
            # >= 8) or the back (older), in group_by order; the other
            # positions are the agg results in spec order.  First-name
            # matching would silently swap a key with an auto-named agg
            # column (e.g. key 'v_sum' vs agg output 'v_sum').
            names = out.column_names
            nk = len(keys)
            if names[:nk] == keys:
                key_idx, agg_idx = list(range(nk)), list(range(nk, len(names)))
            elif names[-nk:] == keys:
                key_idx = list(range(len(names) - nk, len(names)))
                agg_idx = list(range(len(names) - nk))
            else:
                raise AssertionError(
                    f"Unrecognized group-by output layout {names} for keys "
                    f"{keys}")
            assert len(agg_idx) == len(plan.aggs)
            data = {k: out.column(i) for k, i in zip(keys, key_idx)}
            for (_f, _c, out_name), i in zip(plan.aggs, agg_idx):
                data[out_name] = out.column(i)
            return pa.table(data)
        # Global aggregation: one row, computed per spec.
        cols, vals = [], []
        for i, (func, _in, out_name) in enumerate(plan.aggs):
            if func == "count_all":
                value = table.num_rows
            elif func == "count":
                column = table.column(agg_inputs[i])
                value = table.num_rows - column.null_count
            else:
                value = getattr(pc, func)(table.column(agg_inputs[i])).as_py()
            cols.append(out_name)
            vals.append(value)
        return pa.table({n: [v] for n, v in zip(cols, vals)})

    def _try_device_aggregate(self, table: pa.Table, plan: Aggregate,
                              agg_inputs: List[str],
                              identity=None) -> Optional[pa.Table]:
        """Route an eligible GROUP BY through the device segment-reduction
        kernel (ops/aggregate.py).  Eligible: enough rows (conf
        device_agg_min_rows, or the resident threshold when the inputs are
        HBM-cached), integer/bool group keys (float keys would split
        arrow's single NaN group by bit pattern), null-free numeric
        inputs, and only sum/min/max/mean/count/count_all.  Output rows
        come back in ascending key order — GROUP BY output order is
        unspecified, as on the host path."""
        from hyperspace_tpu.ops.aggregate import AGG_OPS

        conf = self.session.conf
        if table.num_rows == 0:
            return None
        pairs = [(k, "order") for k in plan.group_by] + [
            (agg_inputs[i], "num")
            for i, (func, _in, _out) in enumerate(plan.aggs)
            if func not in ("count", "count_all")]
        min_rows = self._cache_aware_min_rows(identity, pairs, "agg")
        # An active mesh opens the sharded aggregate at its own
        # threshold, like the filter/join dispatches.
        mesh = self._active_mesh()
        if mesh is not None:
            min_rows = min(min_rows, conf.mesh_agg_min_rows)
        if table.num_rows < min_rows:
            return None
        if any(func not in AGG_OPS for func, _i, _o in plan.aggs):
            return None
        for k in plan.group_by:
            t = table.schema.field(k).type
            # uint64 excluded: the device domain is int64 (a >= 2^63 value
            # would fail the safe cast; smaller ones would flip the output
            # type with row count).  Narrower unsigned types fit int64.
            if not (pa.types.is_integer(t) or pa.types.is_boolean(t)) \
                    or pa.types.is_uint64(t):
                return None
            if table.column(k).null_count > 0:
                return None
        for i, (func, _in, _out) in enumerate(plan.aggs):
            if func == "count_all":
                continue
            if func == "count":
                # count == group row count only when the input has no
                # nulls; any TYPE is fine since no value is reduced.
                if table.column(agg_inputs[i]).null_count > 0:
                    return None
                continue
            t = table.schema.field(agg_inputs[i]).type
            # Strictly int/float (uint64 excluded — device domain is
            # int64): temporal columns would crash min/max at the cast
            # back (and "sum" over dates must raise, as the host path
            # does); bool sums promote to uint64 on host but int64 on
            # device — excluded rather than special-cased.
            if not (pa.types.is_integer(t) or pa.types.is_floating(t)) \
                    or pa.types.is_uint64(t) \
                    or table.column(agg_inputs[i]).null_count > 0:
                return None

        from hyperspace_tpu.ops.aggregate import (
            grouped_aggregate,
            grouped_aggregate_mesh,
        )

        use_mesh = (mesh is not None
                    and table.num_rows >= conf.mesh_agg_min_rows)
        if use_mesh:
            # Sharded path: rows partitioned by group-key bucket
            # ownership — a group is reduced whole on one device, so
            # every op is exact and no merge pass exists.  Host arrays
            # only (sharded placement is its own layout — the
            # single-device resident cache is bypassed).
            key_words = [np.asarray(columnar.to_order_words(
                table.column(k))) for k in plan.group_by]
            value_cols = [
                np.asarray(columnar.to_device_numeric(
                    table.column(agg_inputs[i])))
                for i, (func, _in, _out) in enumerate(plan.aggs)
                if func not in ("count", "count_all")]
            first_rows, counts, results = grouped_aggregate_mesh(
                key_words, value_cols, [f for f, _i, _o in plan.aggs],
                mesh, pad_to=conf.device_batch_rows)
            resident = False
        else:
            resident = self._all_resident(identity, pairs)
            key_words = [self._device_column(table, k, identity, "order")
                         for k in plan.group_by]
            # One array per NON-count aggregate; counts ship nothing (a
            # dummy column would be ~8 B/row of pointless transfer).
            value_cols = [
                self._device_column(table, agg_inputs[i], identity, "num")
                for i, (func, _in, _out) in enumerate(plan.aggs)
                if func not in ("count", "count_all")]
            first_rows, counts, results = grouped_aggregate(
                key_words, value_cols, [f for f, _i, _o in plan.aggs],
                pad_to=conf.device_batch_rows)
        self.stats.setdefault("aggregates", []).append({
            "strategy": "mesh-segment" if use_mesh else "device-segment",
            "groups": int(len(first_rows)),
            "rows": table.num_rows,
            "resident": resident,
        })
        # Gather only the key columns (the full-width table would random-
        # gather every unused value column too).
        taken = table.select(list(plan.group_by)).take(pa.array(first_rows))
        data = {k: taken.column(k) for k in plan.group_by}
        for (func, _in, out_name), res, i in zip(
                plan.aggs, results, range(len(results))):
            if func in ("count", "count_all"):
                data[out_name] = pa.array(counts.astype(np.int64))
            elif func in ("min", "max"):
                # Reductions return existing values: restore the input type
                # (the device ran float64/int64).
                src_type = table.schema.field(agg_inputs[i]).type
                data[out_name] = pc.cast(pa.array(res), src_type)
            elif func == "mean":
                data[out_name] = pa.array(res.astype(np.float64))
            else:  # sum: int stays int64, float stays float64 — arrow's
                # own promotion for sums.
                data[out_name] = pa.array(res)
        return pa.table(data)

    # -- device windows (whole-partition aggregates over resident data) -----
    def _try_device_window(self, table: pa.Table,
                           plan: Window) -> Optional[pa.Table]:
        """Whole-partition window aggregates (``sum(x) OVER (PARTITION
        BY k)``) over HBM-resident columns: the reduction runs on the
        segment kernel (ops/aggregate.py — the round-4 verdict's ask),
        only per-GROUP results return, and the broadcast back to rows is
        one host searchsorted.  Scope: single int/bool partition key,
        null-free numeric value, no ORDER BY/frame (running frames are
        the vectorized host engine's job); routing by the resident
        'agg' threshold, like grouped aggregation."""
        conf = self.session.conf
        if (plan.frame is not None or plan.order_by
                or len(plan.partition_by) != 1
                or plan.func not in ("sum", "min", "max", "mean",
                                     "count")
                or table.num_rows == 0):
            return None
        key = plan.partition_by[0]
        kt = table.schema.field(key).type
        if not (pa.types.is_integer(kt) or pa.types.is_boolean(kt)) \
                or pa.types.is_uint64(kt) \
                or table.column(key).null_count > 0:
            return None
        pairs = [(key, "order")]
        src_type = None
        if plan.func == "count":
            # count over a null-free value equals the group row count:
            # nothing ships beyond the key, and the value column must
            # not enter `pairs` (it is never cached, so it would pin
            # _all_resident to False forever).
            if plan.value is not None \
                    and table.column(plan.value).null_count > 0:
                return None
        else:
            if plan.value is None:
                return None
            src_type = table.schema.field(plan.value).type
            if not (pa.types.is_integer(src_type)
                    or pa.types.is_floating(src_type)) \
                    or pa.types.is_uint64(src_type) \
                    or table.column(plan.value).null_count > 0:
                return None
            pairs.append((plan.value, "num"))
        identity = self._scan_identity(table)
        if table.num_rows < self._cache_aware_min_rows(identity, pairs,
                                                       "agg"):
            return None
        from hyperspace_tpu.ops.aggregate import grouped_aggregate

        resident = self._all_resident(identity, pairs)
        key_words = [self._device_column(table, key, identity, "order")]
        value_cols = [] if plan.func == "count" else [
            self._device_column(table, plan.value, identity, "num")]
        first_rows, counts, results = grouped_aggregate(
            key_words, value_cols, [plan.func if plan.func != "count"
                                    else "count_all"],
            pad_to=conf.device_batch_rows)
        group_keys = table.column(key).take(pa.array(first_rows))
        gk = np.asarray(columnar.to_device_numeric(group_keys))
        rows = np.asarray(
            columnar.to_device_numeric(table.column(key)))
        idx = np.searchsorted(gk, rows)  # groups ascend by key
        res = results[0]
        if plan.func == "count":
            out = pa.array(counts.astype(np.int64)).take(pa.array(idx))
        elif plan.func in ("min", "max"):
            out = pc.cast(pa.array(res), src_type).take(pa.array(idx))
        elif plan.func == "mean":
            out = pa.array(res.astype(np.float64)).take(pa.array(idx))
        else:  # sum: int64 / float64 by the device result dtype
            out = pa.array(res).take(pa.array(idx))
        self.stats.setdefault("windows", []).append({
            "strategy": "device-segment", "rows": table.num_rows,
            "groups": int(len(counts)), "resident": resident})
        if plan.name in table.column_names:
            return table.set_column(
                table.column_names.index(plan.name), plan.name, out)
        return table.append_column(plan.name, out)

    # -- fused join+aggregate (the whole Q3/Q10 hot path on device) ---------
    _JOIN_AGG_OPS = ("sum", "min", "max", "mean", "count", "count_all")

    def _topn_join_aggregate(self, agg: Aggregate, sort: Sort,
                             n: int) -> Optional[pa.Table]:
        """ORDER BY <aggregate output> LIMIT n over a fused join+agg:
        the ranking runs on device too, so only n groups come home —
        the full Q3/Q10 pipeline (filter ⨝ index → group → top-N) with
        O(n) host traffic.  None = not applicable, take the normal
        path."""
        if len(sort.keys) != 1:
            return None
        key, asc = sort.keys[0]
        agg_index = next((i for i, (_f, _in, out) in enumerate(agg.aggs)
                          if out == key), None)
        if agg_index is None:  # ordering by a group column: no device win
            return None
        attempt = self._try_join_aggregate(
            agg, topn=(agg_index, bool(asc), int(n)))
        if attempt is None:
            return None
        kind, payload = attempt
        if kind == "done":
            table = payload  # k rows already — exact re-sort is cheap
        else:
            table = self._aggregate_on_table(agg, payload)
        return _sorted_table(table, sort.keys).slice(0, n)

    def _static_column_type(self, node, name: str):
        """Arrow type of ``name`` in ``node``'s output when derivable
        WITHOUT executing anything (Filter/Project/Sort/Limit chains
        over Scan/InMemory — the shapes join sides actually take);
        None when unknown."""
        while True:
            if isinstance(node, (Filter, Sort, Limit)):
                node = node.child
                continue
            if isinstance(node, Project):
                if name not in node.columns:
                    return None
                node = node.child
                continue
            if isinstance(node, InMemory):
                if name not in node.table.column_names:
                    return None
                return node.table.schema.field(name).type
            if isinstance(node, Scan):
                try:
                    from hyperspace_tpu.io.parquet import schema_to_arrow

                    m = {k.lower(): v for k, v in
                         self.session.schema_map_of(node).items()}
                    t = m.get(name.lower())
                    return schema_to_arrow({"c": t}).field(0).type \
                        if t is not None else None
                except Exception:
                    return None
            return None

    def _plan_row_upper_bound(self, node) -> Optional[int]:
        """Row UPPER BOUND for a join side without executing it: parquet
        footer counts under Filter/Project chains (filters only shrink).
        None when the shape or format doesn't allow a cheap answer."""
        while isinstance(node, (Filter, Project, Sort, Limit)):
            node = node.child
        if isinstance(node, InMemory):
            return node.table.num_rows
        if not isinstance(node, Scan):
            return None
        rel = node.relation
        try:
            import pyarrow.parquet as pq

            if rel.file_paths is not None:
                paths = list(rel.file_paths)
            else:
                paths = [f.name for f in list_data_files(rel.root_paths)]
            return sum(pq.ParquetFile(p).metadata.num_rows
                       for p in paths)
        except Exception:
            return None

    def _join_agg_static_pregate(self, plan: Aggregate,
                                 child: Join) -> bool:
        """False when the fused path is KNOWABLY ineligible before any
        execution — ambiguous/missing columns, or statically resolvable
        types outside the kernel's domain.  An early False preserves
        the normal path (with its bucketed join) at zero cost; unknowns
        stay True and the data-dependent checks decide later."""
        try:
            l_cols = set(child.left.output_columns(self.session.schema_of))
            r_cols = set(child.right.output_columns(self.session.schema_of))
        except Exception:
            return True  # unresolvable statically: decide after exec
        refs = set(plan.group_by)
        for _func, agg_in, _out in plan.aggs:
            if isinstance(agg_in, Col):
                refs.add(agg_in.name)
            elif isinstance(agg_in, str):
                if agg_in:
                    refs.add(agg_in)
            elif isinstance(agg_in, Expr):
                refs |= set(agg_in.referenced_columns())
        for name in refs:
            in_l, in_r = name in l_cols, name in r_cols
            if in_l == in_r:  # missing or ambiguous
                return False
            side = child.left if in_l else child.right
            t = self._static_column_type(side, name)
            if t is None:
                continue  # unknown: the late check decides
            if name in plan.group_by:
                if not (pa.types.is_integer(t) or pa.types.is_boolean(t)
                        or pa.types.is_temporal(t)) \
                        or pa.types.is_uint64(t):
                    return False
            elif not (pa.types.is_integer(t) or pa.types.is_floating(t)) \
                    or pa.types.is_uint64(t):
                return False
        return True

    def _try_join_aggregate(self, plan: Aggregate, topn=None):
        """Route ``aggregate(inner equi-join)`` through the fused device
        pipeline (ops/join_agg.py): join match, gather, expression
        evaluation, and segment reduction all happen in HBM; only
        per-group results return.  The north-star shapes
        (BASELINE.md Q3/Q10) are exactly this pattern — executed
        separately, the full joined row set would cross to the host.

        Returns None to leave the plan alone (structural mismatch, or
        the device isn't plausibly profitable); ("done", table) with the
        fused result; or ("joined", table) when the sides were
        materialized for the attempt but eligibility failed — the
        caller aggregates the host-joined table without re-executing.
        """
        conf = self.session.conf
        if not plan.group_by:
            return None
        child = plan.child
        if not isinstance(child, Join) or child.how != "inner" \
                or child.residual is not None:
            return None
        # Plausibility gate BEFORE touching anything: the eager populate
        # policy (pay the transfer once, serve repeats from HBM), or a
        # genuinely LOW calibrated cold threshold (locally attached
        # chips, where cold device joins win outright).  Anything else —
        # including the conservative static defaults — leaves the
        # regular path, bucketed host join included, untouched.
        if conf.device_cache_policy != "eager" \
                and conf.device_min_rows("join_agg") > (1 << 22):
            return None
        if any(func not in self._JOIN_AGG_OPS
               for func, _i, _o in plan.aggs):
            return None
        # min/max need a plain column (their result restores its type):
        # statically decidable, so decide it BEFORE materializing sides.
        for func, agg_in, _out in plan.aggs:
            if func in ("min", "max") and not isinstance(agg_in,
                                                         (Col, str)):
                return None
        from hyperspace_tpu.plan.expr import as_equi_join_pairs

        pairs = as_equi_join_pairs(child.condition)
        if pairs is None or len(pairs) != 1:
            return None
        if not self._join_agg_static_pregate(plan, child):
            # Statically ineligible: leave the plan alone so the normal
            # path (bucketed host join included) runs untouched.
            return None
        a, b = pairs[0]
        refs = set(plan.group_by)
        for func, agg_in, _out in plan.aggs:
            if func != "count_all":
                refs |= set(_agg_refs(agg_in))
        # A side whose columns HBM already holds is served from there,
        # its files unopened; every other side is decoded.
        left = self._resident_side(child.left, (a, b), refs)
        right = self._resident_side(child.right, (a, b), refs)
        # Row pre-gate: a resident side's rows are known, a side still to
        # decode has an upper bound from its parquet FOOTERS.  When even
        # that cannot clear the lowest applicable threshold, the device
        # can never win — bail before materializing anything so small
        # joins keep their bucketed host path.
        lo_thresh = min(conf.device_min_rows("join_agg"),
                        conf.resident_min_rows("join_agg"))
        est_l = left.num_rows if left is not None \
            else self._plan_row_upper_bound(child.left)
        est_r = right.num_rows if right is not None \
            else self._plan_row_upper_bound(child.right)
        if est_l is not None and est_r is not None \
                and max(est_l, est_r) < lo_thresh:
            return None

        if left is None:
            left = self._decoded_side(child.left)
        if right is None:
            right = self._decoded_side(child.right)

        def fallback():
            self._count_join_sides(0)
            lt = left.table if left.table is not None \
                else self.execute(child.left)
            rt = right.table if right.table is not None \
                else self.execute(child.right)
            self.stats["joins"].append(
                {"strategy": "plain", "how": "inner"})
            return ("joined", self._host_join_tables(
                lt, rt, child.condition, "inner"))

        if a in left.names and b in right.names:
            lk_name, rk_name = a, b
        elif b in left.names and a in right.names:
            lk_name, rk_name = b, a
        else:
            return fallback()
        if (lk_name == rk_name or lk_name in right.names
                or rk_name in left.names):
            # Name present on both sides: the flat column index below
            # couldn't tell them apart.
            return fallback()
        if not (columnar.is_numeric_type(
                    left.schema.field(lk_name).type)
                and columnar.is_numeric_type(
                    right.schema.field(rk_name).type)):
            return fallback()
        # Inner join: null keys never match — drop them up front (with
        # derived identity so residency carries across repeats; a
        # resident side was resolved past its null keys already).
        lv = self._drop_null_keys(left, lk_name)
        rv = self._drop_null_keys(right, rk_name)
        if lv.num_rows == 0 or rv.num_rows == 0:
            return fallback()

        def side_of(name: str) -> Optional[str]:
            in_l = name in lv.names
            in_r = name in rv.names
            if in_l == in_r:  # missing or ambiguous
                return None
            return "l" if in_l else "r"

        def table_of(side: str) -> "_JoinSide":
            return lv if side == "l" else rv

        # Group keys: int/bool/temporal (int64 device domain), null-free.
        for k in plan.group_by:
            side = side_of(k)
            if side is None:
                return fallback()
            t = table_of(side).schema.field(k).type
            if not (pa.types.is_integer(t) or pa.types.is_boolean(t)
                    or pa.types.is_temporal(t)) or pa.types.is_uint64(t):
                return fallback()
            if table_of(side).null_count(k) > 0:
                return fallback()
        # Aggregate inputs: strictly int/float null-free references;
        # min/max need a plain column (the result restores its type).
        from hyperspace_tpu.ops.filter import build_value_fn

        agg_ref_names: List[str] = []
        for func, agg_in, _out in plan.aggs:
            if func == "count_all":
                continue
            if func == "count" and isinstance(agg_in, Expr) \
                    and not isinstance(agg_in, Col):
                # count(expr): the kernel counts group rows, which only
                # equals count(non-null expr) when the expression can
                # never produce null from null-free inputs — true for
                # the device arithmetic subset (+ - * neg), NOT for
                # division (x/0 -> null).  Validate through the same
                # compiler; ineligible shapes take the host path.
                try:
                    build_value_fn(agg_in, sorted(agg_in.referenced_columns()))
                except ValueError:
                    return fallback()
            if func in ("min", "max") and not (
                    isinstance(agg_in, (Col, str))):
                return fallback()
            for r in _agg_refs(agg_in):
                side = side_of(r)
                if side is None:
                    return fallback()
                t = table_of(side).schema.field(r).type
                if not (pa.types.is_integer(t)
                        or pa.types.is_floating(t)) \
                        or pa.types.is_uint64(t):
                    return fallback()
                if table_of(side).null_count(r) > 0:
                    return fallback()
                agg_ref_names.append(r)

        # Routing: cold-transfer break-even, or the resident/eager
        # threshold when every referenced column of a side is cached
        # (or will be) for that side's — possibly filter-derived —
        # identity.
        id_l, id_r = lv.identity, rv.identity
        need_l = sorted({lk_name} | {
            c for c in set(plan.group_by) | set(agg_ref_names)
            if side_of(c) == "l"})
        need_r = sorted({rk_name} | {
            c for c in set(plan.group_by) | set(agg_ref_names)
            if side_of(c) == "r"})
        pl = [(c, "num") for c in need_l]
        pr = [(c, "num") for c in need_r]
        max_rows = max(lv.num_rows, rv.num_rows)
        cold = conf.device_min_rows("join_agg")
        # The sharded pipeline opens at its own threshold (topn fusion
        # and HBM residency keep the single-device kernel — the mesh
        # path re-partitions between stages, which only pays off when
        # the data is big enough to scale with the devices).
        mesh = self._active_mesh()
        use_mesh = (mesh is not None and topn is None
                    and max_rows >= conf.mesh_join_min_rows)
        use_device = max_rows >= cold
        if not use_device:
            eff = max(self._cache_aware_min_rows(id_l, pl, "join_agg"),
                      self._cache_aware_min_rows(id_r, pr, "join_agg"))
            use_device = eff < cold and max_rows >= eff
        if not use_device and not use_mesh:
            return fallback()
        resident = (lv.arrays is not None or self._all_resident(id_l, pl)) \
            and (rv.arrays is not None or self._all_resident(id_r, pr))
        use_mesh = use_mesh and not resident
        if use_mesh:
            # The sharded path takes host arrays: decode a side that HBM
            # would have served.
            lv = self._decoded_if_resident(lv, child.left, lk_name)
            rv = self._decoded_if_resident(rv, child.right, rk_name)

        # Device arrays for every referenced column (cache-aware); the
        # mesh path takes HOST arrays instead — sharded placement is its
        # own layout, so the single-device resident cache is bypassed.
        ref_order: List[Tuple[str, str]] = \
            [("l", c) for c in need_l] + [("r", c) for c in need_r]
        col_ix = {c: i for i, (_s, c) in enumerate(ref_order)}
        if use_mesh:
            columns = [np.asarray(columnar.to_device_numeric(
                table_of(s).table.column(c))) for s, c in ref_order]
        else:
            columns = [self._side_column(table_of(s), c)
                       for s, c in ref_order]
        sides = [s for s, _c in ref_order]
        group_ix = [col_ix[k] for k in plan.group_by]
        value_fns, lits_list, agg_ops = [], [], []
        for func, agg_in, _out in plan.aggs:
            agg_ops.append(func)
            if func in ("count", "count_all"):
                continue
            expr = Col(agg_in) if isinstance(agg_in, str) else agg_in
            try:
                fn, lits = build_value_fn(
                    expr, [c for _s, c in ref_order])
            except ValueError:
                return fallback()
            value_fns.append(fn)
            lits_list.append(lits)

        from hyperspace_tpu.ops.join_agg import (
            join_group_aggregate,
            join_group_aggregate_mesh,
        )

        if use_mesh:
            counts, results, key_values = join_group_aggregate_mesh(
                columns[col_ix[lk_name]], columns[col_ix[rk_name]],
                columns, sides, group_ix, agg_ops, value_fns, lits_list,
                mesh, pad_to=conf.device_batch_rows)
        else:
            counts, results, key_values = join_group_aggregate(
                columns[col_ix[lk_name]], columns[col_ix[rk_name]],
                columns, sides, group_ix, agg_ops, value_fns, lits_list,
                topn=topn)
        n_resident = (lv.table is None) + (rv.table is None)
        self.stats["joins"].append({
            "strategy": "mesh-fused-agg" if use_mesh
            else "device-fused-agg", "how": "inner",
            "resident": resident, "resident_sides": n_resident})
        self.stats.setdefault("aggregates", []).append({
            "strategy": "mesh-join-agg" if use_mesh
            else "device-join-agg", "groups": int(len(counts)),
            "rows": int(max_rows), "resident": resident,
            "topn": None if topn is None else int(topn[2])})
        # Group keys come back in the int64 domain (a resident side has no
        # host table to take them from): cast to each column's type.
        data = {k: columnar.from_device_int64(
                    key_values[i], table_of(side_of(k)).schema.field(k).type)
                for i, k in enumerate(plan.group_by)}
        # `results` is aligned with plan.aggs: the segment kernel emits
        # one output per op (count slots carry the group counts).
        for (func, agg_in, out_name), res in zip(plan.aggs, results):
            if func in ("count", "count_all"):
                data[out_name] = pa.array(counts.astype(np.int64))
                continue
            if func in ("min", "max"):
                name = agg_in.name if isinstance(agg_in, Col) else agg_in
                src_type = table_of(side_of(name)).schema.field(name).type
                data[out_name] = pc.cast(pa.array(res), src_type)
            elif func == "mean":
                data[out_name] = pa.array(res.astype(np.float64))
            else:  # sum: dtype carried by the device result
                data[out_name] = pa.array(res)
        if not use_mesh:
            self._remember_join_sides((left, lv, right, rv))
        self._count_join_sides(n_resident)
        return ("done", pa.table(data))

    def _decoded_side(self, node) -> "_JoinSide":
        table = self.execute(node)
        return _JoinSide.decoded(table, self._scan_identity(table))

    def _decoded_if_resident(self, side: "_JoinSide", node,
                             key: str) -> "_JoinSide":
        if side.table is not None:
            return side
        return self._drop_null_keys(self._decoded_side(node), key)

    def _drop_null_keys(self, side: "_JoinSide", key: str) -> "_JoinSide":
        if side.table is None or side.null_count(key) == 0:
            return side
        out = side.table.filter(pc.is_valid(side.table.column(key)))
        self._register_derived_identity(out, side.identity,
                                        f"dropnull:{key}")
        return _JoinSide.decoded(out, self._scan_identity(out))

    def _side_column(self, side: "_JoinSide", column: str):
        if side.arrays is not None:
            return side.arrays[column]
        return self._device_column(side.table, column, side.identity, "num")

    def _resident_side(self, node, pair, refs) -> Optional["_JoinSide"]:
        """``node`` as a fused join's side served from HBM with none of
        its files opened, or None to decode it.

        The side must be ``(Project|Filter)*`` over a bucketed index
        scan.  Its identity comes from the plan alone: the fingerprint of
        the bucket-pruned file list, derived through each Filter's
        predicate bottom-up as ``_filter`` derives it, and through
        ``dropnull:<key>`` where the side's manifest counts null keys.
        The side is resident when the device cache holds that identity's
        manifest (``device_cache.SideManifest``) and every column of
        ``refs`` and the join key that it outputs.  The arrays are taken
        before the side counts as resident: an eviction in between
        decodes it.  The resolution runs in an ``exec.scan`` span tagged
        ``decoded=False``, whatever it finds."""
        if not self._cache_on():
            return None
        wrappers, scan = _unwrap_chain(node)
        if not _is_bucketed_index_scan(scan) or scan.relation.hypothetical:
            return None
        try:
            scanned = self.session.schema_of(scan)  # planning cached it
        except Exception:  # noqa: BLE001 — unreadable: decode says why
            return None
        names = _chain_columns(wrappers, scanned)
        keys = [c for c in pair if names is not None and c in names]
        if len(keys) != 1:
            return None
        from hyperspace_tpu.execution.device_cache import (
            derive_fingerprint,
            global_cache,
        )

        rel = scan.relation
        with span("exec.scan", relation=rel.index_scan_of, is_index=True,
                  decoded=False, resident=False) as sp:
            listed = list(rel.file_paths)
            paths = _prune_to_buckets(listed, rel.prune_to_buckets)
            fp, nbytes = self._stat_files(paths)
            if not paths or fp is None:
                return None
            for w in reversed(wrappers):
                if isinstance(w, Filter):
                    fp = derive_fingerprint(fp, f"filter:{w.condition!r}")
            cache = global_cache()
            manifest = cache.side_manifest(fp)
            if manifest is not None \
                    and manifest.null_counts.get(keys[0], 0) > 0:
                fp = derive_fingerprint(fp, f"dropnull:{keys[0]}")
                manifest = cache.side_manifest(fp)
            if manifest is None or not all(n in manifest.null_counts
                                           for n in names):
                return None
            col_keys = {c: (fp, c, "num")
                        for c in {keys[0]} | (set(refs) & set(names))}
            if not all(cache.contains(k) for k in col_keys.values()):
                return None
            arrays = {c: cache.get(k) for c, k in col_keys.items()}
            if any(arr is None for arr in arrays.values()):
                return None  # evicted since the probe
            self.stats.setdefault(
                "device_cache", {"hits": 0, "misses": 0})["hits"] += \
                len(arrays)
            record = self._scan_record(rel, paths, listed, nbytes,
                                       decoded=False)
            sp.set(**record, rows=manifest.num_rows, resident=True)
        schema = pa.schema([manifest.schema.field(n) for n in names])
        return _JoinSide(None, schema, manifest.num_rows,
                         {n: manifest.null_counts[n] for n in names},
                         (fp, frozenset(names)), arrays, record)

    def _remember_join_sides(self, sides) -> None:
        """After a fused run: a manifest for each side it decoded (so a
        repeat can serve the side from HBM), the scan record of each side
        it did not."""
        from hyperspace_tpu.execution.device_cache import global_cache

        budget = self.session.conf.device_cache_bytes
        for side in sides:
            if side.table is not None:
                if side.identity is not None:
                    global_cache().put_side(side.identity[0], side.table,
                                            budget)
            elif side.scan_record is not None:
                self._log_scan(side.scan_record)
                side.scan_record = None  # a side listed twice logs once

    def _count_join_sides(self, resident: int) -> None:
        from hyperspace_tpu.telemetry import metrics

        metrics.inc("exec.join_agg.sides", 2)
        metrics.inc("exec.join_agg.sides_resident", resident)

    # -- scan ---------------------------------------------------------------
    def _scan(self, plan: Scan, columns: Optional[List[str]] = None) -> pa.Table:
        with span("exec.scan") as sp:
            out = self._scan_inner(plan, columns, sp)
            sp.set(rows=out.num_rows)
            return out

    def _scan_inner(self, plan: Scan, columns, sp) -> pa.Table:
        rel = plan.relation
        if rel.hypothetical:
            # A what-if plan leaked past the advisor (advisor/hypothetical
            # .py): hypothetical index scans have zero data files and MUST
            # never execute — answering from one would silently return an
            # empty table for a query that has rows.
            from hyperspace_tpu.exceptions import HyperspaceError

            raise HyperspaceError(
                f"Plan contains a hypothetical index scan "
                f"({rel.index_scan_of!r}); what-if plans are for analysis "
                f"only and can never execute (docs/17-advisor.md)")
        with span("scan.files"):
            read_format = physical_read_format(rel.file_format)
            lake_relation = None
            if rel.file_paths is not None:
                paths = list(rel.file_paths)
            elif rel.file_format.lower() in LAKE_DATA_FORMATS:
                # Lake formats resolve files through the provider's
                # snapshot — a directory walk would see removed/
                # overwritten files too.
                lake_relation = self.session.source_provider_manager \
                    .get_relation(plan)
                paths = [f.name for f in lake_relation.all_files()]
                read_format = lake_relation.read_format
            else:
                paths = [f.name for f in list_data_files(rel.root_paths)]
            all_paths = paths
            paths = _prune_to_buckets(paths, rel.prune_to_buckets)
            # One stat per file gives the bytes about to be read and the
            # cache fingerprint, taken before the read: a file rewritten
            # in between keys its new rows under the old identity, which
            # no later scan resolves.  A vanished file surfaces in
            # read_table with a better error than here.
            fp, bytes_read = self._stat_files(paths)
        scan_record = self._scan_record(rel, paths, all_paths, bytes_read,
                                        decoded=True)
        self._log_scan(scan_record)
        sp.set(**scan_record)
        if not paths:
            # Bucket pruning removed every file (key hashes to an empty
            # bucket): the result is empty but MUST keep the scan schema so
            # downstream Project/Filter still resolve.
            from hyperspace_tpu.io.parquet import read_schema, schema_to_arrow

            if all_paths:
                schema = schema_to_arrow(read_schema(
                    all_paths[0], read_format, rel.options_dict))
                empty = schema.empty_table()
            elif lake_relation is not None:
                # A lake table whose active file set is empty still has a
                # schema in its metadata — downstream Project/Filter must
                # resolve against it, not against a column-less table.
                empty = schema_to_arrow(lake_relation.schema()).empty_table()
            else:
                empty = pa.table({})
            return empty.select(columns) if columns else empty
        # Source scans materialize hive partition columns from paths; index
        # data reads (index_scan_of) never do — v__=N is not a partition.
        roots = rel.root_paths if rel.index_scan_of is None else None
        out = read_table(paths, read_format, columns, rel.options_dict,
                         partition_roots=roots)
        if columns:
            out = out.select(columns)
        scan_record["rows"] = out.num_rows
        self._register_scan_identity(out, fp)
        return out

    @staticmethod
    def _scan_record(rel, paths, all_paths, bytes_read: int,
                     decoded: bool) -> dict:
        """One scan's record; ``decoded`` False when its rows came from
        HBM and none of its files was opened."""
        return {
            "relation": rel.index_scan_of or ",".join(rel.root_paths),
            "is_index": bool(rel.index_scan_of),
            "files_read": len(paths),
            "files_listed": len(all_paths),
            "bytes_read": bytes_read,
            "decoded": decoded,
        }

    def _log_scan(self, scan_record: dict) -> None:
        self.stats["scans"].append(scan_record)
        # The run report carries per-scan IO too: it is what the advisor's
        # workload capture consumes (bytes actually scanned per relation)
        # and what "why was my query slow" reads (telemetry/report.py).
        from hyperspace_tpu.telemetry import report as run_report

        run_report.record("scan", **scan_record)

    # -- filter -------------------------------------------------------------
    def _filter(self, plan: Filter) -> pa.Table:
        table = self.execute(plan.child)
        if table.num_rows == 0:
            return table
        # The span covers this operator's own work, not its child's.
        with span("exec.filter", rows_in=table.num_rows) as sp:
            mask = self._eval_predicate(plan.condition, table)
            out = table.filter(pa.array(mask))
            # The filtered rows are a pure function of (scan files,
            # predicate): give the output a derived identity so repeats
            # of the same query can serve its columns from the HBM cache
            # (the resident join's filtered sides depend on this).
            self._register_derived_identity(
                out, self._scan_identity(table),
                f"filter:{plan.condition!r}")
            sp.set(rows=out.num_rows)
        return out

    def _eval_predicate(self, expr: Expr, table: pa.Table) -> np.ndarray:
        cols = expr.referenced_columns()
        # Device path requires at least one column and all referenced columns
        # numeric and null-free; everything else (strings, nullables,
        # constant predicates) takes the arrow path, which owns SQL
        # three-valued-logic semantics.
        # Small batches stay on host: the device round trip's fixed latency
        # dwarfs a vectorized arrow pass (conf device_filter_min_rows).
        # With an active mesh the MESH threshold also opens the device
        # path — otherwise raising device_filter_min_rows above
        # mesh_filter_min_rows would make the sharded path unreachable
        # in between.  ``hyperspace.parallel.mesh.enabled=off`` pins
        # every dispatch below to the bit-equal single-device path.
        identity = self._scan_identity(table)
        pairs = [(c, "num") for c in cols]
        min_rows = self._cache_aware_min_rows(identity, pairs, "filter")
        mesh = self._active_mesh()
        if mesh is not None:
            min_rows = min(min_rows, self.session.conf.mesh_filter_min_rows)
        numeric = bool(cols) \
            and table.num_rows >= min_rows \
            and all(
                columnar.is_numeric_type(table.schema.field(c).type)
                and table.column(c).null_count == 0
                for c in cols
            ) and self._device_compatible(expr, table)
        if numeric:
            # The mesh branch bypasses the single-device resident cache
            # (sharded placement is its own layout) — its stats must not
            # claim a zero-transfer resident run.
            use_mesh = (mesh is not None and table.num_rows
                        >= self.session.conf.mesh_filter_min_rows)
            resident = not use_mesh and self._all_resident(identity, pairs)
            mask = self._eval_device(expr, table, identity,
                                     mesh=mesh if use_mesh else None)
            self.stats.setdefault("filters", []).append({
                "strategy": "device-mesh" if use_mesh else "device",
                "rows": table.num_rows, "resident": resident})
            return mask
        self.stats.setdefault("filters", []).append({
            "strategy": "host", "rows": table.num_rows})
        return self._eval_arrow(expr, table)

    def _device_compatible(self, expr: Expr, table: pa.Table) -> bool:
        if isinstance(expr, BinOp):
            sides = (expr.left, expr.right)
            if not all(isinstance(s, (Col, Lit)) for s in sides):
                # Compound operands: every leaf must be a column or a
                # plainly numeric literal under + - * / neg arithmetic (no
                # temporal normalization inside arithmetic; division is
                # host-only for x/0 -> null 3VL; CASE/string nodes are
                # host-only entirely).  Column leaves must be strictly
                # int/float: temporal/bool pass the outer is_numeric_type
                # gate but arithmetic over their int64 normalization is
                # unit-dependent (and the host mirror raises), so routing
                # must not depend on row count.
                return all(_arith_device_ok(s, table) for s in sides)
            cols_in_cmp = [s for s in sides if isinstance(s, Col)]
            if not cols_in_cmp:
                # Lit-vs-Lit: constant predicates are host-only (the arrow
                # path owns their 3VL), and there is no column type to
                # normalize a temporal literal against.
                return False
            col_types = [table.schema.field(c.name).type for c in cols_in_cmp]
            if len(col_types) == 2 and (pa.types.is_boolean(col_types[0])
                                        != pa.types.is_boolean(col_types[1])):
                # bool-vs-numeric column pair: arrow has no mixed kernel,
                # so the host path raises — the device 0/1 view must not
                # silently answer instead.
                return False
            if any(pa.types.is_temporal(t) for t in col_types):
                # Temporal columns compare on device only against a
                # temporal-typed literal (normalized below) or a column of
                # the SAME temporal type (same epoch unit).  A raw numeric
                # literal or a mixed-type column pair must route to host —
                # comparing epoch int64s against plain numbers would give a
                # silently different answer above the row threshold than
                # the host path's loud error below it.
                if len(col_types) == 2 and (
                        not all(pa.types.is_temporal(t) for t in col_types)
                        or col_types[0] != col_types[1]):
                    return False
                if any(isinstance(s, Lit)
                       and isinstance(s.value, (int, float, bool,
                                                np.integer, np.floating,
                                                np.bool_))
                       for s in sides):
                    return False
            for side in sides:
                if not isinstance(side, Lit):
                    continue
                v = side.value
                bool_lit = isinstance(v, (bool, np.bool_))
                if bool_lit != pa.types.is_boolean(col_types[0]) and (
                        bool_lit or isinstance(v, (int, float, np.integer,
                                                   np.floating))):
                    # bool-vs-numeric in either direction: arrow has no
                    # mixed (int64, bool) comparison kernel, so the host
                    # path raises — the device path must not silently
                    # answer instead.
                    return False
                if not isinstance(v, (int, float, bool)):
                    # Temporal/string literals: host path normalizes them.
                    if columnar.literal_to_numeric(v, col_types[0]) is None:
                        return False
            return True
        if isinstance(expr, (And, Or)):
            return (self._device_compatible(expr.left, table)
                    and self._device_compatible(expr.right, table))
        if isinstance(expr, Not):
            return self._device_compatible(expr.child, table)
        if isinstance(expr, IsIn):
            # The child must be strictly-numeric (temporal/bool columns
            # would be compared as raw epoch int64s against the plain
            # numeric value set — the host path raises instead).
            return (_arith_device_ok(expr.child, table)
                    and all(isinstance(v, (int, float, bool))
                            for v in expr.values))
        return False

    def _eval_device(self, expr: Expr, table: pa.Table,
                     identity=None, mesh=None) -> np.ndarray:
        from hyperspace_tpu.ops.filter import compile_predicate

        order = sorted(expr.referenced_columns())
        norm = self._normalize_literals(expr, table)
        fn, literals = compile_predicate(norm, order)
        # Scoped x64 so int64 columns keep full width on device (global x64
        # would leak dtype defaults into the embedding application's JAX).
        if mesh is not None:
            # Large scan + a mesh: shard the columns row-wise over every
            # mesh device (the batch is host-resident; other hosts'
            # devices are not addressable from here); the elementwise
            # program partitions with zero collectives (parallel/filter.py,
            # which scopes x64 itself).  The single-device resident cache
            # is bypassed — sharded placement is its own layout.
            from hyperspace_tpu.parallel.filter import eval_predicate_on_mesh

            device_cols = [columnar.to_device_numeric(table.column(c))
                           for c in order]
            return eval_predicate_on_mesh(fn, device_cols, literals,
                                          mesh=mesh)
        device_cols = [self._device_column(table, c, identity, "num")
                       for c in order]
        if not any(isinstance(c, jax.Array) for c in device_cols):
            # Host columns (no HBM-resident copy) are padded to a power
            # of two: a table whose row count moves (a new snapshot's
            # appended rows) keeps its compiled predicate.
            from hyperspace_tpu.ops.sort import _pad_rows

            rows = round_up_pow2(max(1, table.num_rows))
            device_cols = [_pad_rows(c, rows) for c in device_cols]
        t0 = timeline.kernel_begin()
        with _enable_x64():
            mask = fn(device_cols, literals)
        timeline.kernel_end("filter", t0, mask)
        return sync_guard.pull(mask, "filter.mask")[:table.num_rows]

    def _normalize_literals(self, expr: Expr, table: pa.Table) -> Expr:
        """Rewrite temporal/bool literals to their int64 device domain."""
        if isinstance(expr, BinOp):
            left, right = expr.left, expr.right
            if isinstance(left, Col) and isinstance(right, Lit):
                t = table.schema.field(left.name).type
                v = columnar.literal_to_numeric(right.value, t)
                return BinOp(expr.op, left, Lit(v))
            if isinstance(right, Col) and isinstance(left, Lit):
                t = table.schema.field(right.name).type
                v = columnar.literal_to_numeric(left.value, t)
                return BinOp(expr.op, Lit(v), right)
            return expr
        if isinstance(expr, And):
            return And(self._normalize_literals(expr.left, table),
                       self._normalize_literals(expr.right, table))
        if isinstance(expr, Or):
            return Or(self._normalize_literals(expr.left, table),
                      self._normalize_literals(expr.right, table))
        if isinstance(expr, Not):
            return Not(self._normalize_literals(expr.child, table))
        return expr

    def _eval_arrow(self, expr: Expr, table: pa.Table) -> np.ndarray:
        """Host fallback: arrow compute (reference semantics for strings)."""
        result = _arrow_eval(expr, table)
        if isinstance(result, pa.Scalar):
            # Constant predicate: broadcast (null ⇒ no rows, SQL semantics).
            value = result.as_py()
            return np.full(table.num_rows, bool(value) if value is not None else False)
        mask = np.asarray(result.to_numpy(zero_copy_only=False))
        if mask.dtype != np.bool_:
            # Kleene nulls surface as None in an object array: null ⇒ False.
            mask = np.array([bool(v) if v is not None else False for v in mask])
        return mask

    # -- join ---------------------------------------------------------------
    def _join(self, plan: Join, _record: bool = True) -> pa.Table:
        with span("exec.join", how=plan.how) as sp:
            joins_mark = len(self.stats["joins"])
            bucketed = self._try_bucketed_join(plan)
            if bucketed is not None:
                if len(self.stats["joins"]) > joins_mark:
                    sp.set(strategy=self.stats["joins"][joins_mark]
                           .get("strategy"))
                sp.set(rows=bucketed.num_rows)
                return bucketed
            if _record:
                self.stats["joins"].append({"strategy": "plain",
                                            "how": plan.how})
            sp.set(strategy="plain")
            left = self.execute(plan.left)
            right = self.execute(plan.right)
            out = self._host_join_tables(left, right, plan.condition,
                                         plan.how, residual=plan.residual)
            sp.set(rows=out.num_rows)
            return out

    def _host_join_tables(self, left: pa.Table, right: pa.Table,
                          condition: Expr, how: str,
                          residual: Optional[Expr] = None) -> pa.Table:
        """Join two materialized tables.  Match pairs come from the inner
        equi-join kernels over the VALID-key rows (SQL: null keys never
        match); the join type then shapes the output from those pairs —
        null-extension via arrow's null-index take, existence joins by
        membership over the matched left rows."""
        from hyperspace_tpu.plan.expr import as_equi_join_pairs

        pairs = as_equi_join_pairs(condition)
        if pairs is None:
            raise ValueError(f"Non-equi join condition: {condition!r}")
        # Resolve which side each column belongs to.
        l_keys, r_keys = [], []
        for a, b in pairs:
            if a in left.column_names and b in right.column_names:
                l_keys.append(a)
                r_keys.append(b)
            elif b in left.column_names and a in right.column_names:
                l_keys.append(b)
                r_keys.append(a)
            else:
                raise ValueError(f"Join columns {a!r}/{b!r} not found")
        # Null keys never match, but outer/anti joins still EMIT those rows
        # — so track original positions instead of dropping rows outright.
        l_map = _valid_key_positions(left, l_keys)
        r_map = _valid_key_positions(right, r_keys)
        lv = left if len(l_map) == left.num_rows else left.take(pa.array(l_map))
        rv = right if len(r_map) == right.num_rows else right.take(pa.array(r_map))
        # Null-key drops are a pure function of (files, key columns):
        # identity derives through so the resident join still addresses
        # the cache when an identified side has nullable keys.
        if lv is not left:
            self._register_derived_identity(
                lv, self._scan_identity(left), f"dropnull:{l_keys}")
        if rv is not right:
            self._register_derived_identity(
                rv, self._scan_identity(right), f"dropnull:{r_keys}")
        li, ri = self._inner_match_pairs(lv, rv, l_keys, r_keys)
        li = l_map[li] if len(l_map) != left.num_rows else li
        ri = r_map[ri] if len(r_map) != right.num_rows else ri
        if residual is not None and len(li):
            # Inequality correlations etc.: the residual predicate
            # filters the MATCHED pairs before the join type shapes the
            # output (NULL => no match, like any join predicate) — so an
            # anti join keeps exactly the left rows with no SURVIVING
            # match, the literal NOT EXISTS semantics.
            combined = _concat_horizontal(left.take(pa.array(li)),
                                          right.take(pa.array(ri)))
            mask = self._eval_arrow(residual, combined)
            li, ri = li[mask], ri[mask]

        if how == "inner":
            return _concat_horizontal(left.take(pa.array(li)),
                                      right.take(pa.array(ri)))
        if how == "semi":
            return left.take(pa.array(np.unique(li)))
        if how == "anti":
            mask = np.ones(left.num_rows, dtype=bool)
            mask[li] = False
            return left.filter(pa.array(mask))
        # Outer joins: matched pairs first, then each side's unmatched rows
        # null-extended (take with a null index yields a null row).
        l_parts = [li]
        r_parts = [ri]
        l_masks = [np.zeros(len(li), dtype=bool)]
        r_masks = [np.zeros(len(ri), dtype=bool)]
        if how in ("left", "full"):
            unmatched = np.setdiff1d(np.arange(left.num_rows), li)
            l_parts.append(unmatched)
            r_parts.append(np.zeros(len(unmatched), dtype=ri.dtype))
            l_masks.append(np.zeros(len(unmatched), dtype=bool))
            r_masks.append(np.ones(len(unmatched), dtype=bool))
        if how in ("right", "full"):
            unmatched = np.setdiff1d(np.arange(right.num_rows), ri)
            l_parts.append(np.zeros(len(unmatched), dtype=li.dtype))
            r_parts.append(unmatched)
            l_masks.append(np.ones(len(unmatched), dtype=bool))
            r_masks.append(np.zeros(len(unmatched), dtype=bool))
        l_idx = pa.array(np.concatenate(l_parts), mask=np.concatenate(l_masks))
        r_idx = pa.array(np.concatenate(r_parts), mask=np.concatenate(r_masks))
        return _concat_horizontal(left.take(l_idx), right.take(r_idx))

    def _inner_match_pairs(self, left: pa.Table, right: pa.Table,
                           l_keys: List[str], r_keys: List[str]):
        """(left_indices, right_indices) of the INNER matches between two
        null-free-key tables, as int64 numpy arrays."""
        single_numeric = (
            len(l_keys) == 1
            and columnar.is_numeric_type(left.schema.field(l_keys[0]).type)
            and columnar.is_numeric_type(right.schema.field(r_keys[0]).type))
        if single_numeric:
            from hyperspace_tpu.ops.join import (
                sorted_equi_join,
                sorted_equi_join_mesh,
                sorted_equi_join_np,
            )

            # Routing: the cold-transfer break-even normally; when BOTH
            # sides' key columns are HBM-resident for their (possibly
            # filter-derived) scan identities, only round-trip latency and
            # the match-index pull remain, so the much smaller resident
            # threshold applies (the contract the covering-index design
            # states: join kernels over HBM-resident batches,
            # JoinIndexRule.scala:36-50).
            max_rows = max(left.num_rows, right.num_rows)
            cold = self.session.conf.device_min_rows("join")
            id_l = self._scan_identity(left)
            id_r = self._scan_identity(right)
            pl = [(l_keys[0], "num")]
            pr = [(r_keys[0], "num")]
            use_device = max_rows >= cold
            if not use_device:
                eff = max(self._cache_aware_min_rows(id_l, pl, "join"),
                          self._cache_aware_min_rows(id_r, pr, "join"))
                use_device = eff < cold and max_rows >= eff
            resident = use_device and self._all_resident(id_l, pl) \
                and self._all_resident(id_r, pr)
            # An active mesh shards the key space over the devices at
            # its own threshold (host inputs only: resident arrays keep
            # the single-device kernel, whose HBM placement is its own
            # layout).  Same match set either way — the mesh changes
            # where the match ranges are found, not what they hold.
            mesh = self._active_mesh()
            use_mesh = (mesh is not None and not resident
                        and max_rows
                        >= self.session.conf.mesh_join_min_rows)
            if use_mesh:
                lk = columnar.to_device_numeric(left.column(l_keys[0]))
                rk = columnar.to_device_numeric(right.column(r_keys[0]))
                li, ri = sorted_equi_join_mesh(lk, rk, mesh)
            elif use_device:
                lk = self._device_column(left, l_keys[0], id_l, "num")
                rk = self._device_column(right, r_keys[0], id_r, "num")
                li, ri = sorted_equi_join(lk, rk)
            else:
                lk = columnar.to_device_numeric(left.column(l_keys[0]))
                rk = columnar.to_device_numeric(right.column(r_keys[0]))
                li, ri = sorted_equi_join_np(lk, rk)
            self.stats.setdefault("join_kernels", []).append({
                "strategy": "mesh" if use_mesh
                else ("device" if use_device else "host"),
                "rows": int(max_rows), "resident": resident})
            return np.asarray(li, dtype=np.int64), np.asarray(ri, dtype=np.int64)
        # Composite/string keys: digest join on device (or its host
        # mirror below the size threshold) with exact verification —
        # pandas only for key pairs with no exact common domain.
        from hyperspace_tpu.ops.join import (
            UnsupportedJoinKeys,
            hashed_equi_join,
        )

        try:
            use_device = (max(left.num_rows, right.num_rows)
                          >= self.session.conf.device_min_rows("join"))
            li, ri = hashed_equi_join(left, right, l_keys, r_keys,
                                      device=use_device)
            return np.asarray(li, dtype=np.int64), np.asarray(ri, dtype=np.int64)
        except UnsupportedJoinKeys:
            import pandas as pd  # noqa: F401

            ldf = left.to_pandas()
            rdf = right.to_pandas()
            ldf["__li"] = np.arange(len(ldf))
            rdf["__ri"] = np.arange(len(rdf))
            merged = ldf.merge(rdf, left_on=l_keys, right_on=r_keys,
                               how="inner", suffixes=("", "__r"))
            return (merged["__li"].to_numpy(dtype=np.int64),
                    merged["__ri"].to_numpy(dtype=np.int64))

    # -- bucket-aligned join (the shuffle-free SMJ payoff on one chip) ------
    # Structural applicability lives in ``bucketed_join_precheck`` (module
    # level) so the explain physical analyzer predicts the same strategy
    # the executor takes, from one set of checks.
    def _try_bucketed_join(self, plan: Join) -> Optional[pa.Table]:
        """When both sides are (Project|Filter)* chains over index scans
        with MATCHING bucket specs on the join keys (what JoinIndexRule
        constructs), execute and join bucket by bucket: equal keys can only
        meet inside one bucket, so each per-bucket merge works on 1/B of the
        data — the single-chip analog of Spark's exchange-free SMJ over
        matching bucketSpecs (JoinIndexRule.scala:36-50).

        A side may also be a hybrid-scan ``BucketUnion(index, appended)``:
        the appended rows are routed through the build hash kernel into the
        index's bucket space and joined per bucket alongside the index
        files — the executed form of the reference's on-the-fly shuffle
        (RuleUtils.scala:511-570), keeping the index side exchange-free
        instead of degrading to a full-table merge."""
        if plan.residual is not None:
            # Residual joins (subquery inequality correlations) take the
            # plain path: they're semi/anti existence shapes, not the
            # bucketed-index fan-out this optimizes.
            return None
        precheck = bucketed_join_precheck(self.session, plan)
        if precheck is None:
            return None
        left_side, right_side, l_files, r_files = precheck
        scans_mark = len(self.stats["scans"])
        l_parts = self._side_bucket_parts(left_side, l_files)
        r_parts = None if l_parts is None \
            else self._side_bucket_parts(right_side, r_files)
        shared = [] if l_parts is None or r_parts is None \
            else sorted(set(l_parts) & set(r_parts))
        if not shared:
            # Decomposition failed (or zero overlapping buckets — the plain
            # path produces the correct result, including outer
            # null-extension, with the full joined schema): roll back
            # anything recorded while probing.
            del self.stats["scans"][scans_mark:]
            return None
        # One-sided buckets: for inner (and semi on the right / anti on the
        # right) they contribute nothing, but an outer/anti join must still
        # emit the unmatched rows of its preserved side.  Join those buckets
        # against a ZERO-ROW donor of the other side (schema from a shared
        # bucket) — the per-bucket join then null-extends/passes them
        # exactly like the plain path would.
        extra_left = sorted(set(l_parts) - set(r_parts)) \
            if plan.how in ("left", "full", "anti") else []
        extra_right = sorted(set(r_parts) - set(l_parts)) \
            if plan.how in ("right", "full") else []
        hybrid = bool(left_side.appended or right_side.appended)
        mesh_result = self._try_mesh_bucketed_join(
            plan, left_side, right_side, l_parts, r_parts, shared,
            extra_left, extra_right, hybrid, l_files, r_files)
        if mesh_result is not None:
            return mesh_result
        self.stats["joins"].append({
            "strategy": "bucketed",
            "how": plan.how,
            "buckets": len(shared) + len(extra_left) + len(extra_right),
            "hybrid": hybrid,
        })
        # Zero-row schema donors for one-sided buckets: executed ONCE —
        # the donor bucket's table is reused for its own join too, so its
        # files are not decoded (nor its scans recorded) twice.
        pre: Dict[int, Tuple[pa.Table, pa.Table]] = {}
        l_donor = r_donor = None
        if extra_left or extra_right:
            donor = shared[0]
            lt0 = self.execute(l_parts[donor]())
            rt0 = self.execute(r_parts[donor]())
            pre[donor] = (lt0, rt0)
            l_donor, r_donor = lt0.slice(0, 0), rt0.slice(0, 0)

        def join_bucket(bucket: int) -> pa.Table:
            if bucket in extra_left:
                sub = Join(l_parts[bucket](), InMemory(r_donor),
                           plan.condition, plan.how)
            elif bucket in extra_right:
                sub = Join(InMemory(l_donor), r_parts[bucket](),
                           plan.condition, plan.how)
            elif bucket in pre:
                lt, rt = pre[bucket]
                sub = Join(InMemory(lt), InMemory(rt),
                           plan.condition, plan.how)
            else:
                sub = Join(l_parts[bucket](), r_parts[bucket](),
                           plan.condition, plan.how)
            # Per-bucket plans carry no bucket_spec, so this recursion takes
            # the plain per-bucket join path — no re-entry.
            return self._join(sub, _record=False)

        from hyperspace_tpu.utils.parallel_map import parallel_map_ordered

        # Buckets are independent; parquet decode + numpy merge release the
        # GIL.  Each in-flight bucket holds both sides + output (~2/B of
        # the joined data), so 8 concurrent buckets stay memory-modest
        # while keeping every core decoding (nested per-file reads run
        # inline in the shared pool, so this cap IS the read concurrency).
        parts = parallel_map_ordered(join_bucket,
                                     sorted(shared + extra_left + extra_right),
                                     max_workers=8)
        return pa.concat_tables(parts, promote_options="default")

    # -- mesh dispatch of the bucket-aligned join ---------------------------
    def _try_mesh_bucketed_join(self, plan: Join, left_side, right_side,
                                l_parts, r_parts, shared,
                                extra_left, extra_right,
                                hybrid: bool, l_files, r_files):
        """Run the per-bucket joins over the device mesh instead of the
        host thread pool: buckets are range-partitioned over the shard
        axis and ``copartitioned_join_ragged`` joins every device's
        buckets with ZERO collectives (equal keys share a bucket, and a
        bucket lives on exactly one device) — the executed form of the
        reference's distributed exchange-free SMJ
        (BucketUnionExec.scala:52-81 + Spark SMJ over executors).

        Applies to INNER joins with a single numeric key when the engine
        mesh is active (``hyperspace.parallel.mesh.enabled``; off or
        1 device keeps the bit-equal host pool) and the data is large
        enough to amortize the transfer (conf mesh_join_min_rows —
        estimated from parquet FOOTERS before anything is materialized,
        so a below-threshold join never loses the host pool's
        8-concurrent-bucket memory bound); everything else keeps the
        host pool.  The mesh path itself holds all buckets resident by
        construction — that is what the threshold gates."""
        if plan.how != "inner" or extra_left or extra_right:
            return None
        mesh = self._active_mesh()
        if mesh is None:
            return None
        devices = list(mesh.devices.flat)
        from hyperspace_tpu.plan.expr import as_equi_join_pairs

        pairs = as_equi_join_pairs(plan.condition)
        if pairs is None or len(pairs) != 1:
            return None
        # Key columns are the (single) bucket columns — precheck guaranteed
        # the pairs map them — so eligibility is decidable from STORED
        # schemas before executing anything.
        lk_name = left_side.scan.relation.bucket_spec[1][0]
        rk_name = right_side.scan.relation.bucket_spec[1][0]
        try:
            from hyperspace_tpu.io.parquet import schema_to_arrow

            l_map = {k.lower(): v for k, v in
                     self.session.schema_map_of(left_side.scan).items()}
            r_map = {k.lower(): v for k, v in
                     self.session.schema_map_of(right_side.scan).items()}
            l_type = l_map[lk_name.lower()]
            r_type = r_map[rk_name.lower()]
            if not (columnar.is_numeric_type(
                        schema_to_arrow({"c": l_type}).field(0).type)
                    and columnar.is_numeric_type(
                        schema_to_arrow({"c": r_type}).field(0).type)):
                return None
        except Exception:
            return None
        # Row estimate from footers only (no decode): filters above the
        # scans can shrink actual rows, so this is an upper bound — the
        # threshold is a routing heuristic, not a correctness gate.
        est = _footer_row_estimate(l_files, shared) \
            + _footer_row_estimate(r_files, shared)
        if est < self.session.conf.mesh_join_min_rows:
            return None

        from hyperspace_tpu.utils.parallel_map import parallel_map_ordered

        l_tabs = parallel_map_ordered(
            lambda b: self.execute(l_parts[b]()), shared, max_workers=8)
        r_tabs = parallel_map_ordered(
            lambda b: self.execute(r_parts[b]()), shared, max_workers=8)
        # Resolve the executed tables' key column spellings (projections
        # preserve source case; the spec columns are case-insensitive).
        lk_name = _find_column(l_tabs[0], lk_name)
        rk_name = _find_column(r_tabs[0], rk_name)
        if lk_name is None or rk_name is None:
            # Shouldn't happen (the join condition references them), but a
            # wrong guess must degrade to an error-free fallback: run the
            # buckets through the host join path on the materialized pairs.
            return pa.concat_tables(
                [self._join(Join(InMemory(lt), InMemory(rt),
                                 plan.condition, plan.how), _record=True)
                 for lt, rt in zip(l_tabs, r_tabs)],
                promote_options="default")
        # Null keys never match an inner join: drop per bucket up front.

        def drop_nulls(tabs, key):
            out = []
            for t in tabs:
                if t.column(key).null_count > 0:
                    t = t.filter(pc.is_valid(t.column(key)))
                out.append(t)
            return out

        l_tabs = drop_nulls(l_tabs, lk_name)
        r_tabs = drop_nulls(r_tabs, rk_name)
        # MOD bucket ownership over the shard axis (device d owns bucket
        # b iff b % D == d — the same ownership the sharded build route
        # writes with, so index shards and query shards stay aligned);
        # one concatenated table + key shard per device.
        from hyperspace_tpu.parallel.join import copartitioned_join_ragged
        from hyperspace_tpu.telemetry import metrics

        D = len(devices)
        groups = [[i for i, b in enumerate(shared) if b % D == d]
                  for d in range(D)]
        l_dev_tabs, r_dev_tabs, l_shards, r_shards = [], [], [], []
        for g in groups:
            lt = pa.concat_tables([l_tabs[i] for i in g]) if len(g) \
                else l_tabs[0].slice(0, 0)
            rt = pa.concat_tables([r_tabs[i] for i in g]) if len(g) \
                else r_tabs[0].slice(0, 0)
            l_dev_tabs.append(lt)
            r_dev_tabs.append(rt)
            l_shards.append(np.asarray(
                columnar.to_device_numeric(lt.column(lk_name))))
            r_shards.append(np.asarray(
                columnar.to_device_numeric(rt.column(rk_name))))
        dev_ids, l_local, r_local = copartitioned_join_ragged(
            l_shards, r_shards, mesh)
        metrics.set_gauge("exec.mesh.devices", D)
        self.stats["joins"].append({
            "strategy": "bucketed-mesh",
            "how": plan.how,
            "buckets": len(shared),
            "devices": D,
            "hybrid": hybrid,
        })
        parts = []
        for d in range(D):
            sel = dev_ids == d
            if not sel.any():
                continue
            parts.append(_concat_horizontal(
                l_dev_tabs[d].take(pa.array(l_local[sel])),
                r_dev_tabs[d].take(pa.array(r_local[sel]))))
        if not parts:
            empty_l = l_dev_tabs[0].slice(0, 0)
            empty_r = r_dev_tabs[0].slice(0, 0)
            return _concat_horizontal(empty_l, empty_r)
        return pa.concat_tables(parts, promote_options="default")

    def _side_bucket_parts(self, side: "_BucketedSide", by_bucket):
        """bucket id -> zero-arg builder of that bucket's sub-plan for one
        join side, or None when the side can't be decomposed.  Index files
        group by the bucket id in their name (``by_bucket``, precomputed by
        the caller); appended rows (hybrid scan) are routed with the build
        hash kernel."""
        appended_by_bucket: Dict[int, pa.Table] = {}
        if side.appended is not None:
            num_buckets, cols, _sort = side.scan.relation.bucket_spec
            routed = self._execute_appended(
                side.appended, lambda table: self._route_to_buckets(
                    table, cols, num_buckets, side.scan))
            if routed is None:
                return None
            appended_by_bucket = routed

        def make(bucket: int) -> LogicalPlan:
            parts: List[LogicalPlan] = []
            if bucket in by_bucket:
                parts.append(_rewrap(side.scan, side.inner, by_bucket[bucket]))
            if bucket in appended_by_bucket:
                parts.append(InMemory(appended_by_bucket[bucket]))
            # strict: index ∪ its own appended rows (see Union docstring).
            node = parts[0] if len(parts) == 1 else Union(parts, strict=True)
            for w in reversed(side.outer):
                node = w.with_children((node,))
            return node

        return {b: (lambda b=b: make(b))
                for b in set(by_bucket) | set(appended_by_bucket)}

    def _execute_appended(self, node: LogicalPlan, then=None):
        """Execute a hybrid scan's appended branch (the source files the
        index does not cover yet) under ``exec.hybrid_appended``: listing,
        decode and, through ``then`` on the bucketed join path, routing
        into the index's buckets.  Returns ``then(table)`` or the table.
        The answer states the source version the branch's file list was
        taken at (sources/snapshots.py)."""
        from hyperspace_tpu.sources import snapshots
        from hyperspace_tpu.telemetry import metrics

        scans = node.leaf_relations()
        for s in scans:
            if s.relation.source_version is not None:
                snapshots.record_version(
                    os.path.abspath(s.relation.root_paths[0]),
                    s.relation.source_version)
        files = sum(len(s.relation.file_paths or ()) for s in scans)
        with span("exec.hybrid_appended", files=files) as sp:
            table = self.execute(node)
            sp.set(rows=table.num_rows)
            out = table if then is None else then(table)
        metrics.inc("hybrid.appended_files", files)
        metrics.inc("hybrid.appended_rows", table.num_rows)
        return out

    def _route_to_buckets(self, table: pa.Table, cols, num_buckets: int,
                          index_scan: Scan) -> Optional[Dict[int, pa.Table]]:
        """Partition ``table`` by the index's bucket assignment.  Uses the
        host mirror of the build kernel (bit-identical, parity-tested):
        hybrid-scan thresholds cap appended bytes at a fraction of the
        index, so these batches are small and a device round trip would be
        pure latency.  Key columns are cast to the index's STORED type
        first — the kernel hashes raw bits, so an int64 row hashed as
        float64 would land in the wrong bucket."""
        from hyperspace_tpu.io.columnar import to_hash_words
        from hyperspace_tpu.io.parquet import schema_to_arrow
        from hyperspace_tpu.ops.hash import bucket_ids_np

        if table.num_rows == 0:
            return {}
        by_lower = {c.lower(): c for c in table.column_names}
        stored = {k.lower(): v
                  for k, v in self.session.schema_map_of(index_scan).items()}
        word_cols = []
        for c in cols:
            name = by_lower.get(c.lower())
            if name is None:
                return None
            col = table.column(name)
            stored_type = stored.get(c.lower())
            if stored_type is not None and str(col.type) != stored_type:
                target = schema_to_arrow({"c": stored_type}).field(0).type
                try:
                    col = pc.cast(col, target)
                except (pa.ArrowInvalid, pa.ArrowNotImplementedError,
                        pa.ArrowTypeError):
                    return None
            word_cols.append(np.asarray(to_hash_words(col)))
        bucket_ids = bucket_ids_np(word_cols, num_buckets)
        return {int(b): table.filter(pa.array(bucket_ids == b))
                for b in np.unique(bucket_ids)}


def _footer_row_estimate(files_by_bucket, buckets) -> int:
    """Sum of parquet footer row counts for the given buckets' files —
    O(footer) per file, no column decode.  Non-parquet/unreadable files
    contribute 0 (the estimate is a routing heuristic only)."""
    import pyarrow.parquet as pq

    total = 0
    for b in buckets:
        for path in files_by_bucket.get(b, ()):
            try:
                total += pq.read_metadata(path).num_rows
            except Exception:
                pass
    return total


def _find_column(table: pa.Table, name: str) -> Optional[str]:
    """Case-insensitive column lookup, exact spelling preferred."""
    if name in table.column_names:
        return name
    lower = name.lower()
    for c in table.column_names:
        if c.lower() == lower:
            return c
    return None


def _valid_key_positions(table: pa.Table, keys: List[str]) -> np.ndarray:
    """Row positions whose join keys are ALL non-null (the rows that can
    participate in matching)."""
    valid = np.ones(table.num_rows, dtype=bool)
    for k in keys:
        col = table.column(k)
        if col.null_count > 0:
            valid &= np.asarray(
                pc.is_valid(col).to_numpy(zero_copy_only=False))
    return np.nonzero(valid)[0] if not valid.all() \
        else np.arange(table.num_rows)


def _arith_device_ok(e: Expr, table: pa.Table) -> bool:
    """Device-evaluable value expression: strictly-numeric columns, numeric
    literals, and + - * arithmetic over them (division is host-only: x/0
    must null; temporal/bool columns are host-only: their arithmetic over
    raw int64 normalization would be unit-dependent)."""
    if isinstance(e, Col):
        try:
            t = table.schema.field(e.name).type
        except KeyError:
            return False
        return pa.types.is_integer(t) or pa.types.is_floating(t)
    if isinstance(e, Lit):
        # bool is excluded despite being an int subclass: the host mirror
        # has no (int64, bool) arithmetic kernel, so admitting it would
        # key the outcome on row count.
        return (isinstance(e.value, (int, float))
                and not isinstance(e.value, bool))
    if isinstance(e, Arith):
        return (e.op != "/" and _arith_device_ok(e.left, table)
                and _arith_device_ok(e.right, table))
    if isinstance(e, Neg):
        return _arith_device_ok(e.child, table)
    return False


class _BucketedSide:
    """One join side decomposed for bucket-aligned execution: the bucketed
    index ``scan``, ``inner`` wrappers between the hybrid BucketUnion and
    the scan (empty when there is no union), ``outer`` wrappers above, and
    the ``appended`` subtree (None when the side is a pure index chain)."""

    def __init__(self, scan: Scan, inner, outer, appended) -> None:
        self.scan = scan
        self.inner = inner
        self.outer = outer
        self.appended = appended


def _is_appended_branch(node: LogicalPlan) -> bool:
    """The appended branch of a hybrid-scan union, as rules/hybrid.py
    marks it."""
    return any(s.relation.hybrid_appended_of is not None
               for s in node.leaf_relations())


class _JoinSide:
    """One side of a fused join-aggregate as its checks see it: a decoded
    ``table``, or (``table`` None) a side served from HBM, described by
    its manifest, with the device ``arrays`` the join needs already
    taken and the ``scan_record`` of the scan it skipped."""

    __slots__ = ("table", "schema", "names", "num_rows", "null_counts",
                 "identity", "arrays", "scan_record")

    def __init__(self, table, schema, num_rows: int, null_counts,
                 identity, arrays=None, scan_record=None) -> None:
        self.table = table
        self.schema = schema
        self.names = frozenset(schema.names)
        self.num_rows = num_rows
        self.null_counts = null_counts
        self.identity = identity
        self.arrays = arrays
        self.scan_record = scan_record

    @classmethod
    def decoded(cls, table: pa.Table, identity) -> "_JoinSide":
        return cls(table, table.schema, table.num_rows, None, identity)

    def null_count(self, name: str) -> int:
        if self.table is not None:
            return self.table.column(name).null_count
        return self.null_counts[name]


def _agg_refs(agg_in) -> List[str]:
    """Columns an aggregate's input references."""
    if isinstance(agg_in, Col):
        return [agg_in.name]
    if isinstance(agg_in, str):
        return [agg_in]
    return list(agg_in.referenced_columns())


def _prune_to_buckets(paths: List[str], buckets) -> List[str]:
    """The bucket-pruning read: drop index files whose bucket id (from
    the file name) is not in ``buckets``; None keeps every file."""
    if buckets is None:
        return paths
    wanted = set(buckets)
    return [p for p in paths
            if (b := bucket_id_of_file(p)) is None or b in wanted]


def _chain_columns(wrappers: List[LogicalPlan],
                   scanned: List[str]) -> Optional[List[str]]:
    """Output columns of a ``(Project|Filter)*`` chain over a scan of
    ``scanned`` columns; None when a node names a column its child
    lacks."""
    avail = list(scanned)
    for w in reversed(wrappers):  # innermost first
        need = w.columns if isinstance(w, Project) \
            else w.condition.referenced_columns()
        if not set(need) <= set(avail):
            return None
        if isinstance(w, Project):
            avail = list(w.columns)
    return avail


def _is_bucketed_index_scan(node: LogicalPlan) -> bool:
    return (isinstance(node, Scan) and bool(node.relation.bucket_spec)
            and node.relation.file_paths is not None
            and bool(node.relation.index_scan_of))


def _unwrap_chain(node: LogicalPlan):
    wrappers: List[LogicalPlan] = []
    while isinstance(node, (Project, Filter)):
        wrappers.append(node)
        node = node.children[0]
    return wrappers, node


def _bucketed_side(node: LogicalPlan) -> Optional[_BucketedSide]:
    """Match ``(Project|Filter)*`` over either a bucketed index scan or a
    hybrid-scan ``BucketUnion(index chain, appended subtree)``."""
    outer, node = _unwrap_chain(node)
    if _is_bucketed_index_scan(node):
        return _BucketedSide(node, [], outer, None)
    if isinstance(node, BucketUnion) and len(node.children) == 2:
        # The rule constructs [index_side, appended_side]; identify the
        # index chain structurally rather than by position.
        for index_child, appended_child in (node.children,
                                            node.children[::-1]):
            inner, leaf = _unwrap_chain(index_child)
            if _is_bucketed_index_scan(leaf):
                return _BucketedSide(leaf, inner, outer, appended_child)
    return None


def bucketed_join_precheck(session, plan: Join):
    """Structural applicability of the bucket-aligned join — side-effect
    free, shared by the executor and the explain physical analyzer so the
    predicted strategy can never diverge from the executed one.  Returns
    (left_side, right_side, left_files_by_bucket, right_files_by_bucket)
    or None when the plain join path applies.

    Multi-column keys qualify when the join pairs map the two sides'
    bucket columns POSITION BY POSITION (the reference's compatible-order
    requirement, JoinIndexRule.scala:483-530) — same hash inputs in the
    same order means equal key tuples share a bucket id."""
    from hyperspace_tpu.plan.expr import as_equi_join_pairs

    pairs = as_equi_join_pairs(plan.condition)
    if not pairs:
        return None
    aligned = [_bucketed_side(side) for side in (plan.left, plan.right)]
    if any(a is None for a in aligned):
        return None
    left_side, right_side = aligned
    l_scan, r_scan = left_side.scan, right_side.scan
    l_spec, r_spec = l_scan.relation.bucket_spec, r_scan.relation.bucket_spec
    if l_spec[0] != r_spec[0]:
        return None
    l_cols = tuple(c.lower() for c in l_spec[1])
    r_cols = tuple(c.lower() for c in r_spec[1])
    if len(pairs) != len(l_cols) or len(l_cols) != len(r_cols):
        return None
    # Orient each pair to (left-side column, right-side column); a pair
    # whose columns don't belong to the two bucket specs disqualifies.
    l_to_r = {}
    for a, b in pairs:
        la, rb = a.lower(), b.lower()
        fwd = la in l_cols and rb in r_cols
        rev = rb in l_cols and la in r_cols
        if fwd and rev and la != rb:
            # Ambiguous orientation (both names exist on both specs): the
            # per-bucket sub-join resolves sides by TABLE columns and could
            # pick the other pairing — partitioning on one orientation and
            # joining on the other silently drops matches.  Plain path.
            return None
        if fwd:
            l_to_r[la] = rb
        elif rev:
            l_to_r[rb] = la
        else:
            return None
    if [l_to_r.get(c) for c in l_cols] != list(r_cols):
        return None
    # Bucket ids only align when both sides hashed the SAME bit patterns:
    # an int64 key on one side and float64 on the other put equal VALUES in
    # different buckets (to_hash_words hashes raw bits), while the plain
    # join path matches them by value — so a type mismatch must fall back,
    # or results silently change.
    for lc, rc in zip(l_spec[1], r_spec[1]):
        l_type = session.schema_map_of(l_scan).get(lc)
        r_type = session.schema_map_of(r_scan).get(rc)
        if l_type is None or r_type is None or l_type != r_type:
            return None
    # Cheap structural checks for BOTH sides before the executor runs any
    # appended subtree (a late failure would re-execute it on the plain
    # path).
    l_files = _files_by_bucket(left_side.scan)
    r_files = _files_by_bucket(right_side.scan)
    if l_files is None or r_files is None:
        return None
    return left_side, right_side, l_files, r_files


def _files_by_bucket(scan: Scan):
    """Bucket id -> files, honoring the scan's own bucket pruning (a
    filter under the join may have restricted the buckets already)."""
    allowed = None if scan.relation.prune_to_buckets is None \
        else set(scan.relation.prune_to_buckets)
    out: Dict[int, List[str]] = {}
    for p in scan.relation.file_paths:
        b = bucket_id_of_file(p)
        if b is None:
            return None
        if allowed is not None and b not in allowed:
            continue
        out.setdefault(b, []).append(p)
    return out


def _rewrap(scan: Scan, wrappers, files) -> LogicalPlan:
    import dataclasses as dc

    rel = dc.replace(scan.relation, file_paths=tuple(files),
                     bucket_spec=None, prune_to_buckets=None)
    node: LogicalPlan = Scan(rel)
    for w in reversed(wrappers):
        node = w.with_children((node,))
    return node


def _sorted_table(table: pa.Table, keys) -> pa.Table:
    """ORDER BY with Spark's null order: nulls sort as SMALLEST — first
    ascending, last descending (the reference's executor for ORDER BY is
    Spark SQL).  Arrow's null_placement is positional (one setting for all
    keys regardless of direction), so each null-bearing key gets a validity
    flag key in front: false < true puts nulls first under the key's own
    direction when ascending and last when descending, and within each flag
    group the real key orders rows."""
    if table.num_rows == 0:
        return table
    return table.take(_sort_indices(table, keys))


def _sort_indices(table: pa.Table, keys) -> pa.Array:
    """Sort permutation with Spark's null order (nulls first ascending,
    last descending) — the validity-flag technique of _sorted_table."""
    work = table
    sort_keys = []
    for c, asc in keys:
        direction = "ascending" if asc else "descending"
        if table.column(c).null_count > 0:
            flag = f"__valid__{c}"
            n = 1
            while flag in work.column_names:
                flag = f"__valid__{c}__{n}"
                n += 1
            work = work.append_column(flag, pc.is_valid(table.column(c)))
            sort_keys.append((flag, direction))
        sort_keys.append((c, direction))
    return pc.sort_indices(work, sort_keys=sort_keys)


def _window_empty_type(table: pa.Table, plan: Window):
    """Output type for a zero-row input — must match the rowful path so
    the schema doesn't depend on whether the input had rows."""
    out_type = {"row_number": pa.int32(), "rank": pa.int32(),
                "dense_rank": pa.int32(), "ntile": pa.int32(),
                "count": pa.int64(),
                "mean": pa.float64()}.get(plan.func)
    if out_type is None and plan.func in ("lag", "lead", "first_value",
                                          "last_value"):
        out_type = table.schema.field(plan.value).type
    if out_type is None and plan.func == "sum":
        src = table.schema.field(plan.value).type
        out_type = pa.int64() \
            if pa.types.is_integer(src) or pa.types.is_boolean(src) \
            else pa.float64()
    if out_type is None:  # min/max follow the input column
        out_type = table.schema.field(plan.value).type \
            if plan.value else pa.int64()
    return out_type


def _np_window_values(v_sorted: pa.Array):
    """(values, valid) numpy views of a sorted Arrow column for the
    frame kernels: temporals/bools lower to their integer repr so int
    arithmetic stays exact; nulls are filled with 0 and tracked in
    ``valid``.  Non-numeric types return (None, valid) — the caller
    decides whether that's an error or an Arrow-side path."""
    t = v_sorted.type
    valid = np.asarray(pc.is_valid(v_sorted)
                       .to_numpy(zero_copy_only=False))
    num = None
    if pa.types.is_boolean(t):
        num = v_sorted.cast(pa.int8())
    elif pa.types.is_date32(t) or pa.types.is_time32(t):
        num = v_sorted.cast(pa.int32())
    elif (pa.types.is_date64(t) or pa.types.is_time64(t)
            or pa.types.is_timestamp(t) or pa.types.is_duration(t)):
        num = v_sorted.cast(pa.int64())
    elif pa.types.is_integer(t) or pa.types.is_floating(t):
        num = v_sorted
    # Decimals deliberately return None: a float64 view would sum with
    # rounded increments and could argmin the WRONG row when two
    # decimals collide at float precision — the caller picks an exact
    # Arrow-side path or fails loudly.
    if num is None:
        return None, valid
    filled = pc.fill_null(num, pa.scalar(0, type=num.type)
                          if not pa.types.is_floating(num.type)
                          else pa.scalar(0.0, type=num.type))
    vals = filled.to_numpy(zero_copy_only=False)
    return vals, valid


def _whole_partition_agg_arrow(v_sorted: pa.Array, part: np.ndarray,
                               func: str) -> pa.Array:
    """Whole-partition aggregates for types the numpy kernels don't
    take (strings, binary, decimals): Arrow hash aggregation broadcast
    back by the dense partition code — exact in the value's own type."""
    t = pa.table({"__c": pa.array(part), "__v": v_sorted})
    agg = t.group_by("__c").aggregate([("__v", func)])
    agg = agg.sort_by("__c")
    by_code = agg.column(f"__v_{func}")
    if isinstance(by_code, pa.ChunkedArray):
        by_code = by_code.combine_chunks()
    return by_code.take(pa.array(part))


def _window(table: pa.Table, plan: Window) -> pa.Table:
    """One analytic column over ``table``: sort once by (partition,
    order keys), then evaluate with the vectorized segment kernels in
    :mod:`hyperspace_tpu.ops.window` — no per-partition Python/pandas
    loop.  Semantics in the Window node's docstring."""
    from hyperspace_tpu.ops import window as W

    n = table.num_rows
    if n == 0:
        return table.append_column(
            plan.name, pa.array([], type=_window_empty_type(table, plan)))

    part_orig = W.partition_codes(table, plan.partition_by)
    pname = "__part"
    suffix = 1
    while pname in table.column_names:  # user column collision guard
        pname = f"__part__{suffix}"
        suffix += 1
    work = table.append_column(pname, pa.array(part_orig))
    perm = _sort_indices(work, [(pname, True)] + list(plan.order_by))
    perm_np = np.asarray(perm)
    part = part_orig[perm_np]
    new_part = np.empty(n, dtype=bool)
    new_part[0] = True
    new_part[1:] = part[1:] != part[:-1]

    # Tie groups: partition change OR any order-key change (null-safe,
    # both-NaN equal — Spark normalizes NaN ordering ties).
    new_tie = new_part.copy()
    for c, _asc in plan.order_by:
        col_sorted = table.column(c).take(perm)
        valid = np.asarray(pc.is_valid(col_sorted)
                           .to_numpy(zero_copy_only=False))
        vals = col_sorted.to_numpy(zero_copy_only=False)
        with np.errstate(invalid="ignore"):
            eq = vals[1:] == vals[:-1]
        if vals.dtype.kind == "f":
            eq = eq | (np.isnan(vals[1:].astype(float))
                       & np.isnan(vals[:-1].astype(float)))
        same = (valid[1:] == valid[:-1]) & (~valid[1:] | eq)
        new_tie[1:] |= ~same.astype(bool)

    part_start, part_end = W.segment_bounds(new_part)
    func = plan.func
    if func in ("lag", "lead"):
        # Exact index shift within partitions on the sorted layout;
        # Arrow take preserves the value type bit-for-bit and
        # out-of-partition positions null via the validity mask.
        src_type = table.schema.field(plan.value).type
        v_sorted = table.column(plan.value).take(perm)
        if isinstance(v_sorted, pa.ChunkedArray):
            v_sorted = v_sorted.combine_chunks()
        shift = plan.offset if func == "lag" else -plan.offset
        idx = np.arange(n) - shift
        inb = (idx >= 0) & (idx < n)
        rows = np.nonzero(inb)[0]
        valid = np.zeros(n, dtype=bool)
        valid[rows] = part[idx[rows]] == part[rows]
        taken = v_sorted.take(pa.array(np.where(valid, idx, 0)))
        out = pc.if_else(pa.array(valid), taken,
                         pa.scalar(None, type=src_type))
    elif func == "row_number":
        out = pa.array(W.row_number(part_start))
    elif func == "rank":
        out = pa.array(W.rank_from_ties(part_start, new_tie))
    elif func == "dense_rank":
        out = pa.array(W.dense_rank_from_ties(new_part, new_tie))
    elif func == "ntile":
        out = pa.array(W.ntile(part_start, part_end, plan.offset))
    else:
        _, tie_end = W.segment_bounds(new_tie)
        lo, hi = W.frame_bounds(part_start, part_end, tie_end,
                                plan.frame, bool(plan.order_by))
        src_type = table.schema.field(plan.value).type if plan.value \
            else None
        v_sorted = None
        if plan.value is not None:
            v_sorted = table.column(plan.value).take(perm)
            if isinstance(v_sorted, pa.ChunkedArray):
                v_sorted = v_sorted.combine_chunks()
        if func in ("first_value", "last_value"):
            arg, nonempty = W.frame_first_last(lo, hi,
                                               func == "first_value")
            taken = v_sorted.take(pa.array(arg))
            out = pc.if_else(pa.array(nonempty), taken,
                             pa.scalar(None, type=src_type))
        elif func == "count" and plan.value is None:
            out = pa.array(W.frame_count(None, lo, hi))
        else:
            vals, valid = _np_window_values(v_sorted)
            if vals is None:
                # Strings/binary/decimals: exact Arrow hash-agg path for
                # whole-partition shapes, loud error for running frames
                # (parity with the round-4 engine; decimals additionally
                # avoid a lossy float64 round-trip).
                whole = plan.frame is None and not plan.order_by
                arrow_funcs = ("min", "max") \
                    if not pa.types.is_decimal(v_sorted.type) \
                    else ("min", "max", "sum", "mean")
                if func in arrow_funcs and whole:
                    out = _whole_partition_agg_arrow(v_sorted, part, func)
                    if func in ("sum", "mean"):
                        out = pc.cast(out, pa.float64())
                elif func == "count":
                    out = pa.array(W.frame_count(valid, lo, hi))
                else:
                    raise ValueError(
                        f"Running window {func}() over a "
                        f"{v_sorted.type} column is not supported; "
                        f"drop the ORDER BY for a whole-partition "
                        f"{func}, or cast the column to a "
                        f"numeric/temporal type")
            elif func == "count":
                out = pa.array(W.frame_count(valid, lo, hi))
            elif func == "sum":
                sums, cnt = W.frame_sum(vals, valid, lo, hi)
                if vals.dtype.kind == "u":
                    # uint64 sums computed in uint64; the int64 result
                    # column overflows loudly, never wraps.
                    if sums.size and sums.max() > np.iinfo(np.int64).max:
                        raise ValueError(
                            "window sum() over a uint64 column "
                            "overflows the int64 result type")
                    out = pa.array(sums.astype(np.int64),
                                   mask=(cnt == 0))
                elif vals.dtype.kind in "ib":
                    out = pa.array(sums.astype(np.int64),
                                   mask=(cnt == 0))
                else:
                    out = pa.array(sums.astype(np.float64),
                                   mask=(cnt == 0))
            elif func == "mean":
                means, cnt = W.frame_mean(vals, valid, lo, hi)
                out = pa.array(means, mask=(cnt == 0))
            else:  # min / max
                arg, cnt = W.frame_min_max(
                    vals, valid, lo, hi, part_start, part_end,
                    plan.frame, is_min=(func == "min"))
                taken = v_sorted.take(pa.array(arg))
                out = pc.if_else(pa.array(cnt > 0), taken,
                                 pa.scalar(None, type=src_type))
    # Scatter back to the original row order.
    inverse = np.empty(n, dtype=np.int64)
    inverse[perm_np] = np.arange(n)
    out = out.take(pa.array(inverse))
    if plan.name in table.column_names:
        return table.set_column(table.column_names.index(plan.name),
                                plan.name, out)
    return table.append_column(plan.name, out)


def _concat_horizontal(left: pa.Table, right: pa.Table) -> pa.Table:
    names = list(left.column_names)
    cols = list(left.columns)
    for name, col in zip(right.column_names, right.columns):
        out_name = name
        n = 1
        while out_name in names:
            out_name = f"{name}__{n}"
            n += 1
        names.append(out_name)
        cols.append(col)
    return pa.table(dict(zip(names, cols)))


def _eval_column(expr: Expr, table: pa.Table):
    """Evaluate ``expr`` as an output COLUMN (Compute/WithColumns/agg
    inputs): array results pass through, scalar results broadcast to the
    table's length (``lit(1)`` as a column)."""
    result = _arrow_eval(expr, table)
    if isinstance(result, pa.Scalar):
        return pa.array([result.as_py()] * table.num_rows,
                        type=result.type if result.is_valid else None)
    return result


def _coerce_numeric_strings(column) -> np.ndarray:
    """Vectorized null-on-failure numeric parse of a string column:
    float64 values with NaN where the string (or a null) didn't parse —
    the one shared home for the pd.to_numeric coerce idiom."""
    import pandas as pd

    arr = column.to_numpy(zero_copy_only=False)
    return pd.to_numeric(pd.Series(arr), errors="coerce") \
        .to_numpy(dtype=np.float64, na_value=np.nan)


def _parse_numeric(column, target_type) -> pa.Array:
    """Parse a string column as ``target_type``, null on failure — the
    Spark coercion for string-column vs numeric-literal comparisons
    ('05' == 5 and '5.0' == 5 match via the double promotion; 'abc'
    becomes null and the row drops)."""
    try:
        return pc.cast(column, target_type)
    except (pa.ArrowInvalid, pa.ArrowTypeError):
        # 'abc' -> NaN, which no comparison matches — same row-drop
        # effect as Spark's null.
        return pa.array(_coerce_numeric_strings(column), type=target_type)


def _arrow_eval(expr: Expr, table: pa.Table):
    if isinstance(expr, Col):
        return table.column(expr.name)
    if isinstance(expr, Lit):
        return pa.scalar(expr.value)
    if isinstance(expr, BinOp):
        left = _arrow_eval(expr.left, table)
        right = _arrow_eval(expr.right, table)
        ops = {"==": pc.equal, "<": pc.less, "<=": pc.less_equal,
               ">": pc.greater, ">=": pc.greater_equal}
        try:
            return ops[expr.op](left, right)
        except pa.ArrowNotImplementedError:
            # Spark-style coercion.  String column vs numeric literal: Spark
            # casts the STRING side to the numeric type ('05' == 5 matches),
            # so cast the column, not the literal.  Otherwise a scalar of a
            # different type is cast to the column's type (e.g. "2024" vs an
            # int64 partition column).  Uncastable values re-raise.
            def coerced(scalar, column):
                if (pa.types.is_string(column.type)
                        and (pa.types.is_integer(scalar.type)
                             or pa.types.is_floating(scalar.type))):
                    # Spark promotes string-vs-numeric to DOUBLE, so
                    # '5.0' == 5 and '5e0' == 5 both match.
                    target = pa.float64()
                    return pc.cast(scalar, target), \
                        _parse_numeric(column, target)
                # pc.cast parses, e.g. string "2024" -> int64 2024.
                return pc.cast(scalar, column.type), column

            try:
                if isinstance(left, pa.Scalar) and not isinstance(right, pa.Scalar):
                    lhs, rhs = coerced(left, right)
                    return ops[expr.op](lhs, rhs)
                if isinstance(right, pa.Scalar) and not isinstance(left, pa.Scalar):
                    rhs, lhs = coerced(right, left)
                    return ops[expr.op](lhs, rhs)
            except (pa.ArrowInvalid, pa.ArrowTypeError, ValueError, TypeError):
                pass
            raise
    if isinstance(expr, Arith):
        left = _arrow_eval(expr.left, table)
        right = _arrow_eval(expr.right, table)
        if expr.op == "/":
            # Spark non-ANSI division: result is DOUBLE; x / 0 is NULL
            # (arrow would give inf for floats and raise for ints).
            left = pc.cast(left, pa.float64())
            right = pc.cast(right, pa.float64())
            zero = pc.equal(right, pa.scalar(0.0))
            safe = pc.if_else(zero, pa.scalar(1.0), right)
            return pc.if_else(zero, pa.scalar(None, type=pa.float64()),
                              pc.divide(left, safe))
        fn = {"+": pc.add, "-": pc.subtract, "*": pc.multiply}[expr.op]
        return fn(left, right)
    if isinstance(expr, Neg):
        return pc.negate(_arrow_eval(expr.child, table))
    if isinstance(expr, And):
        return pc.and_kleene(_arrow_eval(expr.left, table), _arrow_eval(expr.right, table))
    if isinstance(expr, Or):
        return pc.or_kleene(_arrow_eval(expr.left, table), _arrow_eval(expr.right, table))
    if isinstance(expr, Not):
        return pc.invert(_arrow_eval(expr.child, table))
    if isinstance(expr, BucketIn):
        # Quarantine containment (rules/hybrid.py): membership of each
        # row's hash bucket — computed with the build kernel's own host
        # mirror, so "rows of bucket b" here can never disagree with
        # which rows the damaged index file actually held.  Nulls hash to
        # their deterministic sentinel bucket (same as the build): the
        # mask is null-free.
        from hyperspace_tpu.io.columnar import to_hash_words
        from hyperspace_tpu.ops.hash import bucket_ids_np

        word_cols = [np.asarray(to_hash_words(table.column(c)))
                     for c in expr.columns]
        row_buckets = bucket_ids_np(word_cols, expr.num_buckets)
        return pa.array(np.isin(
            row_buckets, np.asarray(expr.buckets, dtype=row_buckets.dtype)))
    if isinstance(expr, IsIn):
        child = _arrow_eval(expr.child, table)
        # Spark 3VL, which arrow's is_in does not implement:
        #   NULL IN (...)          -> NULL  (arrow: false)
        #   x IN (..no match.., NULL) -> NULL  (arrow: false)
        # Both matter under NOT — false would flip to TRUE and keep rows
        # SQL drops.
        values = [v for v in expr.values if v is not None]
        null_in_list = len(values) != len(expr.values)
        null_bool = pa.scalar(None, type=pa.bool_())
        result = pc.is_in(child, value_set=pa.array(values)) if values \
            else pa.scalar(False)
        if null_in_list:
            result = pc.if_else(result, pa.scalar(True), null_bool)
        if isinstance(child, pa.Scalar):
            if not child.is_valid:
                return null_bool
            return result
        return pc.if_else(pc.is_valid(child), result, null_bool)
    if isinstance(expr, IsNull):
        return pc.is_null(_arrow_eval(expr.child, table))
    if isinstance(expr, Cast):
        from hyperspace_tpu.io.parquet import _dtype_from_string

        child = _arrow_eval(expr.child, table)
        target = _dtype_from_string(expr.type_name)
        try:
            return pc.cast(child, target)
        except (pa.ArrowInvalid, pa.ArrowNotImplementedError,
                pa.ArrowTypeError):
            pass
        # Spark non-ANSI CAST: unconvertible values become null and
        # float->int truncates toward zero (out-of-range -> null), never
        # an error.  Vectorized try isn't available in arrow, so retry
        # element-wise only when the bulk safe cast fails.
        import math

        def int_bounds(t):
            bits = t.bit_width
            if pa.types.is_signed_integer(t):
                return -(1 << (bits - 1)), (1 << (bits - 1)) - 1
            return 0, (1 << bits) - 1

        def scalar_cast(v):
            if v is None:
                return None
            if isinstance(v, (float, str)) and pa.types.is_integer(target):
                # Spark parses numeric strings as decimal and truncates:
                # '3.5' AS INT is 3, not null.  Integer strings parse
                # EXACTLY (int64 strings must not round-trip via float64)
                # but only ASCII-digit forms — int()'s Python-only syntax
                # ('1_000', unicode digits) must null exactly like the
                # vectorized pd.to_numeric column path does.
                if isinstance(v, str):
                    import re

                    sv = v.strip()
                    if re.fullmatch(r"[+-]?[0-9]+", sv):
                        v = int(sv)
                    else:
                        f = _coerce_numeric_strings(pa.array([v]))[0]
                        if math.isnan(f):
                            return None
                        v = float(f)
                if isinstance(v, float):
                    if math.isnan(v) or math.isinf(v):
                        return None
                    v = int(v)  # truncation toward zero, like Spark
                lo, hi = int_bounds(target)
                return v if lo <= v <= hi else None
            try:
                return pc.cast(pa.array([v]), target)[0].as_py()
            except (pa.ArrowInvalid, pa.ArrowNotImplementedError,
                    pa.ArrowTypeError, ValueError, OverflowError):
                return None

        if isinstance(child, pa.Scalar):
            return pa.scalar(scalar_cast(child.as_py()), type=target)
        ctype = child.type
        if (pa.types.is_integer(target) and target.bit_width <= 64
                and not (pa.types.is_unsigned_integer(target)
                         and target.bit_width == 64)
                and (pa.types.is_floating(ctype)
                     or pa.types.is_string(ctype)
                     or pa.types.is_large_string(ctype))):
            # The common fallback cases — float->int with fractional values,
            # string->int with any bad value — stay vectorized instead of
            # an O(n) Python loop.  Numeric strings parse as decimal first
            # ('3.5' AS INT is 3, like Spark), bad values null.
            valid = np.asarray(
                pc.is_valid(child).to_numpy(zero_copy_only=False))
            if pa.types.is_floating(ctype):
                arr = np.asarray(pc.fill_null(child, 0.0)
                                 .to_numpy(zero_copy_only=False),
                                 dtype=np.float64)
            else:
                arr = _coerce_numeric_strings(child)
                valid &= ~np.isnan(arr)
                arr = np.where(np.isnan(arr), 0.0, arr)
            finite = np.isfinite(arr)
            trunc = np.trunc(np.where(finite, arr, 0.0))
            lo, hi = int_bounds(target)
            hi_f = float(hi)
            ok = valid & finite & (trunc >= float(lo)) & (
                trunc <= hi_f if int(hi_f) == hi else trunc < hi_f)
            vals = np.where(ok, trunc, 0.0).astype(np.int64)
            if not pa.types.is_floating(ctype):
                # float64 is exact only below 2**53: integer strings in the
                # tail (int64-range ids) re-parse exactly, element-wise
                # over just those rows.  Only ASCII-integer forms can gain
                # precision — the vectorized regex keeps the loop empty for
                # float-form tails ('1e300' columns stay O(1) Python).
                big = np.nonzero(valid & (np.abs(trunc) >= 2.0**53))[0]
                if big.size:
                    intlike = np.asarray(pc.fill_null(
                        pc.match_substring_regex(
                            child, r"^\s*[+-]?[0-9]+\s*$"), False)
                        .to_numpy(zero_copy_only=False), dtype=bool)
                    big = big[intlike[big]]
                for i in big.tolist():
                    exact = scalar_cast(child[i].as_py())
                    if exact is None:
                        ok[i] = False
                    else:
                        vals[i] = exact
                        ok[i] = True
            out = pa.array(vals, mask=~ok)
            return pc.cast(out, target)
        return pa.array([scalar_cast(v) for v in child.to_pylist()],
                        type=target)
    if isinstance(expr, Extract):
        child = _arrow_eval(expr.child, table)
        fns = {"year": pc.year, "month": pc.month, "day": pc.day,
               "quarter": pc.quarter}
        out = fns[expr.field](child)
        # Spark's year()/month()/... return INT (32-bit); arrow yields
        # int64 — match Spark so downstream casts/joins see the same type.
        return pc.cast(out, pa.int32())
    if isinstance(expr, StringFn):
        args = [_arrow_eval(a, table) for a in expr.args]
        if expr.name == "upper":
            return pc.utf8_upper(args[0])
        if expr.name == "lower":
            return pc.utf8_lower(args[0])
        if expr.name == "length":
            return pc.cast(pc.utf8_length(args[0]), pa.int32())
        if expr.name == "trim":
            return pc.utf8_trim_whitespace(args[0])
        if expr.name == "ltrim":
            return pc.utf8_ltrim_whitespace(args[0])
        if expr.name == "rtrim":
            return pc.utf8_rtrim_whitespace(args[0])
        if expr.name == "substring":
            # SQL 1-based start (validated >= 1 at construction).
            begin = expr.args[1].value - 1
            if len(expr.args) == 2:
                return pc.utf8_slice_codeunits(args[0], begin)
            return pc.utf8_slice_codeunits(args[0], begin,
                                           begin + expr.args[2].value)
        # concat: Spark casts every part to string and nulls the WHOLE
        # result when any part is null.  Scalars stay scalars —
        # binary_join_element_wise broadcasts them without an O(rows)
        # literal array.
        def as_str(part):
            t = part.type
            if not (pa.types.is_string(t) or pa.types.is_large_string(t)):
                part = pc.cast(part, pa.string())
            return part

        parts = [as_str(a) for a in args]
        return pc.binary_join_element_wise(
            *parts, "", null_handling="emit_null")
    if isinstance(expr, StringMatch):
        child = _arrow_eval(expr.child, table)
        if expr.kind == "like":
            return pc.match_like(child, expr.pattern)
        if expr.kind == "startswith":
            return pc.starts_with(child, expr.pattern)
        if expr.kind == "endswith":
            return pc.ends_with(child, expr.pattern)
        return pc.match_substring(child, expr.pattern)
    if isinstance(expr, Case):
        # Spark CASE: branches in order, null condition = branch NOT taken
        # (arrow's if_else would propagate the null instead), no ELSE =
        # null.  Built right-to-left so earlier branches win.
        result = _arrow_eval(expr.otherwise, table)
        if isinstance(result, pa.Scalar) and not result.is_valid \
                and result.type == pa.null():
            # Untyped null ELSE: let if_else infer the branch type.
            result = None
        for cond, value in reversed(expr.branches):
            mask = _arrow_eval(cond, table)
            if isinstance(mask, pa.Scalar):
                mask = pa.scalar(bool(mask.as_py())
                                 if mask.is_valid else False)
            else:
                mask = pc.fill_null(mask, False)
            val = _arrow_eval(value, table)
            if result is None:
                # First (innermost) branch with a null ELSE: null of the
                # branch value's type.
                result = pa.scalar(None, type=val.type)
            result = pc.if_else(mask, val, result)
        return result
    raise ValueError(f"Unsupported expression: {expr!r}")
