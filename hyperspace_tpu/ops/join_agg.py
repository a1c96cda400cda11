"""Fused device join→aggregate pipeline: the whole Q3/Q10 hot path on chip.

The north-star workloads (BASELINE.md: TPC-H Q3/Q10 wall-clock) are
``aggregate(filter ⨝ index)`` shapes.  Executing the join and the
aggregation as separate engines forces the full joined row set through
host memory and back across the host–device link.  This
pipeline keeps the intermediate entirely in HBM:

  1. sorted equi-join over the (resident) key columns — match ranges
     ranked by the sort unit (one merged sort of both sides' keys, one
     back to row order; ops/join.py), one host sync for the match count
     (the standard XLA dynamic-shape point);
  2. device gather of every referenced column through the match indices
     (group keys, aggregate inputs) — the joined table never
     materializes anywhere;
  3. expression aggregate inputs (sum(price * (1 - discount))) evaluated
     elementwise on the gathered arrays (ops/filter.build_value_fn);
  4. group-by via the segment machinery (ops/aggregate._group_sort /
     _segment_reduce) — second host sync for the group count;
  5. only the per-group results cross back to host: counts, reductions
     and the group-key values in their int64 domain, which the executor
     casts back to the columns' arrow types — so a side served from HBM
     needs no host table (in group slots of the power of two above the
     group count, padded toward half the join's match capacity by at most 8
     times, so a moving group count keeps its programs).

Reference contract: Spark executes the rewritten plans of
JoinIndexRule.scala:36-50 as exchange-free SMJ + HashAggregate; this is
the TPU-native fusion of the two with O(groups) — not O(matches) —
host traffic.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from hyperspace_tpu.execution import sync_guard
from hyperspace_tpu.ops.aggregate import _group_sort, _segment_reduce
from jax import enable_x64 as _enable_x64
from hyperspace_tpu.ops.join import _join_pairs, _join_ranges
from hyperspace_tpu.ops.sort import _pad_rows
from hyperspace_tpu.utils.shapes import round_up_pow2


@partial(jax.jit, static_argnames=("k", "ascending", "capacity"))
def _topk_groups(col, n_valid, *, k: int, ascending: bool,
                 capacity: int):
    """Indices of the top/bottom-k VALID group slots by ``col`` —
    the device form of ORDER BY <agg> LIMIT k, so only k groups (not
    all of them) ever cross to the host.  Invalid (padding) slots
    are parked with sentinels; ``k`` and the capacity are static, the
    valid count is traced."""
    valid = jnp.arange(capacity) < n_valid
    if jnp.issubdtype(col.dtype, jnp.floating):
        sentinel = jnp.array(-jnp.inf, dtype=col.dtype)
        work = col if not ascending else -col
        # NaN must map to the sentinel BEFORE top_k: lax.top_k ranks NaN
        # unpredictably, so an ORDER BY <agg> LIMIT k could otherwise
        # pick different boundary groups than the host sort.  (-NaN is
        # still NaN, so one check after the flip covers both orders.)
        work = jnp.where(jnp.isnan(work), sentinel, work)
    else:
        sentinel = jnp.iinfo(col.dtype).min
        # Ascending via BITWISE not (monotone decreasing, total on the
        # whole domain): arithmetic negation overflows at iinfo.min, so
        # ORDER BY <agg> ASC could mis-rank a group whose count/sum hit
        # the extreme value.
        work = col if not ascending else ~col
    work = jnp.where(valid, work, sentinel)
    _vals, idx = jax.lax.top_k(work, k)
    return idx


@jax.jit
def _stack_rows(cols, rows):
    return jnp.stack([c[rows].astype(jnp.int64) for c in cols])


def _group_keys_at(gathered, group_col_ix, rows) -> np.ndarray:
    """The group columns' int64-domain values at the joined ``rows`` (each
    group's first), gathered on device and pulled in one transfer."""
    if not group_col_ix:
        return np.empty((0, int(rows.shape[0])), np.int64)
    return sync_guard.pull(
        _stack_rows(tuple(gathered[i] for i in group_col_ix), rows),
        "join_agg.group_keys")


def _empty_result(n_aggs: int, n_group_cols: int):
    return (np.empty(0, np.int32), [np.empty(0) for _ in range(n_aggs)],
            np.empty((n_group_cols, 0), np.int64))


def _int_order_words(x: jnp.ndarray) -> jnp.ndarray:
    """(n, 2) uint32 monotone order words from an int64-domain array
    (ints, bools, temporals in their numeric normalization): flip the
    sign bit, split halves.  Bit layout matches what the group sort
    needs — any monotone injective encoding works, order falls out."""
    ux = (x.astype(jnp.int64) ^ jnp.int64(-(2 ** 63))).astype(jnp.uint64)
    hi = (ux >> np.uint64(32)).astype(jnp.uint32)
    lo = (ux & np.uint64(0xFFFFFFFF)).astype(jnp.uint32)
    return jnp.stack([hi, lo], axis=-1)


def _int_order_words_np(x: np.ndarray) -> np.ndarray:
    """Host mirror of :func:`_int_order_words` — the same (n, 2) uint32
    monotone encoding, bit-identical, for the sharded wrapper's
    group-key partitioning."""
    with np.errstate(over="ignore"):
        ux = (x.astype(np.int64) ^ np.int64(-(2 ** 63))).astype(np.uint64)
        hi = (ux >> np.uint64(32)).astype(np.uint32)
        lo = (ux & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    return np.stack([hi, lo], axis=-1)


def join_group_aggregate_mesh(
    l_key,
    r_key,
    columns: Sequence,
    column_sides: Sequence[str],
    group_col_ix: Sequence[int],
    agg_ops: Sequence[str],
    value_fns: Sequence[Callable],
    literals: Sequence[Sequence[float]],
    mesh,
    pad_to: int = 0,
) -> Tuple[np.ndarray, List[np.ndarray], np.ndarray]:
    """Sharding-aware entry of the join→aggregate pipeline: the same
    result contract as :func:`join_group_aggregate` (groups ascending by
    key), computed as three mesh stages —

      1. co-partitioned inner join by join-key bucket ownership
         (``ops.join.sorted_equi_join_mesh``: zero cross-device shuffle,
         only the match-index gather),
      2. elementwise aggregate-input evaluation sharded row-wise over
         the mesh (GSPMD partitions the expression with zero
         collectives, ``parallel/filter.eval_predicate_on_mesh``),
      3. grouped aggregation with GROUP-key bucket ownership
         (``parallel/aggregate.mesh_grouped_aggregate`` — each group is
         reduced whole on one device, no partial-merge pass).

    Unlike the fused single-device kernel the joined intermediate
    transits host between stages (O(matches) traffic — the price of
    re-partitioning from join-key to group-key ownership); the win is
    that every stage scales with the mesh.  ``topn`` fusion is not
    supported — callers wanting it keep the single-device kernel.
    Host inputs only (resident arrays keep the fused kernel)."""
    from hyperspace_tpu.ops.join import sorted_equi_join_mesh
    from hyperspace_tpu.parallel.aggregate import mesh_grouped_aggregate
    from hyperspace_tpu.parallel.filter import eval_predicate_on_mesh

    l_key = np.asarray(l_key)
    r_key = np.asarray(r_key)
    host_cols = [np.asarray(c) for c in columns]

    if l_key.shape[0] == 0 or r_key.shape[0] == 0:
        return _empty_result(len(agg_ops), len(group_col_ix))
    li, ri = sorted_equi_join_mesh(l_key, r_key, mesh)
    if li.size == 0:
        return _empty_result(len(agg_ops), len(group_col_ix))
    gathered = [c[li if side == "l" else ri]
                for c, side in zip(host_cols, column_sides)]
    key_words = [_int_order_words_np(gathered[i]) for i in group_col_ix]
    # Literal dtype follows numpy inference (all-int vectors stay
    # integral), exactly like the fused kernel's literal handling.
    value_cols = [
        np.asarray(eval_predicate_on_mesh(
            fn, gathered,
            np.asarray(lits) if lits else np.zeros(0), mesh))
        for fn, lits in zip(value_fns, literals)]
    first_rows, counts, results = mesh_grouped_aggregate(
        key_words, value_cols, agg_ops, mesh, pad_to=pad_to)
    first_rows = first_rows.astype(np.int64)
    keys = np.stack([gathered[i][first_rows] for i in group_col_ix]
                    ).astype(np.int64).reshape(len(group_col_ix), -1)
    return counts, results, keys


def join_group_aggregate(
    l_key,
    r_key,
    columns: Sequence,
    column_sides: Sequence[str],
    group_col_ix: Sequence[int],
    agg_ops: Sequence[str],
    value_fns: Sequence[Callable],
    literals: Sequence[Sequence[float]],
    topn: Optional[Tuple[int, bool, int]] = None,
) -> Tuple[np.ndarray, List[np.ndarray], np.ndarray]:
    """Inner-join two sides on single numeric keys, then group-aggregate
    the joined rows — all on device.

    Args:
      l_key/r_key: numeric key arrays (device-resident jax arrays pass
        through untouched; numpy ships once).
      columns: referenced column arrays, each tagged "l"/"r" in
        ``column_sides`` (lengths match their side's key).
      group_col_ix: indices into ``columns`` forming the group key, in
        group-by order (int64-domain values).
      agg_ops: per aggregate, one of sum/min/max/mean/count/count_all.
      value_fns/literals: per NON-count aggregate, an elementwise
        builder over the gathered columns (ops/filter.build_value_fn)
        and its literal vector.
      topn: optional (agg_index, ascending, k) — keep only the k
        groups ranking first by that aggregate's result (ORDER BY
        <agg> LIMIT k fused on device; host traffic drops from
        O(groups) to O(k)).

    Returns:
      (counts, results, keys): per group, its row count, one result
      array per aggregate, and — a (len(group_col_ix), groups) int64
      array pulled in one transfer — the group keys' values at its first
      joined row, which the executor casts back to the columns' types.
    """

    from hyperspace_tpu.telemetry import timeline
    from hyperspace_tpu.utils.xla_cache import ensure_persistent_xla_cache

    ensure_persistent_xla_cache()
    t0 = timeline.kernel_begin()
    if t0 is not None:
        # Attribution seam (conf-gated): host inputs are about to ship.
        timeline.record_transfer("h2d", sum(
            int(getattr(a, "nbytes", 0))
            for a in (l_key, r_key, *columns)
            if not isinstance(a, jax.Array)))
    with _enable_x64():
        n_left, n_right = int(l_key.shape[0]), int(r_key.shape[0])
        if n_left == 0 or n_right == 0:
            return _empty_result(len(agg_ops), len(group_col_ix))
        # Keys padded to a power of two (ops/join.sorted_equi_join's
        # capacity contract): padded rows match nothing, so the match
        # indices only ever address the real rows of ``columns``.
        lk = jnp.asarray(_pad_rows(l_key, round_up_pow2(n_left)))
        rk = jnp.asarray(_pad_rows(r_key, round_up_pow2(n_right)))
        r_perm, lo, hi, total = _join_ranges(lk, rk, n_left, n_right)
        # sync 1: match count (the standard XLA dynamic-shape point)
        total = int(sync_guard.scalar(total, "join_agg.matches"))
        if total == 0:
            timeline.kernel_end("join_agg", t0, (lo, hi))
            return _empty_result(len(agg_ops), len(group_col_ix))
        capacity = round_up_pow2(total)
        li, ri = _join_pairs(r_perm, lo, hi, capacity)
        # Host columns are padded like their side's key, so a side whose
        # row count moves (a new snapshot's appended rows) reuses its
        # gather programs; an HBM-resident column keeps its fixed shape.
        side_rows = {"l": lk.shape[0], "r": rk.shape[0]}
        gathered = [
            jnp.asarray(c if isinstance(c, jax.Array)
                        else _pad_rows(c, side_rows[side]))[
                li if side == "l" else ri]
            for c, side in zip(columns, column_sides)]
        key_words = tuple(_int_order_words(gathered[i])
                          for i in group_col_ix)
        # Literal dtype follows numpy inference: all-int literal vectors
        # stay integral so int expression aggregates don't silently
        # promote to float (host arrow keeps them int64).
        value_cols = tuple(
            fn(gathered, jnp.asarray(np.asarray(lits))
               if lits else jnp.zeros(0))
            for fn, lits in zip(value_fns, literals))
        perm, boundaries, n_groups = _group_sort(key_words, total)
        # sync 2: group count
        g = int(sync_guard.scalar(n_groups, "join_agg.groups"))
        if g == 0:
            timeline.kernel_end("join_agg", t0, perm)
            return _empty_result(len(agg_ops), len(group_col_ix))
        # Group slots: the power of two above g, padded toward half the
        # match capacity by at most 8 times.  A group count that moves
        # with a growing table (a new snapshot's rows) keeps its programs
        # while it stays between capacity/16 and capacity/2, and a few
        # groups over many matches pull a few dozen slots, not the join.
        gcap = round_up_pow2(g)
        gcap = max(gcap, min(capacity // 2, 8 * gcap))
        out = _segment_reduce(perm, boundaries, total, value_cols,
                              ops=tuple(agg_ops), capacity=gcap)
        if topn is not None:
            agg_i, ascending, k = topn
            k_eff = min(int(k), g)
            sel = _topk_groups(out[2 + agg_i], g, k=k_eff,
                               ascending=bool(ascending), capacity=gcap)
            timeline.kernel_end("join_agg", t0, (out, sel))
            counts = sync_guard.pull(out[1][sel], "join_agg.counts")
            results = [sync_guard.pull(r[sel], "join_agg.results")
                       for r in out[2:]]
            keys = _group_keys_at(gathered, group_col_ix, out[0][sel])
            return counts, results, keys
        timeline.kernel_end("join_agg", t0, out)
        # Gathered over all ``gcap`` slots and cut on host: programs sized
        # by the exact group count would compile anew whenever it moves.
        counts = sync_guard.pull(out[1], "join_agg.counts")[:g]
        results = [sync_guard.pull(r, "join_agg.results")[:g]
                   for r in out[2:]]
        keys = _group_keys_at(gathered, group_col_ix, out[0])[:, :g]
    return counts, results, keys
