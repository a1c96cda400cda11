"""Device-side grouped aggregation kernel: sort by key words, segment-reduce.

Reference contract: Spark's HashAggregateExec is what executes the
reference's GROUP BY plans (the reference itself ships no aggregation code —
SURVEY.md §2.4's "components Spark provides" note); this engine previously
ran every aggregation on host arrow.  The device path reuses the bucket
machinery's normalization: group keys become monotone uint32 order words
(io/columnar.to_order_words), rows lexsort by them, group boundaries fall
out of adjacent-word comparison, and every aggregate is one XLA
``segment_sum``/``segment_min``/``segment_max`` over the sorted rows.

Two static-shape programs, like the join kernels:
  1. sort + boundary detection; only the GROUP COUNT crosses to host
     (perm/boundaries stay device-resident),
  2. capacity-padded segment reduction (capacity = next pow2 of the group
     count, so repeated queries share compiled programs).

Supported: non-empty integer/bool group keys, null-free numeric inputs,
sum/min/max/mean/count/count_all.  Everything else stays on the arrow host
path (the executor gates, execution/executor.py).  Floating-point KEYS are
excluded: NaN bit patterns would split arrow's single NaN group.
"""

from __future__ import annotations

from functools import partial
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from hyperspace_tpu.execution import sync_guard
from hyperspace_tpu.ops.hash import lexsort32
from jax import enable_x64 as _enable_x64
from hyperspace_tpu.utils.shapes import round_up_pow2

AGG_OPS = ("sum", "min", "max", "mean", "count", "count_all")


@jax.jit
def _group_sort(key_words, n_valid):
    """(perm, boundaries, n_groups): rows lexsorted by key words with
    padding parked last (validity is the PRIMARY sort key, as in the join
    kernel); boundaries mark the first row of each group among the valid
    prefix."""
    n = key_words[0].shape[0]
    positions = jnp.arange(n, dtype=jnp.int32)
    invalid = (positions >= n_valid).astype(jnp.uint32)
    keys = []
    for w in reversed(key_words):
        keys.append(w[:, 1])
        keys.append(w[:, 0])
    keys.append(invalid)  # LAST key = primary: valid rows first
    perm = lexsort32(keys)
    is_valid = positions < n_valid
    diff = jnp.zeros(n, dtype=bool)
    for w in key_words:
        sorted_w = w[perm]
        d = (sorted_w[1:] != sorted_w[:-1]).any(axis=-1)
        diff = diff.at[1:].set(diff[1:] | d)
    boundaries = (diff | (positions == 0)) & is_valid
    return perm, boundaries, jnp.sum(boundaries, dtype=jnp.int32)


@partial(jax.jit, static_argnames=("ops", "capacity"))
def _segment_reduce(perm, boundaries, n_valid, value_cols, *, ops, capacity):
    """Per-group reductions over the sorted rows.  Returns
    (first_positions, counts, per-op arrays), each (capacity,); slots past
    the real group count are zeros/identities and sliced off on host."""
    n = perm.shape[0]
    positions = jnp.arange(n, dtype=jnp.int32)
    is_valid = positions < n_valid
    seg_ids = jnp.cumsum(boundaries.astype(jnp.int32)) - 1
    # Padded rows (sorted past the valid prefix) get segment `capacity` —
    # out of every real segment's range.
    seg_ids = jnp.where(is_valid, seg_ids, capacity)
    first_pos = jnp.nonzero(boundaries, size=capacity, fill_value=n - 1)[0]
    first_rows = perm[first_pos].astype(jnp.int32)
    counts = jax.ops.segment_sum(is_valid.astype(jnp.int32), seg_ids,
                                 num_segments=capacity + 1)[:capacity]
    outs = []
    vi = 0
    for op in ops:
        if op in ("count", "count_all"):
            # No value column — counts need nothing shipped or gathered.
            outs.append(counts)
            continue
        col = value_cols[vi]
        vi += 1
        vals = col[perm]
        if op in ("sum", "mean"):
            r = jax.ops.segment_sum(
                jnp.where(is_valid, vals, jnp.zeros_like(vals)), seg_ids,
                num_segments=capacity + 1)[:capacity]
            if op == "mean":
                r = r.astype(jnp.float64) / jnp.maximum(counts, 1)
        elif op == "min":
            r = jax.ops.segment_min(vals, seg_ids,
                                    num_segments=capacity + 1)[:capacity]
        elif op == "max":
            r = jax.ops.segment_max(vals, seg_ids,
                                    num_segments=capacity + 1)[:capacity]
        else:  # unreachable: AGG_OPS is validated by the caller
            raise AssertionError(op)
        outs.append(r)
    return (first_rows, counts) + tuple(outs)


def grouped_aggregate_mesh(
    key_words: Sequence[np.ndarray],
    value_cols: Sequence[np.ndarray],
    ops: Sequence[str],
    mesh,
    pad_to: int = 0,
) -> Tuple[np.ndarray, np.ndarray, List[np.ndarray]]:
    """Sharding-aware entry of the grouped aggregation: same contract
    and group order as :func:`grouped_aggregate`, computed over ``mesh``
    with group-key bucket ownership (a group's rows all land on one
    device, so every reduction is exact — parallel/aggregate.py)."""
    from hyperspace_tpu.parallel.aggregate import mesh_grouped_aggregate

    return mesh_grouped_aggregate(key_words, value_cols, ops, mesh,
                                  pad_to=pad_to)


def grouped_aggregate(
    key_words: Sequence[np.ndarray],
    value_cols: Sequence[np.ndarray],
    ops: Sequence[str],
    pad_to: int = 0,
) -> Tuple[np.ndarray, np.ndarray, List[np.ndarray]]:
    """Device grouped aggregation.

    Args:
      key_words: per group-key column, (n, 2) uint32 monotone order words.
      value_cols: one length-n numeric array per NON-count aggregate, in
        ops order (count/count_all ship no data — nothing to reduce).
      ops: per aggregate, one of AGG_OPS.
      pad_to: round the row dimension up to a multiple (compile-cache
        sharing across row counts, conf device_batch_rows).

    Returns:
      (first_row_indices, counts, results): for each of G groups, the index
      of its first row in the ORIGINAL order (host gathers the key values
      from the arrow table — no dtype round trip), the row count, and one
      result array per aggregate.  Groups are emitted in ascending key
      order.
    """
    from hyperspace_tpu.ops.sort import _pad_rows
    from hyperspace_tpu.utils.xla_cache import ensure_persistent_xla_cache

    for op in ops:
        if op not in AGG_OPS:
            raise ValueError(f"Unsupported device aggregate {op!r}")
    ensure_persistent_xla_cache()
    n = int(key_words[0].shape[0])
    capacity_rows = n
    if pad_to and pad_to > 0:
        capacity_rows = -(-max(n, 1) // pad_to) * pad_to
    from hyperspace_tpu.telemetry import timeline

    t0 = timeline.kernel_begin()
    if t0 is not None:
        timeline.record_transfer("h2d", sum(
            int(getattr(a, "nbytes", 0))
            for a in (*key_words, *value_cols)
            if not isinstance(a, jax.Array)))
    with _enable_x64():
        # Device-resident inputs (jax arrays from the HBM cache) pass
        # through _pad_rows untouched — it pads them on device instead of
        # pulling.  Padding must run INSIDE the x64 region: jnp.pad of a
        # float64/int64 device array under 32-bit mode silently downcasts,
        # which cost float sums ~1e-6 relative error.
        kw = tuple(_pad_rows(w, capacity_rows) for w in key_words)
        vc = tuple(_pad_rows(v, capacity_rows) for v in value_cols)
        perm, boundaries, n_groups = _group_sort(kw, n)
        # The one dynamic-shape sync point: only the group COUNT crosses.
        g = int(sync_guard.scalar(n_groups, "aggregate.groups"))
        if g == 0:
            timeline.kernel_end("aggregate", t0, perm)
            return (np.empty(0, np.int32), np.empty(0, np.int32),
                    [np.empty(0) for _ in ops])
        capacity = round_up_pow2(g)
        out = _segment_reduce(perm, boundaries, n, vc,
                              ops=tuple(ops), capacity=capacity)
    timeline.kernel_end("aggregate", t0, out)
    first_rows = sync_guard.pull(out[0], "aggregate.first_rows")[:g]
    counts = sync_guard.pull(out[1], "aggregate.counts")[:g]
    results = [sync_guard.pull(r, "aggregate.results")[:g]
               for r in out[2:]]
    return first_rows, counts, results
