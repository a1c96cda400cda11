"""Device-side bucket/sort permutation kernel — the heart of the index build.

Reference contract: ``repartition(numBuckets, cols)`` + sort-within-bucket
(actions/CreateActionBase.scala:124-142 and the bucketed writer
DataFrameWriterExtensions.scala:49-67).  Spark does this as a cluster-wide
hash shuffle followed by per-task sorts; on TPU the whole thing is ONE fused
XLA program: hash → lexicographic sort by (bucket, key columns) → output a
gather permutation.  The host then applies the permutation to the arrow
table (zero-copy take) and slices per-bucket runs for the writer.

All kernel inputs are uint32 words (hash words from
``hyperspace_tpu.io.columnar.to_hash_words``; monotone order words from
``to_order_words``): the kernel is dtype-monomorphic AND pure 32-bit, so it
never leans on x64 int64 emulation — TPU's VPU lanes are 32-bit native.
"""

from __future__ import annotations

from functools import partial
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from hyperspace_tpu.ops.hash import _route_sort_impl, use_pallas

# One bucket-assignment-and-sort implementation for the monolithic build,
# the external build's per-chunk route pass, and the query paths —
# duplicating it risks the programs silently diverging, which corrupts
# the durable on-disk bucket layout.  The shared impl lives with the
# hash kernel (ops/hash._route_sort_impl); ``n_valid`` is a TRACED
# scalar there, so row-count changes never retrace, and a Z-order build
# passes ONE precomputed Morton-word column (the host ranks in
# io/parquet.zorder_codes_host define the layout AND the file-split
# keys, so the device never re-ranks).  One stacked (2, n) output = ONE
# device->host transfer for both arrays.
_bucket_sort_impl = _route_sort_impl


def _pad_rows(arr, capacity: int):
    import numpy as np

    if isinstance(arr, jax.Array):
        # HBM-resident input (execution/device_cache.py): pad on device —
        # np.asarray would pull the whole array back to host.
        if arr.shape[0] == capacity:
            return arr
        widths = [(0, capacity - arr.shape[0])] + [(0, 0)] * (arr.ndim - 1)
        return jnp.pad(arr, widths)
    arr = np.asarray(arr)
    if arr.shape[0] == capacity:
        return arr
    pad = np.zeros((capacity - arr.shape[0],) + arr.shape[1:], dtype=arr.dtype)
    return np.concatenate([arr, pad], axis=0)


def bucket_sort_permutation(
    word_cols: Sequence[jnp.ndarray],
    order_words: Sequence[jnp.ndarray],
    num_buckets: int,
    pad_to: int = 0,
) -> "Tuple[np.ndarray, np.ndarray]":
    """Fused hash + sort kernel.

    Args:
      word_cols: per key column (n, 2) uint32 hash words.
      order_words: per key column (n, 2) uint32 monotone order words.
      num_buckets: static bucket count.
      pad_to: when > 0, pad the row dimension up to the next multiple so
        every build shares one compiled program per (capacity, key count) —
        without this each distinct dataset size pays a fresh XLA compile
        (tens of seconds on a real chip).  The conf knob is
        ``device_batch_rows``.

    Returns:
      (bucket_ids int32 (n,), perm int32 (n,)) HOST numpy arrays (pulled in
      one transfer) where perm orders rows by (bucket, *key columns) —
      ready for ``write_bucketed``.

    On TPU the hash stage runs as the fused pallas kernel; the choice is a
    static jit arg so env flips retrace (see ``ops.hash.use_pallas``).
    """
    from hyperspace_tpu.execution import sync_guard
    from hyperspace_tpu.telemetry import timeline
    from hyperspace_tpu.utils.xla_cache import ensure_persistent_xla_cache

    ensure_persistent_xla_cache()
    n = int(word_cols[0].shape[0])
    if pad_to and pad_to > 0:
        capacity = -(-max(n, 1) // pad_to) * pad_to
        word_cols = [_pad_rows(w, capacity) for w in word_cols]
        order_words = [_pad_rows(w, capacity) for w in order_words]
    t0 = timeline.kernel_begin()
    if t0 is not None:
        timeline.record_transfer("h2d", sum(
            int(getattr(a, "nbytes", 0))
            for a in (*word_cols, *order_words)
            if not isinstance(a, jax.Array)))
    out = _bucket_sort_impl(
        tuple(word_cols), tuple(order_words), n, num_buckets, use_pallas())
    timeline.kernel_end("bucket_sort", t0, out)
    stacked = sync_guard.pull(out, "sort.permutation")
    return stacked[0, :n], stacked[1, :n]


def bucket_sort_permutation_np(
    word_cols,
    order_words,
    num_buckets: int,
) -> "Tuple[np.ndarray, np.ndarray]":
    """Bit-identical HOST mirror of ``bucket_sort_permutation`` (same cost
    model as the filter/join host mirrors: below
    ``device_build_min_rows`` the device round trip's transfer + compile
    latency exceeds a numpy lexsort).  Identity holds
    because bucket assignment shares ``bucket_ids_np`` (parity-tested
    against the device kernel) and both sorts are stable lexsorts over the
    SAME (bucket, order-word) key sequence — padding in the device path
    parks only pad rows at the tail, never reordering real ties.  The
    host mirror IS the external build's route mirror
    (``ops.hash.route_partition_np``): one implementation, one ordering."""
    from hyperspace_tpu.ops.hash import route_partition_np

    return route_partition_np(word_cols, order_words, num_buckets)


@partial(jax.jit, static_argnames=("num_buckets",))
def _bucket_counts_xla(buckets: jnp.ndarray, num_buckets: int) -> jnp.ndarray:
    return jax.ops.segment_sum(
        jnp.ones_like(buckets, dtype=jnp.int32), buckets, num_segments=num_buckets)


def bucket_counts(buckets: jnp.ndarray, num_buckets: int) -> jnp.ndarray:
    """Rows per bucket.  On TPU: the pallas one-hot histogram kernel
    (ops/pallas_kernels.py) — VPU compares instead of segment_sum's
    serialized scatter-add; elsewhere one XLA segment-sum over HBM."""
    if use_pallas():
        from hyperspace_tpu.ops.pallas_kernels import bucket_histogram

        return bucket_histogram(buckets, num_buckets)
    return _bucket_counts_xla(buckets, num_buckets)
