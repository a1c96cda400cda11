"""Distributed bucket shuffle: capacity-padded all_to_all over the mesh.

This is the TPU-native re-expression of the reference's cluster-wide hash
shuffle (``repartition(numBuckets, indexedCols)``,
actions/CreateActionBase.scala:131-132; Spark moves rows executor→executor
over TCP).  Here every device:

  1. hashes its local rows to buckets (same uint32 kernel as single-chip,
     ops/hash.py) and maps each bucket to its owning device — buckets are
     RANGE-partitioned over the mesh so each device emits a contiguous,
     sorted run of buckets for the writer,
  2. scatters rows into a fixed-capacity send buffer laid out as
     ``(n_devices * capacity, words)`` — the MoE-dispatch pattern: XLA needs
     static shapes, so per-destination space is padded to ``capacity`` and
     overflow is *counted* rather than sent (the host retries with doubled
     capacity — see ``bucket_shuffle``),
  3. exchanges buffers with ONE ``lax.all_to_all`` riding ICI,
  4. lexsorts its received rows by (bucket, order words) — after which every
     device holds its buckets' rows fully sorted, ready for the bucketed
     Parquet writer.

Everything on device is uint32 words (hash words, monotone order words,
row-id words), so one compiled program serves any key schema — and no x64
emulation is involved on TPU.
"""

from __future__ import annotations

import functools
from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map as _shard_map

from hyperspace_tpu.execution import sync_guard
from hyperspace_tpu.io.columnar import join_words64, split_words64
from hyperspace_tpu.ops.hash import _bucket_ids_impl, lexsort32, use_pallas
from hyperspace_tpu.parallel.mesh import SHARD_AXIS


class ShuffleResult(NamedTuple):
    """Host-side view of a completed shuffle.

    ``perm``/``buckets_sorted`` follow the same contract as the single-chip
    ``bucket_sort_permutation``: ``perm`` lists original row indices in
    (bucket, key) order; ``buckets_sorted[i]`` is the bucket of row
    ``perm[i]``.  ``device_row_counts[d]`` says how many of those rows were
    produced (and are held) by mesh device ``d`` — the writer uses it to
    emit per-device file groups without re-partitioning.
    """

    perm: np.ndarray
    buckets_sorted: np.ndarray
    device_row_counts: np.ndarray
    capacity: int


def scatter_to_buffer(record, dest, n_dest: int, capacity: int):
    """Pack ``record`` rows into an ``(n_dest * capacity)`` send buffer by
    destination (the MoE-dispatch pattern: static shapes, overflow COUNTED
    rather than sent).  ``dest == n_dest`` drops the row (padding).
    Shared by the flat and hierarchical shuffle kernels — both must pack
    identically for their outputs to be bit-identical."""
    n = record.shape[0]
    order = jnp.argsort(dest, stable=True)
    sorted_dest = dest[order]
    rank = jnp.arange(n, dtype=jnp.int32) - jnp.searchsorted(
        sorted_dest, sorted_dest, side="left").astype(jnp.int32)
    in_window = (rank < capacity) & (sorted_dest < n_dest)
    overflow = jnp.sum((rank >= capacity) & (sorted_dest < n_dest),
                       dtype=jnp.int32)
    slot = jnp.where(in_window, sorted_dest * capacity + rank,
                     n_dest * capacity)
    send = jnp.zeros((n_dest * capacity, record.shape[1]), jnp.uint32)
    send = send.at[slot].set(record[order], mode="drop")
    return send, overflow


def make_row_records(hash_words, order_words, row_words, payload, bucket):
    """The on-wire row record both kernels route:
    [flag, bucket, row_hi, row_lo, order words..., payload...]."""
    L = hash_words.shape[0]
    return jnp.concatenate([
        jnp.ones((L, 1), jnp.uint32),
        bucket.astype(jnp.uint32)[:, None],
        row_words,
        order_words,
        payload,
    ], axis=1)


def sort_received(recv, n_key_cols: int):
    """Per-device final order: valid first, then (bucket, order words),
    with the GLOBAL ROW ID as the final tiebreak — arrival order in the
    receive buffer depends on the traffic pattern, so without it equal
    keys would order differently across topologies (flat vs hierarchical
    shuffle); with it, ties come out in original row order, matching the
    single-chip kernel's stable sort exactly.  Returns (sorted rows,
    valid count)."""
    flag = recv[:, 0]
    rbucket = recv[:, 1]
    keys: List[jnp.ndarray] = [recv[:, 3], recv[:, 2]]  # row lo, hi
    for k in reversed(range(n_key_cols)):
        keys.append(recv[:, 4 + 2 * k + 1])  # lo
        keys.append(recv[:, 4 + 2 * k])      # hi
    keys.append(rbucket)
    keys.append(jnp.uint32(1) - flag)        # primary: invalid rows last
    perm = lexsort32(keys)
    return recv[perm], jnp.sum(flag, dtype=jnp.int32)


def _route_kernel(num_buckets: int, num_devices: int, capacity: int,
                  n_key_cols: int, pallas: bool,
                  hash_words, order_words, row_words, payload, valid):
    """Per-device body run under shard_map.  All inputs are the LOCAL shard:
    hash_words (L, 2K), order_words (L, 2K), row_words (L, 2), payload
    (L, E), valid (L,) int32."""
    word_cols = tuple(hash_words[:, 2 * k:2 * k + 2] for k in range(n_key_cols))
    bucket = _bucket_ids_impl(word_cols, num_buckets, pallas)
    buckets_per_device = -(-num_buckets // num_devices)  # ceil
    dest = bucket // buckets_per_device
    dest = jnp.where(valid.astype(bool), dest, num_devices)  # sentinel: drop
    record = make_row_records(hash_words, order_words, row_words, payload,
                              bucket)
    send, overflow = scatter_to_buffer(record, dest, num_devices, capacity)
    recv = jax.lax.all_to_all(send, SHARD_AXIS, split_axis=0, concat_axis=0,
                              tiled=True)
    out, count = sort_received(recv, n_key_cols)
    return out, count[None], overflow[None]


@functools.partial(
    jax.jit,
    static_argnames=("num_buckets", "num_devices", "capacity", "n_key_cols",
                     "mesh", "pallas"))
def _shuffle_program(hash_words, order_words, row_words, payload, valid, *,
                     num_buckets, num_devices, capacity, n_key_cols, mesh,
                     pallas):
    # ``pallas`` is part of the jit cache key so HYPERSPACE_TPU_PALLAS flips
    # between calls retrace instead of silently reusing the old kernel path.
    body = functools.partial(_route_kernel, num_buckets, num_devices,
                             capacity, n_key_cols, pallas)
    spec = P(SHARD_AXIS)
    return _shard_map(
        body, mesh=mesh,
        in_specs=(spec, spec, spec, spec, spec),
        out_specs=(spec, spec, spec),
        # The pallas hash kernel's pallas_call cannot type its output's
        # varying mesh axes; every output here is sharded on the one axis.
        check_vma=False,
    )(hash_words, order_words, row_words, payload, valid)


def bucket_shuffle(
    hash_words: Sequence[np.ndarray],
    order_words: Sequence[np.ndarray],
    num_buckets: int,
    mesh,
    payload_words: Optional[np.ndarray] = None,
    capacity: Optional[int] = None,
    slack: float = 1.5,
    pad_local_to: int = 0,
) -> Tuple[ShuffleResult, Optional[np.ndarray]]:
    """Run the distributed shuffle for ``n`` global rows.

    Args:
      hash_words: per key column (n, 2) uint32 arrays (columnar.to_hash_words).
      order_words: per key column (n, 2) uint32 arrays (columnar.to_order_words).
      num_buckets: bucket count (range-partitioned over mesh devices).
      mesh: 1-D mesh from parallel.mesh.build_mesh.
      payload_words: optional (n, E) uint32 extra words routed with each row
        (numeric column data for all-device pipelines).
      capacity: per-(src,dst) row capacity; None = balanced estimate with
        ``slack`` headroom, doubled on overflow until the shuffle fits.
      pad_local_to: when > 0, round the per-device shard length up to the
        next multiple so builds of different dataset sizes share one
        compiled program (the ``valid`` mask drops the padding) — the same
        capacity-padding contract as the single-chip kernel's ``pad_to``.

    Returns:
      (ShuffleResult, routed_payload) — routed_payload is (n, E) uint32 in
      ``perm`` order (None when no payload was given).
    """
    from hyperspace_tpu.utils.xla_cache import ensure_persistent_xla_cache

    ensure_persistent_xla_cache()
    n = hash_words[0].shape[0]
    n_devices = mesh.devices.size
    if n == 0:
        # Zero-row build (empty source): nothing to route.
        return empty_shuffle_result(n_devices, payload_words)
    n_key_cols = len(hash_words)
    hw, ow, rw, pl, valid, local = marshal_shuffle_inputs(
        hash_words, order_words, payload_words, n_devices, pad_local_to)

    if capacity is None:
        capacity = max(16, int(-(-local * slack // n_devices)))
    capacity = min(local, -(-capacity // 8) * 8)  # align, never beyond local

    while True:
        out, counts, overflow = _shuffle_program(
            hw, ow, rw, pl, valid,
            num_buckets=num_buckets, num_devices=n_devices, capacity=capacity,
            n_key_cols=n_key_cols, mesh=mesh, pallas=use_pallas())
        overflow_total = int(sync_guard.scalar(
            jnp.sum(overflow), "shuffle.overflow"))
        if overflow_total == 0:
            break
        if capacity >= local:  # cannot grow further; should be unreachable
            raise RuntimeError("bucket_shuffle: capacity overflow at maximum")
        capacity = min(local, capacity * 2)

    counts = sync_guard.pull(counts, "shuffle.counts").reshape(-1)
    perm, buckets_sorted, routed_payload = unpack_shuffle_output(
        sync_guard.pull(out, "shuffle.routed"), counts,
        n_devices, n_devices * capacity,
        n_key_cols, payload_words is not None)
    result = ShuffleResult(perm=perm, buckets_sorted=buckets_sorted,
                           device_row_counts=counts, capacity=capacity)
    return result, routed_payload


def empty_shuffle_result(n_devices: int, payload_words):
    return ShuffleResult(
        perm=np.empty(0, np.int64),
        buckets_sorted=np.empty(0, np.int32),
        device_row_counts=np.zeros(n_devices, np.int32),
        capacity=0,
    ), (np.empty((0, payload_words.shape[1]), np.uint32)
        if payload_words is not None else None)


def marshal_shuffle_inputs(hash_words, order_words, payload_words,
                           n_devices: int, pad_local_to: int):
    """Host-side input marshalling shared by the flat and hierarchical
    shuffles: concatenated uint32 word planes, global row-id words, the
    padded validity mask, and the per-device shard length."""
    n = hash_words[0].shape[0]
    local = -(-n // n_devices)  # rows per device, ceil
    if pad_local_to and pad_local_to > 0:
        quantum = max(1, -(-pad_local_to // n_devices))
        local = -(-local // quantum) * quantum
    padded = local * n_devices

    def pad(a: np.ndarray) -> np.ndarray:
        if a.shape[0] == padded:
            return a
        width = ((0, padded - a.shape[0]),) + ((0, 0),) * (a.ndim - 1)
        return np.pad(a, width)

    hw = pad(np.concatenate([np.asarray(w, np.uint32)
                             for w in hash_words], axis=1))
    ow = pad(np.concatenate([np.asarray(w, np.uint32)
                             for w in order_words], axis=1))
    rw = split_words64(np.arange(padded, dtype=np.uint64))
    pl = pad(np.asarray(payload_words, np.uint32)) \
        if payload_words is not None else np.zeros((padded, 0), np.uint32)
    valid = pad(np.ones(n, dtype=np.int32))
    return hw, ow, rw, pl, valid, local


def unpack_shuffle_output(out, counts, n_devices: int, rows_per_device: int,
                          n_key_cols: int, has_payload: bool):
    """Host-side output unpacking shared by both shuffles: per-device
    valid prefixes concatenate into (perm, buckets_sorted, payload)."""
    per_dev = out.reshape(n_devices, rows_per_device, -1)
    perm_parts, bucket_parts, payload_parts = [], [], []
    for d in range(n_devices):
        c = int(counts[d])
        rows = per_dev[d, :c]
        perm_parts.append(join_words64(rows[:, 2], rows[:, 3]).astype(np.int64))
        bucket_parts.append(rows[:, 1].astype(np.int32))
        if has_payload:
            payload_parts.append(rows[:, 4 + 2 * n_key_cols:])
    perm = np.concatenate(perm_parts) if perm_parts else np.empty(0, np.int64)
    buckets_sorted = np.concatenate(bucket_parts) if bucket_parts else \
        np.empty(0, np.int32)
    payload = np.concatenate(payload_parts) if has_payload else None
    return perm, buckets_sorted, payload
