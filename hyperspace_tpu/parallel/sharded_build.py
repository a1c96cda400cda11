"""Mesh-sharded fused route+partition: the external build's per-chunk
pass, scaled horizontally.

Single device, one spill chunk runs ``ops/hash.route_partition`` — hash,
then one stable lexsort by (bucket, keys).  Over a mesh the same chunk
becomes: rows data-parallel over the ``shard`` axis → per-device hash →
ONE ``lax.all_to_all`` delivering every row to its owning device (device
``d`` OWNS every bucket with ``bucket_id % n_devices == d`` — the
embarrassingly-parallel ownership ROADMAP item 1 names) → per-device
stable lexsort of the owned rows → the HOST GATHER SEAM: one attributed
``sync_guard.pull`` per device per chunk, after which a host counting
merge by bucket reassembles the global ``(bucket_ids, perm)``.

The result is BIT-IDENTICAL to ``route_partition_np`` (and therefore to
the single-device kernel): bucket assignment shares ``_bucket_ids_impl``,
each device's sort keys on (validity, bucket, order words, GLOBAL row
id) exactly like the flat shuffle (``sort_received``), and a bucket
lives on exactly ONE device — so a stable host sort by bucket over the
concatenated per-device streams reproduces the global
(bucket, keys, original row) order with no cross-device tie to break.
Layout can never depend on how many devices routed the chunk, which is
what lets ``actions/create._BucketSpill`` feed the per-device runs
straight into the streaming bucket-group finalize unchanged.

Inputs are placed under ``NamedSharding`` by the rule-driven shard fns
(``parallel/mesh.match_partition_rules`` + ``make_shard_and_gather_fns``)
— placement policy lives in the rule table, not here.
"""

from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map as _shard_map

from hyperspace_tpu.execution import sync_guard
from hyperspace_tpu.io.columnar import join_words64
from hyperspace_tpu.ops.hash import _bucket_ids_impl, use_pallas
from hyperspace_tpu.parallel.mesh import (
    SHARD_AXIS,
    make_shard_and_gather_fns,
    match_partition_rules,
)
from hyperspace_tpu.parallel.shuffle import (
    make_row_records,
    marshal_shuffle_inputs,
    scatter_to_buffer,
    sort_received,
)


def bucket_group_bounds(num_buckets: int, groups: int) -> list:
    """Contiguous bucket-range cuts shared by every ownership layer:
    group (or host) ``g`` owns buckets ``bounds[g] <= b < bounds[g+1]``.
    ``actions/create._BucketSpill`` cuts its spill/finalize groups with
    this, and ``parallel/multihost_build`` claims the SAME ranges
    cross-host — one contract, so a group finalized on any host is the
    byte-identical unit a single process would have produced."""
    return [-(-g * num_buckets // groups) for g in range(groups + 1)]


def _route_body(num_buckets: int, num_devices: int, capacity: int,
                n_key_cols: int, n_order_cols: int, pallas: bool,
                hash_words, order_words, row_words, valid):
    """Per-device body under shard_map.  All inputs are the LOCAL shard:
    hash_words (L, 2K), order_words (L, 2K'), row_words (L, 2),
    valid (L,) int32.  Ownership is MOD, not range: dest = bucket %
    num_devices."""
    word_cols = tuple(hash_words[:, 2 * k:2 * k + 2]
                      for k in range(n_key_cols))
    bucket = _bucket_ids_impl(word_cols, num_buckets, pallas)
    dest = bucket % jnp.int32(num_devices)
    dest = jnp.where(valid.astype(bool), dest, num_devices)  # drop padding
    L = hash_words.shape[0]
    payload = jnp.zeros((L, 0), jnp.uint32)
    record = make_row_records(hash_words, order_words, row_words, payload,
                              bucket)
    send, overflow = scatter_to_buffer(record, dest, num_devices, capacity)
    recv = jax.lax.all_to_all(send, SHARD_AXIS, split_axis=0, concat_axis=0,
                              tiled=True)
    out, count = sort_received(recv, n_order_cols)
    return out, count[None], overflow[None]


@functools.partial(
    jax.jit,
    static_argnames=("num_buckets", "num_devices", "capacity", "n_key_cols",
                     "n_order_cols", "mesh", "pallas"))
def _route_program(hash_words, order_words, row_words, valid, *,
                   num_buckets, num_devices, capacity, n_key_cols,
                   n_order_cols, mesh, pallas):
    body = functools.partial(_route_body, num_buckets, num_devices,
                             capacity, n_key_cols, n_order_cols, pallas)
    spec = P(SHARD_AXIS)
    return _shard_map(
        body, mesh=mesh,
        in_specs=(spec, spec, spec, spec),
        out_specs=(spec, spec, spec),
        # The pallas hash kernel's pallas_call cannot type its output's
        # varying mesh axes; every output here is sharded on the one axis.
        check_vma=False,
    )(hash_words, order_words, row_words, valid)


def mesh_route_partition(
    word_cols: Sequence[np.ndarray],
    order_words: Sequence[np.ndarray],
    num_buckets: int,
    mesh,
    pad_to: int = 0,
    slack: float = 1.5,
) -> Tuple[np.ndarray, np.ndarray]:
    """Sharded fused route+partition for one spill chunk over ``mesh``.

    Same contract as ``ops.hash.route_partition`` / ``route_partition_np``
    — ``(bucket_ids, perm)`` host int32 arrays, ``perm`` ordering the
    chunk's rows by (bucket, *keys) with original-row tie order, sorted
    within bucket when ``order_words`` is non-empty, grouped-only
    otherwise — and bit-identical output (tests/test_parallel_mesh.py
    holds it to that).  ``pad_to`` quantizes the per-device shard length
    so chunks of different sizes share one compiled program.
    """
    from hyperspace_tpu.telemetry import metrics, timeline
    from hyperspace_tpu.telemetry.trace import span
    from hyperspace_tpu.utils.xla_cache import ensure_persistent_xla_cache

    ensure_persistent_xla_cache()
    n = int(word_cols[0].shape[0])
    n_devices = int(mesh.devices.size)
    if n == 0:
        return np.empty(0, np.int32), np.empty(0, np.int32)
    n_key_cols = len(word_cols)
    n_order_cols = len(order_words)
    hw, ow, rw, _pl, valid, local = marshal_shuffle_inputs(
        word_cols, order_words if n_order_cols
        else [np.zeros((n, 0), np.uint32)],
        None, n_devices, pad_to)
    if not n_order_cols:
        ow = np.zeros((hw.shape[0], 0), np.uint32)

    with span("exec.mesh.route", devices=n_devices, rows=n):
        # Rule-driven placement: the table, not this call site, owns the
        # specs; the gather fns are the attributed host seam for the
        # whole-array outputs (per-device shards pull individually below).
        in_names = ("hash_words", "order_words", "row_words", "valid")
        specs = match_partition_rules(in_names + ("counts",))
        shard_fns, gather_fns = make_shard_and_gather_fns(
            mesh, specs, site="mesh.route")
        arrays = dict(zip(in_names, (hw, ow, rw, valid)))
        sharded = {k: shard_fns[k](v) for k, v in arrays.items()}

        capacity = max(16, int(-(-local * slack // n_devices)))
        capacity = min(local, -(-capacity // 8) * 8)
        t0 = timeline.kernel_begin()
        if t0 is not None:
            timeline.record_transfer("h2d", sum(
                int(a.nbytes) for a in arrays.values()))
        while True:
            out, counts, overflow = _route_program(
                sharded["hash_words"], sharded["order_words"],
                sharded["row_words"], sharded["valid"],
                num_buckets=num_buckets, num_devices=n_devices,
                capacity=capacity, n_key_cols=n_key_cols,
                n_order_cols=n_order_cols, mesh=mesh, pallas=use_pallas())
            overflow_total = int(sync_guard.scalar(
                jnp.sum(overflow), "mesh.route.overflow"))
            if overflow_total == 0:
                break
            if capacity >= local:  # cannot grow further; unreachable
                raise RuntimeError(
                    "mesh_route_partition: capacity overflow at maximum")
            capacity = min(local, capacity * 2)
        timeline.kernel_end("mesh_route", t0, out,
                            devices=list(mesh.devices.flat))
        counts_np = gather_fns["counts"](counts).reshape(-1)
        # THE host gather seam: one attributed pull per device per chunk,
        # each pulling only that device's resident shard (no cross-device
        # re-layout before the d2h hop).
        rows_per_device = n_devices * capacity
        by_start = {
            (s.index[0].start or 0): s.data
            for s in out.addressable_shards}
        bucket_parts, rowid_parts = [], []
        pulls = 0
        for d in range(n_devices):
            shard = by_start.get(d * rows_per_device)
            if shard is None:  # non-addressable (multi-host): skip ours
                continue
            rows = sync_guard.pull(
                shard, f"mesh.route.gather.d{d}")[:int(counts_np[d])]
            pulls += 1
            bucket_parts.append(rows[:, 1].astype(np.int32))
            rowid_parts.append(
                join_words64(rows[:, 2], rows[:, 3]).astype(np.int64))
        metrics.inc("exec.mesh.gather.pulls", pulls)
        metrics.inc("exec.mesh.route.chunks")
        metrics.set_gauge("exec.mesh.devices", n_devices)

    # Host counting merge: a bucket lives on exactly one device, so a
    # STABLE sort by bucket over the device-order concatenation is the
    # full global (bucket, keys, original row) order.
    bucket_all = np.concatenate(bucket_parts) if bucket_parts \
        else np.empty(0, np.int32)
    rowid_all = np.concatenate(rowid_parts) if rowid_parts \
        else np.empty(0, np.int64)
    order = np.argsort(bucket_all, kind="stable")
    perm = rowid_all[order].astype(np.int32)
    buckets_sorted = bucket_all[order]
    bucket_ids = np.empty(n, dtype=np.int32)
    bucket_ids[perm] = buckets_sorted
    return bucket_ids, perm
