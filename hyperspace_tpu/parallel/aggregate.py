"""Mesh-sharded grouped aggregation: bucket-owned groups, no merge pass.

The single-device kernel (ops/aggregate.py) lexsorts rows by group key
and segment-reduces.  The sharded form partitions ROWS BY GROUP-KEY
BUCKET — device ``d`` owns every group whose key hashes to a bucket with
``bucket % n_devices == d`` (the same mod ownership as the sharded build
route, computed with the bit-identical host hash mirror
``ops.hash.bucket_ids_np``) — so a group's rows land WHOLLY on one
device.  That is the property that makes the distributed aggregate
exact: every reduction (sum/min/max/mean/count) runs over the complete
group on its owner, there is no partial-aggregate merge tree, and mean
is an ordinary per-group division, not a weighted recombination.

Each device then runs the SAME ``_group_sort`` + ``_segment_reduce``
programs as the single-device kernel under ``shard_map`` (two host syncs:
per-device group counts, then the capacity-padded reduction), and the
host gather seam pulls per-group outputs through attributed
``sync_guard.pull`` sites.  Groups come back in ascending key order —
the single-device kernel's contract — via one host lexsort over the
group keys' order words.

Partitioning keeps each device's rows in ORIGINAL order, and the
per-device stable sort keeps each group's rows in original order — the
same per-group accumulation sequence as the single-device kernel, so
integer results are bit-equal and float results differ at most by the
platform's reduction-order latitude inside one segment.
"""

from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map as _shard_map

from hyperspace_tpu.ops.aggregate import AGG_OPS, _group_sort, _segment_reduce
from hyperspace_tpu.ops.hash import bucket_ids_np
from hyperspace_tpu.parallel.mesh import (
    SHARD_AXIS,
    make_shard_and_gather_fns,
    match_partition_rules,
)
from jax import enable_x64 as _enable_x64
from hyperspace_tpu.utils.shapes import round_up_pow2


@functools.partial(jax.jit, static_argnames=("n_key_cols", "mesh"))
def _count_program(key_words, n_valid, *, n_key_cols, mesh):
    def body(kw, nv):
        cols = tuple(kw[:, 2 * k:2 * k + 2] for k in range(n_key_cols))
        _perm, _boundaries, n_groups = _group_sort(cols, nv[0])
        return n_groups[None]

    spec = P(SHARD_AXIS)
    return _shard_map(body, mesh=mesh, in_specs=(spec, spec),
                      out_specs=spec)(key_words, n_valid)


@functools.partial(
    jax.jit, static_argnames=("n_key_cols", "ops", "capacity", "mesh"))
def _reduce_program(key_words, n_valid, value_cols, *, n_key_cols, ops,
                    capacity, mesh):
    def body(kw, nv, vc):
        cols = tuple(kw[:, 2 * k:2 * k + 2] for k in range(n_key_cols))
        perm, boundaries, n_groups = _group_sort(cols, nv[0])
        out = _segment_reduce(perm, boundaries, nv[0], vc,
                              ops=ops, capacity=capacity)
        return out + (n_groups[None],)

    spec = P(SHARD_AXIS)
    n_out = 2 + len(ops) + 1
    return _shard_map(
        body, mesh=mesh,
        in_specs=(spec, spec, tuple(spec for _ in value_cols)),
        out_specs=tuple(spec for _ in range(n_out)),
    )(key_words, n_valid, value_cols)


def _scatter_to_shards(col: np.ndarray, positions: np.ndarray,
                       total: int) -> np.ndarray:
    out = np.zeros((total,) + col.shape[1:], dtype=col.dtype)
    out[positions] = col
    return out


def mesh_grouped_aggregate(
    key_words: Sequence[np.ndarray],
    value_cols: Sequence[np.ndarray],
    ops: Sequence[str],
    mesh,
    pad_to: int = 0,
) -> Tuple[np.ndarray, np.ndarray, List[np.ndarray]]:
    """Sharded grouped aggregation over ``mesh``.

    Same contract as ``ops.aggregate.grouped_aggregate`` — per group in
    ascending key order: the index of its first row in the ORIGINAL
    order, the row count, and one result array per aggregate.  Inputs
    must be HOST arrays (device-resident columns keep the single-device
    kernel; sharded placement is its own layout).
    """
    from hyperspace_tpu.telemetry import metrics, timeline
    from hyperspace_tpu.telemetry.trace import span
    from hyperspace_tpu.utils.xla_cache import ensure_persistent_xla_cache

    for op in ops:
        if op not in AGG_OPS:
            raise ValueError(f"Unsupported device aggregate {op!r}")
    ensure_persistent_xla_cache()
    key_words = [np.asarray(w, dtype=np.uint32) for w in key_words]
    value_cols = [np.asarray(v) for v in value_cols]
    n = int(key_words[0].shape[0])
    n_devices = int(mesh.devices.size)
    if n == 0:
        return (np.empty(0, np.int32), np.empty(0, np.int32),
                [np.empty(0) for _ in ops])

    # Mod bucket ownership over the key words (bit-identical host hash
    # mirror): a group's rows all carry the same words, so they share an
    # owner and no group ever splits across devices.
    owner = bucket_ids_np(key_words, n_devices)
    part_perm = np.argsort(owner, kind="stable")
    dev_counts = np.bincount(owner, minlength=n_devices).astype(np.int32)
    lmax = max(int(dev_counts.max()), 1)
    if pad_to and pad_to > 0:
        quantum = max(1, -(-int(pad_to) // n_devices))
        lmax = -(-lmax // quantum) * quantum
    total = lmax * n_devices
    owner_sorted = owner[part_perm]
    starts = np.searchsorted(owner_sorted, np.arange(n_devices), "left")
    rank = np.arange(n, dtype=np.int64) - starts[owner_sorted]
    positions = owner_sorted.astype(np.int64) * lmax + rank
    offsets = starts  # original-row lookup per device below

    with span("exec.mesh.agg", devices=n_devices, rows=n):
        names = ("key_words", "value_cols", "n_valid", "counts")
        specs = match_partition_rules(names)
        shard_fns, gather_fns = make_shard_and_gather_fns(
            mesh, specs, site="mesh.agg")
        kw_plane = _scatter_to_shards(
            np.concatenate(key_words, axis=1)[part_perm], positions, total)
        kw_sharded = shard_fns["key_words"](kw_plane)
        nv_sharded = shard_fns["n_valid"](dev_counts)
        t0 = timeline.kernel_begin()
        if t0 is not None:
            timeline.record_transfer("h2d", int(kw_plane.nbytes) + sum(
                int(v.nbytes) for v in value_cols))
        counts_per_dev = gather_fns["counts"](_count_program(
            kw_sharded, nv_sharded, n_key_cols=len(key_words),
            mesh=mesh)).reshape(-1)
        g_max = int(counts_per_dev.max()) if counts_per_dev.size else 0
        g_total = int(counts_per_dev.sum())
        if g_total == 0:
            timeline.kernel_end("mesh_aggregate", t0, kw_sharded,
                                devices=list(mesh.devices.flat))
            return (np.empty(0, np.int32), np.empty(0, np.int32),
                    [np.empty(0) for _ in ops])
        capacity = round_up_pow2(g_max)
        with _enable_x64():
            # x64 scope: int64/float64 value planes must keep full width
            # through the shard placement AND the reduction program.
            vc_sharded = tuple(
                shard_fns["value_cols"](
                    _scatter_to_shards(v[part_perm], positions, total))
                for v in value_cols)
            out = _reduce_program(
                kw_sharded, nv_sharded, vc_sharded,
                n_key_cols=len(key_words), ops=tuple(ops),
                capacity=capacity, mesh=mesh)
        timeline.kernel_end("mesh_aggregate", t0, out,
                            devices=list(mesh.devices.flat))
        # Host gather seam: one attributed pull per output plane.
        from hyperspace_tpu.execution import sync_guard

        first_local = sync_guard.pull(out[0], "mesh.agg.first_rows")
        counts_g = sync_guard.pull(out[1], "mesh.agg.counts")
        results_g = [sync_guard.pull(r, "mesh.agg.results")
                     for r in out[2:-1]]
        n_groups = sync_guard.pull(out[-1], "mesh.agg.groups").reshape(-1)
        metrics.set_gauge("exec.mesh.devices", n_devices)
        metrics.inc("exec.mesh.gather.pulls", 3 + len(results_g))

    # Per-device valid prefixes -> original row ids -> one global
    # ascending-key order (the single-device kernel's output contract).
    first_parts, count_parts = [], []
    result_parts: List[List[np.ndarray]] = [[] for _ in ops]
    for d in range(n_devices):
        g_d = int(n_groups[d])
        if g_d == 0:
            continue
        lo, hi = d * capacity, d * capacity + g_d
        local_first = first_local[lo:hi].astype(np.int64)
        first_parts.append(part_perm[offsets[d] + local_first])
        count_parts.append(counts_g[lo:hi])
        for i in range(len(ops)):
            result_parts[i].append(results_g[i][lo:hi])
    first_rows = np.concatenate(first_parts)
    counts = np.concatenate(count_parts)
    results = [np.concatenate(parts) for parts in result_parts]
    sort_keys = []
    for w in reversed(key_words):
        fw = w[first_rows]
        sort_keys.append(fw[:, 1])
        sort_keys.append(fw[:, 0])
    order = np.lexsort(tuple(sort_keys))
    return (first_rows[order].astype(np.int32), counts[order],
            [r[order] for r in results])
