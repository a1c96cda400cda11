"""Co-partitioned distributed equi-join: zero-communication by construction.

The point of the covering index's bucket layout (JoinIndexRule.scala:36-50):
when both join sides are bucketed by the join key with the same bucket
count, matching keys are guaranteed co-located, so the join runs per-bucket
with NO shuffle.  On the mesh the same invariant holds per-device — buckets
are range-partitioned identically on both sides (parallel/shuffle.py), so
``shard_map`` runs a purely local sorted join on every device and the only
"collective" is the host gathering match counts.

Like the single-chip join (ops/join.py) this is two-phase: count matches
(static-shape program #1), then materialize pairs with the max per-device
count as the static output capacity (program #2).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map as _shard_map

from hyperspace_tpu.execution import sync_guard
from hyperspace_tpu.ops.hash import lexsort32
from hyperspace_tpu.parallel.mesh import SHARD_AXIS
from jax import enable_x64 as _enable_x64
from hyperspace_tpu.utils.shapes import round_up_pow2


def _ranges_local(lk, lvalid, rk, rvalid):
    """Per-device match ranges of left keys in the sorted right keys.

    Padding slots are excluded by VALIDITY, not by a sentinel value — a
    sentinel (inf/intmax) would collide with real keys of that value and a
    valid NaN key would sort past it, letting padding slots leak into the
    match window.  Valid rows are lexsorted first; the tail is overwritten
    with the largest valid key so the array stays sorted, and both range
    ends are clamped to the valid count."""
    inv = jnp.uint32(1) - rvalid.astype(jnp.uint32)
    # Primary: valid rows first.
    r_order = lexsort32((rk, inv)) \
        if not jnp.issubdtype(rk.dtype, jnp.floating) \
        else jnp.lexsort((rk, inv))
    rk_ord = rk[r_order]
    n_r = jnp.sum(rvalid, dtype=jnp.int32)
    max_valid = rk_ord[jnp.maximum(n_r - 1, 0)]
    positions = jnp.arange(rk.shape[0], dtype=jnp.int32)
    rk_sorted = jnp.where(positions < n_r, rk_ord, max_valid)
    lo = jnp.searchsorted(rk_sorted, lk, side="left").astype(jnp.int32)
    hi = jnp.searchsorted(rk_sorted, lk, side="right").astype(jnp.int32)
    lo = jnp.minimum(lo, n_r)
    hi = jnp.where(lvalid.astype(bool), jnp.minimum(hi, n_r), lo)
    return lo, hi, r_order


@functools.partial(jax.jit, static_argnames=("mesh",))
def _count_program(lk, lvalid, rk, rvalid, *, mesh):
    def body(lk, lvalid, rk, rvalid):
        lo, hi, _ = _ranges_local(lk, lvalid, rk, rvalid)
        return jnp.sum(hi - lo, dtype=jnp.int32)[None]

    spec = P(SHARD_AXIS)
    return _shard_map(body, mesh=mesh, in_specs=(spec,) * 4,
                      out_specs=spec)(lk, lvalid, rk, rvalid)


@functools.partial(jax.jit, static_argnames=("capacity", "mesh"))
def _materialize_program(lk, lvalid, rk, rvalid, *, capacity, mesh):
    def body(lk, lvalid, rk, rvalid):
        lo, hi, r_order = _ranges_local(lk, lvalid, rk, rvalid)
        counts = hi - lo
        total = jnp.sum(counts, dtype=jnp.int32)
        left_idx = jnp.repeat(jnp.arange(lo.shape[0], dtype=jnp.int32), counts,
                              total_repeat_length=capacity)
        starts = jnp.cumsum(counts) - counts
        within = jnp.arange(capacity, dtype=jnp.int32) - jnp.repeat(
            starts.astype(jnp.int32), counts, total_repeat_length=capacity)
        right_pos = lo[left_idx] + within
        right_idx = r_order[jnp.clip(right_pos, 0, r_order.shape[0] - 1)]
        return (left_idx, right_idx.astype(jnp.int32), total[None])

    spec = P(SHARD_AXIS)
    return _shard_map(body, mesh=mesh, in_specs=(spec,) * 4,
                      out_specs=(spec, spec, spec))(lk, lvalid, rk, rvalid)


def copartitioned_join(
    left_keys: np.ndarray, right_keys: np.ndarray, mesh,
) -> Tuple[np.ndarray, np.ndarray]:
    """Inner equi-join of DENSE co-partitioned key shards.

    ``left_keys``/``right_keys`` are (D, L) / (D, R) arrays: row i of each
    holds device i's shard and EVERY slot is a real key (all slots join).
    For ragged shards with trailing padding use ``copartitioned_join_ragged``,
    which tracks per-slot validity.  Returns GLOBAL (left, right) index
    pairs into the flattened (D*L,) / (D*R,) arrays.
    """
    D, L = left_keys.shape
    _, R = right_keys.shape
    lk = np.ascontiguousarray(left_keys).reshape(D * L)
    rk = np.ascontiguousarray(right_keys).reshape(D * R)
    lvalid = np.ones(D * L, np.int32)
    rvalid = np.ones(D * R, np.int32)
    return _copartitioned_join_padded(lk, lvalid, rk, rvalid, D, L, R, mesh)


def copartitioned_join_ragged(
    left_shards, right_shards, mesh,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Join ragged per-device key shards (lists of 1-D arrays, one per mesh
    device).  Returns (device_ids, left_local, right_local): for each match,
    the owning device and the row positions within that device's input
    shards.  Keys on different devices never match — that's the
    co-partitioning invariant the bucket layout guarantees."""
    D = len(left_shards)
    Lmax = max(max((len(v) for v in left_shards), default=0), 1)
    Rmax = max(max((len(v) for v in right_shards), default=0), 1)
    lk = np.zeros((D, Lmax), dtype=np.asarray(left_shards[0]).dtype)
    rk = np.zeros((D, Rmax), dtype=np.asarray(right_shards[0]).dtype)
    lvalid = np.zeros((D, Lmax), np.int32)
    rvalid = np.zeros((D, Rmax), np.int32)
    for i in range(D):
        lk[i, :len(left_shards[i])] = left_shards[i]
        lvalid[i, :len(left_shards[i])] = 1
        rk[i, :len(right_shards[i])] = right_shards[i]
        rvalid[i, :len(right_shards[i])] = 1
    li, ri = _copartitioned_join_padded(
        lk.reshape(-1), lvalid.reshape(-1), rk.reshape(-1), rvalid.reshape(-1),
        D, Lmax, Rmax, mesh)
    return li // Lmax, li % Lmax, ri % Rmax


def _copartitioned_join_padded(lk, lvalid, rk, rvalid, D, L, R, mesh):
    from hyperspace_tpu.telemetry import timeline

    # Scoped x64: int64 join keys keep full width (see ops/join.py).
    t0 = timeline.kernel_begin()
    with _enable_x64():
        counts = sync_guard.pull(
            _count_program(lk, lvalid, rk, rvalid, mesh=mesh),
            "mesh_join.counts")
        capacity = int(counts.max()) if counts.size else 0
        if capacity == 0:
            timeline.kernel_end("mesh_join", t0, None,
                                devices=list(mesh.devices.flat))
            return np.empty(0, np.int64), np.empty(0, np.int64)
        capacity = round_up_pow2(capacity)
        li, ri, totals = _materialize_program(
            lk, lvalid, rk, rvalid, capacity=capacity, mesh=mesh)
    timeline.kernel_end("mesh_join", t0, (li, ri, totals),
                        devices=list(mesh.devices.flat))
    li = sync_guard.pull(li, "mesh_join.li").reshape(D, capacity)
    ri = sync_guard.pull(ri, "mesh_join.ri").reshape(D, capacity)
    totals = sync_guard.pull(totals, "mesh_join.totals").reshape(D)
    out_l, out_r = [], []
    for d in range(D):
        t = int(totals[d])
        out_l.append(li[d, :t].astype(np.int64) + d * L)
        out_r.append(ri[d, :t].astype(np.int64) + d * R)
    return np.concatenate(out_l), np.concatenate(out_r)
