"""Device-side equi-join kernel over sorted keys.

Reference analog: the shuffle-free sort-merge join the covering indexes
enable (JoinIndexRule.scala:36-50).  Spark's SMJ streams row iterators; the
XLA-native formulation is vectorized:

  1. merge both sides' keys in one stable sort (the TPU's sort unit; on
     bucketed index data the input is already sorted),
  2. rank them there → the right side's sort permutation and each left
     row's match range [lo, hi) of right ranks — running counts over the
     merged order and a second sort back to row order, where a binary
     search would be a loop of dependent gathers,
  3. expand to output pairs: one more sort merges each left row's start
     with the output slots, so every slot finds its row by running sums.

Step 3 needs the total match count N as a static shape, so the kernel is
two-phase with one host sync in between (count → materialize) — the standard
XLA pattern for dynamic-size outputs.
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from hyperspace_tpu.execution import sync_guard
from jax import enable_x64 as _enable_x64
from hyperspace_tpu.utils.shapes import round_up_pow2


def _same_key(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """``a == b`` as ``lax.sort`` orders keys: -0.0 equals 0.0 and every
    NaN equals every NaN."""
    if jnp.issubdtype(a.dtype, jnp.floating):
        return (a == b) | (jnp.isnan(a) & jnp.isnan(b))
    return a == b


@jax.jit
def _match_ranges(left_keys: jnp.ndarray, right_keys: jnp.ndarray
                  ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(r_perm, lo, hi): the stable argsort of ``right_keys`` and, per
    left row, the ranks [lo, hi) of the right rows whose key equals it —
    ``jnp.searchsorted`` of each left key into the sorted right keys, on
    the left and on the right side.

    Two sorts and two scans, no gathers.  The first sort merges both
    sides by key, left rows ahead of equal right rows (concatenation
    order, stable sort): the right rows before a left row are its
    ``lo``, those up to the end of its equal-key run its ``hi``, and a
    right row's ``lo`` is its rank.  The second sort sends every row
    home — left rows to their position, right rows to their rank — so
    the ranges come back in left-row order and ``r_perm`` in rank order.
    Integer right keys merged as floats can round together; their own
    values then order them second, as the right side's own sort would.
    """
    n_left = left_keys.shape[0]
    n = n_left + right_keys.shape[0]
    keys = jnp.concatenate([left_keys, right_keys])
    if keys.dtype == jnp.bool_:
        keys = keys.astype(jnp.int32)
    operands = [keys, jax.lax.iota(jnp.int32, n)]
    if (jnp.issubdtype(right_keys.dtype, jnp.integer)
            and jnp.issubdtype(keys.dtype, jnp.floating)):
        lowest = jnp.full(n_left, jnp.iinfo(right_keys.dtype).min,
                          right_keys.dtype)
        operands.insert(1, jnp.concatenate([lowest, right_keys]))
    keys, *_, pos = jax.lax.sort(tuple(operands),
                                 num_keys=len(operands) - 1, is_stable=True)
    is_right = pos >= n_left
    right_upto = jax.lax.cumsum(is_right.astype(jnp.int32))
    lo = right_upto - is_right
    run_end = jnp.concatenate([~_same_key(keys[1:], keys[:-1]),
                               jnp.ones(1, jnp.bool_)])
    hi = jax.lax.cummin(jnp.where(run_end, right_upto, n), reverse=True)
    home = jnp.where(is_right, n_left + lo, pos)
    _, lo_or_row, hi = jax.lax.sort(
        (home, jnp.where(is_right, pos - n_left, lo), hi), num_keys=1)
    return lo_or_row[n_left:], lo_or_row[:n_left], hi[:n_left]


@partial(jax.jit, static_argnames=("capacity",))
def _expand(lo: jnp.ndarray, hi: jnp.ndarray, capacity: int
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(left_idx, right_pos) of each of ``capacity`` output slots: left
    row i fills the ``hi[i] - lo[i]`` slots from its running start with
    the right ranks ``lo[i], lo[i] + 1, ...``.

    ``capacity`` is the match count rounded UP to a power of two (the
    caller slices to the true count): the static output shape must not
    track the exact count or every distinct query result size costs a
    fresh XLA compile.  Slots past the match count belong to the last
    row; callers slice them off.

    A slot belongs to the last row starting at or before it.  One sort
    merges a marker per row (at its start, ahead of the slot there) with
    the slots; running sums along it count the markers before each slot
    and add up each marker's change in ``lo - start``, which leaves the
    covering row and its offset at every slot.  A second sort puts the
    slots back in order.  Unsigned keys ``2 * start`` and ``2 * slot + 1``
    order a marker before the slot it starts at without a stable sort."""
    counts = hi - lo
    starts = jnp.cumsum(counts) - counts
    off = lo - starts
    keys = jnp.concatenate([starts.astype(jnp.uint32) * 2,
                            jax.lax.iota(jnp.uint32, capacity) * 2 + 1])
    steps = jnp.concatenate([off[:1], off[1:] - off[:-1],
                             jnp.zeros(capacity, off.dtype)])
    keys, steps = jax.lax.sort((keys, steps), num_keys=1)
    is_slot = (keys & 1) == 1
    left_idx = jax.lax.cumsum((~is_slot).astype(jnp.int32)) - 1
    right_pos = (keys >> 1).astype(off.dtype) + jax.lax.cumsum(steps)
    home = jnp.where(is_slot, keys >> 1, jnp.uint32(capacity))
    _, left_idx, right_pos = jax.lax.sort((home, left_idx, right_pos),
                                          num_keys=1)
    return left_idx[:capacity], right_pos[:capacity]


@jax.jit
def _join_ranges(left_keys, right_keys, n_left, n_right):
    """(r_perm, lo, hi, match count) over capacity-padded keys: rows at
    or past ``n_left`` / ``n_right`` are padding and match nothing.

    Right padding takes the dtype's largest value (NaN for floats), so
    the stable sort parks it after every real row, real ties with the
    fill value included; clamping the ranges to ``n_right`` then drops
    it.  Padded left rows get empty ranges."""
    dt = right_keys.dtype
    if jnp.issubdtype(dt, jnp.floating):
        fill = jnp.array(jnp.nan, dt)
    elif dt == jnp.bool_:
        fill = jnp.array(True)
    else:
        fill = jnp.array(jnp.iinfo(dt).max, dt)
    rk = jnp.where(jnp.arange(right_keys.shape[0]) < n_right, right_keys,
                   fill)
    r_perm, lo, hi = _match_ranges(left_keys, rk)
    lo = jnp.minimum(lo, n_right)
    hi = jnp.where(jnp.arange(left_keys.shape[0]) < n_left,
                   jnp.minimum(hi, n_right), lo)
    return r_perm, lo, hi, jnp.sum(hi - lo)


@partial(jax.jit, static_argnames=("capacity",))
def _join_pairs(r_perm, lo, hi, capacity: int):
    """(left_idx, right_idx) of the first ``sum(hi - lo)`` of ``capacity``
    slots: the expanded match ranges, right positions mapped back
    through the sort permutation."""
    left_idx, right_pos = _expand(lo, hi, capacity)
    return left_idx, r_perm[jnp.clip(right_pos, 0, r_perm.shape[0] - 1)]


def sorted_equi_join_np(left_keys: np.ndarray, right_keys: np.ndarray
                        ) -> Tuple[np.ndarray, np.ndarray]:
    """Host mirror of ``sorted_equi_join`` — the same ranges and pair
    order, from numpy's sort and binary searches.  Below the device row
    threshold a device round trip costs more than it saves;
    covering-index data arrives sorted within buckets, so the mergesort
    argsort here is near-linear."""
    left_keys = np.asarray(left_keys)
    right_keys = np.asarray(right_keys)
    if left_keys.size == 0 or right_keys.size == 0:
        return np.empty(0, dtype=np.int64), np.empty(0, dtype=np.int64)
    r_perm = np.argsort(right_keys, kind="stable")
    rk_sorted = right_keys[r_perm]
    lo = np.searchsorted(rk_sorted, left_keys, side="left")
    hi = np.searchsorted(rk_sorted, left_keys, side="right")
    counts = hi - lo
    total = int(counts.sum())
    if total == 0:
        return np.empty(0, dtype=np.int64), np.empty(0, dtype=np.int64)
    left_idx = np.repeat(np.arange(left_keys.shape[0]), counts)
    starts = np.cumsum(counts) - counts
    within = np.arange(total) - np.repeat(starts, counts)
    right_idx = r_perm[lo[left_idx] + within]
    return left_idx.astype(np.int64), right_idx.astype(np.int64)


_FNV_OFFSET = np.uint64(0xcbf29ce484222325)
_FNV_PRIME = np.uint64(0x100000001b3)


def key_digests(table, key_columns, null_salt: int = 1) -> np.ndarray:
    """(n,) uint64 digest per row over ``key_columns`` — FNV-1a over each
    column's 64-bit hash words (io/columnar.to_hash_words: equal values,
    including -0.0/0.0 and equal strings, always produce equal words).
    Equal key tuples get equal digests; collisions are possible and are
    removed by ``hashed_equi_join``'s verification pass.

    Rows with a null in ANY key column get a digest unique to (row,
    ``null_salt``): inner-join semantics can never match them, and letting
    them share to_hash_words' null sentinel would make the digest join
    emit an n_left_nulls x n_right_nulls candidate cross product just for
    verification to discard."""
    import pyarrow.compute as pc

    from hyperspace_tpu.io import columnar

    n = table.num_rows
    acc = np.full(n, _FNV_OFFSET, dtype=np.uint64)
    nulls = np.zeros(n, dtype=bool)
    with np.errstate(over="ignore"):
        for c in key_columns:
            col = table.column(c)
            if col.null_count > 0:
                nulls |= np.asarray(pc.is_null(col))
            words = np.asarray(columnar.to_hash_words(col))
            w64 = (words[:, 0].astype(np.uint64) << np.uint64(32)) \
                | words[:, 1].astype(np.uint64)
            acc = (acc ^ w64) * _FNV_PRIME
        if nulls.any():
            acc[nulls] = (np.flatnonzero(nulls).astype(np.uint64)
                          * _FNV_PRIME) ^ (np.uint64(null_salt) << np.uint64(62))
    return acc


class UnsupportedJoinKeys(Exception):
    """Key pair the hashed join cannot handle exactly (e.g. string vs int)."""


def hashed_equi_join(left, right, l_keys, r_keys,
                     device: bool) -> Tuple[np.ndarray, np.ndarray]:
    """Inner equi-join for COMPOSITE and STRING keys: 64-bit digests joined
    with the sorted kernel (device or host mirror), then candidate pairs
    verified column-by-column against the actual values — hash collisions
    can only ADD candidates, never hide a match, so the verified result is
    exact.  Mixed numeric/numeric key pairs are compared as float64 (the
    Spark cast); NaN keys match NaN (Spark normalizes NaN for joins).

    Raises UnsupportedJoinKeys for key pairs with no exact common domain
    (caller falls back to the host hash join)."""
    import pyarrow as pa
    import pyarrow.compute as pc

    lcols, rcols = [], []
    for lc, rc in zip(l_keys, r_keys):
        la, ra = left.column(lc), right.column(rc)
        if la.type != ra.type:
            if (pa.types.is_floating(la.type) or pa.types.is_integer(la.type)) \
                    and (pa.types.is_floating(ra.type)
                         or pa.types.is_integer(ra.type)):
                la = pc.cast(la, pa.float64())
                ra = pc.cast(ra, pa.float64())
            else:
                raise UnsupportedJoinKeys(f"{la.type} vs {ra.type}")
        lcols.append(la)
        rcols.append(ra)
    ltab = pa.table({f"k{i}": c for i, c in enumerate(lcols)})
    rtab = pa.table({f"k{i}": c for i, c in enumerate(rcols)})
    join = sorted_equi_join if device else sorted_equi_join_np
    li, ri = join(
        key_digests(ltab, ltab.column_names, null_salt=1).view(np.int64),
        key_digests(rtab, rtab.column_names, null_salt=2).view(np.int64))
    if li.size == 0:
        return li, ri
    keep = np.ones(li.size, dtype=bool)
    for lc, rc in zip(ltab.columns, rtab.columns):
        la = lc.take(pa.array(li))
        ra = rc.take(pa.array(ri))
        eq = pc.fill_null(pc.equal(la, ra), False)
        mask = np.asarray(eq.to_numpy(zero_copy_only=False), dtype=bool)
        if pa.types.is_floating(la.type):
            both_nan = (
                np.asarray(pc.fill_null(pc.is_nan(la), False))
                & np.asarray(pc.fill_null(pc.is_nan(ra), False)))
            mask |= both_nan
        keep &= mask
    return li[keep], ri[keep]


def _key_owner_shards(keys: np.ndarray, n_devices: int):
    """(shards, originals): per mesh device, the key values it owns and
    their original indices.  Ownership is the key's hash bucket mod the
    device count — the same mod ownership as the sharded build route,
    through the bit-identical host hash mirror, so EQUAL keys always
    share an owner and the per-device joins are exhaustive."""
    import pyarrow as pa

    from hyperspace_tpu.io import columnar
    from hyperspace_tpu.ops.hash import bucket_ids_np

    words = np.asarray(columnar.to_hash_words(
        pa.chunked_array([pa.array(keys)])))
    owner = bucket_ids_np([words], n_devices)
    order = np.argsort(owner, kind="stable")
    owner_sorted = owner[order]
    starts = np.searchsorted(owner_sorted, np.arange(n_devices), "left")
    ends = np.searchsorted(owner_sorted, np.arange(n_devices), "right")
    shards = [keys[order[starts[d]:ends[d]]] for d in range(n_devices)]
    originals = [order[starts[d]:ends[d]] for d in range(n_devices)]
    return shards, originals


def sorted_equi_join_mesh(left_keys: np.ndarray, right_keys: np.ndarray,
                          mesh) -> Tuple[np.ndarray, np.ndarray]:
    """Sharding-aware entry of the inner equi-join: the same MATCH SET
    as :func:`sorted_equi_join` (pair order is not contractual), with
    both sides co-partitioned by key-hash bucket ownership and every
    device joining only its owned keys under ``shard_map``
    (parallel/join.copartitioned_join_ragged — zero collectives; the
    only host traffic is the final gather of match indices).  Host
    inputs only: resident arrays keep the single-device kernel, whose
    HBM placement is its own layout."""
    from hyperspace_tpu.parallel.join import copartitioned_join_ragged

    left_keys = np.asarray(left_keys)
    right_keys = np.asarray(right_keys)
    if left_keys.size == 0 or right_keys.size == 0:
        return np.empty(0, dtype=np.int64), np.empty(0, dtype=np.int64)
    n_devices = int(mesh.devices.size)
    l_shards, l_orig = _key_owner_shards(left_keys, n_devices)
    r_shards, r_orig = _key_owner_shards(right_keys, n_devices)
    dev_ids, l_local, r_local = copartitioned_join_ragged(
        l_shards, r_shards, mesh)
    li_parts, ri_parts = [], []
    for d in range(n_devices):
        sel = dev_ids == d
        if not sel.any():
            continue
        li_parts.append(l_orig[d][l_local[sel]])
        ri_parts.append(r_orig[d][r_local[sel]])
    if not li_parts:
        return np.empty(0, dtype=np.int64), np.empty(0, dtype=np.int64)
    return (np.concatenate(li_parts).astype(np.int64),
            np.concatenate(ri_parts).astype(np.int64))


def sorted_equi_join(left_keys: np.ndarray, right_keys: np.ndarray
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """Inner equi-join on single numeric keys.

    Returns (left_indices, right_indices) into the ORIGINAL (unsorted)
    inputs.  Right side is sorted on device; left side order is preserved.
    """
    # Narrow integer keys to int32 when every value fits: TPU has no native
    # int64 (XLA emulates it as two u32 passes), so a 32-bit sort is the
    # fast path.  Keys that genuinely need 64 bits (TPC-H orderkey at
    # SF100 exceeds 2^31) take the scoped-x64 path — scoped, not global,
    # because flipping x64 globally would change dtype defaults for every
    # other JAX user in the process.
    from hyperspace_tpu.utils.xla_cache import ensure_persistent_xla_cache

    ensure_persistent_xla_cache()
    # HBM-resident inputs (jax arrays from the device column cache) stay
    # on device: np.asarray would pull them back through the very
    # transfer residency exists to avoid.  Value-scan narrowing is
    # host-only for the same reason — resident int64 keys sort in x64.
    resident = isinstance(left_keys, jax.Array) \
        or isinstance(right_keys, jax.Array)
    if not resident:
        left_keys = np.asarray(left_keys)
        right_keys = np.asarray(right_keys)
    if (not resident
            and np.issubdtype(left_keys.dtype, np.integer)
            and np.issubdtype(right_keys.dtype, np.integer)
            and left_keys.size and right_keys.size):

        def fits32(a: np.ndarray) -> bool:
            if np.can_cast(a.dtype, np.int32):
                return True  # dtype already guarantees it: skip the scan
            return bool(a.min() >= -2**31 and a.max() <= 2**31 - 1)

        if fits32(left_keys) and fits32(right_keys):
            left_keys = left_keys.astype(np.int32, copy=False)
            right_keys = right_keys.astype(np.int32, copy=False)
    from hyperspace_tpu.ops.sort import _pad_rows
    from hyperspace_tpu.telemetry import timeline

    n_left, n_right = int(left_keys.shape[0]), int(right_keys.shape[0])
    if n_left == 0 or n_right == 0:
        return np.empty(0, dtype=np.int64), np.empty(0, dtype=np.int64)
    t0 = timeline.kernel_begin()
    if t0 is not None and not resident:
        # Attribution seam (conf-gated): host inputs are about to ship.
        timeline.record_transfer(
            "h2d", int(left_keys.nbytes) + int(right_keys.nbytes))
    with _enable_x64():
        # Capacity padding: every input size up to the next power of two
        # shares one compiled program (a bucketed join calls this once
        # per bucket, each a different size).
        lk = jnp.asarray(_pad_rows(left_keys, round_up_pow2(n_left)))
        rk = jnp.asarray(_pad_rows(right_keys, round_up_pow2(n_right)))
        r_perm, lo, hi, total = _join_ranges(lk, rk, n_left, n_right)
        # The one dynamic-shape sync point: only the match count crosses.
        total = int(sync_guard.scalar(total, "join.matches"))
        if total == 0:
            timeline.kernel_end("join", t0, (lo, hi))
            return np.empty(0, dtype=np.int64), np.empty(0, dtype=np.int64)
        left_idx, right_idx = _join_pairs(r_perm, lo, hi,
                                          round_up_pow2(total))
        timeline.kernel_end("join", t0, (left_idx, right_idx))
        # Attributed pulls (exec.transfer.d2h counted inside the seam).
        out_l = sync_guard.pull(left_idx, "join.left_idx")[:total]
        out_r = sync_guard.pull(right_idx, "join.right_idx")[:total]
        return out_l, out_r
