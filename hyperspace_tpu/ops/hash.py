"""Device-side bucket-hash kernel.

This is the TPU re-expression of the reference's bucket assignment
(``repartition(numBuckets, indexedCols)`` = Murmur3Hash pmod numBuckets,
actions/CreateActionBase.scala:131-132).  We use our own murmur3-style mix —
self-consistent hashing is sufficient because indexes are only ever read by
this engine (SURVEY.md §7 "hard parts"); there is no interop with
Spark-written buckets.

Every key column is first normalized host-side to an ``(n, 2)`` uint32
"hash words" array (hyperspace_tpu.io.columnar.to_hash_words) so the device
kernel is dtype-monomorphic: one compiled program serves any key schema,
which keeps XLA's compile cache hot across heterogeneous datasets.  The
kernel itself is pure elementwise uint32 math — XLA fuses the whole chain
into a single VPU pass over HBM-resident batches.
"""

from __future__ import annotations

import os
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

# numpy scalars, NOT jnp: a module-scope jnp constant would initialize the
# JAX backend at import time, which must wait for the first kernel call.
_C1 = np.uint32(0x85EBCA6B)
_C2 = np.uint32(0xC2B2AE35)
_SEED = np.uint32(0x3C074A61)


def _fmix32(h: jnp.ndarray) -> jnp.ndarray:
    """Murmur3 finalizer (public algorithm)."""
    h = h ^ (h >> 16)
    h = h * _C1
    h = h ^ (h >> 13)
    h = h * _C2
    h = h ^ (h >> 16)
    return h


def use_pallas() -> bool:
    """Route per-row kernels through pallas?  ``HYPERSPACE_TPU_PALLAS`` =
    on | off | auto (default).  Auto: compiled pallas on TPU, plain XLA
    elsewhere — interpret-mode pallas on CPU is a correctness tool, not a
    fast path, so CPU CI opts in explicitly (tests/test_pallas_kernels.py;
    ``pallas_kernels._interpret`` refuses an unrequested interpret run).
    """
    mode = os.environ.get("HYPERSPACE_TPU_PALLAS", "auto").lower()
    if mode == "on":
        return True
    if mode == "off":
        return False
    return jax.default_backend() == "tpu"


def combine_hashes_xla(word_cols: Sequence[jnp.ndarray]) -> jnp.ndarray:
    """Reference XLA implementation (kept for parity testing + fallback)."""
    h = jnp.full(word_cols[0].shape[0], _SEED, dtype=jnp.uint32)
    for words in word_cols:
        h = _fmix32(h * jnp.uint32(31) ^ _fmix32(words[:, 0]))
        h = _fmix32(h * jnp.uint32(31) ^ _fmix32(words[:, 1]))
    return h


def _fmix32_np(h: np.ndarray) -> np.ndarray:
    h = h ^ (h >> np.uint32(16))
    h = h * _C1
    h = h ^ (h >> np.uint32(13))
    h = h * _C2
    h = h ^ (h >> np.uint32(16))
    return h


def bucket_ids_np(word_cols: Sequence[np.ndarray], num_buckets: int) -> np.ndarray:
    """Host mirror of ``bucket_ids`` — bit-identical uint32 math in numpy
    (wrap-around multiplication is exact in both).  For tiny inputs (bucket
    pruning probes a handful of key combinations per query) a device round
    trip costs pure latency; this keeps pruning on host while provably
    agreeing with device placement (parity-tested in tests/test_ops.py)."""
    with np.errstate(over="ignore"):
        h = np.full(np.asarray(word_cols[0]).shape[0], _SEED, dtype=np.uint32)
        for words in word_cols:
            words = np.asarray(words, dtype=np.uint32)
            h = _fmix32_np(h * np.uint32(31) ^ _fmix32_np(words[:, 0]))
            h = _fmix32_np(h * np.uint32(31) ^ _fmix32_np(words[:, 1]))
    return (h % np.uint32(num_buckets)).astype(np.int32)


def combine_hashes(word_cols: Sequence[jnp.ndarray]) -> jnp.ndarray:
    """uint32 row hash from per-column (n, 2) uint32 hash words.

    On TPU this is the fused pallas kernel (ops/pallas_kernels.py) — one
    VMEM pass over the word columns; elsewhere the plain XLA chain.  Both
    are bit-identical.
    """
    if use_pallas():
        from hyperspace_tpu.ops.pallas_kernels import hash_buckets

        return hash_buckets(tuple(word_cols), 0)
    return combine_hashes_xla(word_cols)


@partial(jax.jit, static_argnames=("num_buckets", "pallas"))
def _bucket_ids_impl(word_cols, num_buckets: int, pallas: bool) -> jnp.ndarray:
    if pallas:
        from hyperspace_tpu.ops.pallas_kernels import hash_buckets

        return hash_buckets(word_cols, num_buckets).astype(jnp.int32)
    h = combine_hashes_xla(word_cols)
    return (h % jnp.uint32(num_buckets)).astype(jnp.int32)


def bucket_ids(word_cols: Sequence[jnp.ndarray], num_buckets: int) -> jnp.ndarray:
    """Per-row bucket assignment in [0, num_buckets) as int32.

    The pallas/XLA choice is part of the jit cache key (static arg): env
    flips between calls retrace instead of silently reusing the old path.
    """
    from hyperspace_tpu.telemetry import timeline

    t0 = timeline.kernel_begin()
    out = _bucket_ids_impl(tuple(word_cols), num_buckets, use_pallas())
    timeline.kernel_end("bucket_ids", t0, out)
    return out


def lexsort32(keys) -> jnp.ndarray:
    """``jnp.lexsort(keys)`` for integer or bool keys (LAST key primary;
    ties keep row order), as one stable single-key argsort per 32-bit
    word, least significant first, inside a loop.

    The same permutation as one multi-operand sort, but the TPU compiler
    compiles the loop body once: a 1<<20-row sort compiles in about 20 s
    whatever the key count, where a k-key ``jnp.lexsort`` took about
    20 s per key, and an int64 argsort over a minute
    (tests/test_tpu_compile.py).  Signed keys map to unsigned order by
    flipping the sign bit; 64-bit keys split into (lo, hi) words."""
    words = []
    for k in keys:
        if k.dtype == jnp.bool_:
            k = k.astype(jnp.uint32)
        elif not jnp.issubdtype(k.dtype, jnp.integer):
            raise TypeError(f"lexsort32 sorts integer keys, got {k.dtype}")
        if jnp.dtype(k.dtype).itemsize == 8:
            u = k.astype(jnp.uint64)
            if jnp.issubdtype(k.dtype, jnp.signedinteger):
                u = u ^ jnp.uint64(1 << 63)
            words.append((u & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32))
            words.append((u >> jnp.uint64(32)).astype(jnp.uint32))
            continue
        if jnp.issubdtype(k.dtype, jnp.signedinteger):
            k = k.astype(jnp.int32).astype(jnp.uint32) ^ jnp.uint32(1 << 31)
        words.append(k.astype(jnp.uint32))
    stacked = jnp.stack(words)
    n = stacked.shape[1]

    def pass_(i, perm):
        return perm[jnp.argsort(stacked[i][perm], stable=True)]

    return jax.lax.fori_loop(0, len(words), pass_,
                             jnp.arange(n, dtype=jnp.int32))


# ---------------------------------------------------------------------------
# Fused route+partition kernel (the external build's per-chunk pass)
# ---------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("num_buckets", "pallas"))
def _route_sort_impl(
    word_cols,
    order_words,
    n_valid,
    num_buckets: int,
    pallas: bool,
) -> jnp.ndarray:  # (2, n) stacked [buckets, perm] — one host transfer
    """Hash → (bucket, *keys) stable lexsort → stacked (buckets, perm).

    THE bucket/sort program: ``ops.sort.bucket_sort_permutation`` (the
    monolithic build) and :func:`route_partition` (the external build's
    per-chunk pass) both trace exactly this function, so the two paths
    share one compiled program per capacity and can never diverge in
    bucket assignment or tie order.  ``order_words`` may be EMPTY: the
    lexsort then groups rows by bucket only, original order preserved
    within each bucket (the partition-only mode for rank-mapped key
    types whose chunk-local order words are not globally comparable).
    """
    buckets = _bucket_ids_impl(word_cols, num_buckets, pallas)
    # Capacity padding: rows at positions >= n_valid get bucket id
    # ``num_buckets`` — past every real bucket, so the stable lexsort
    # parks them after all real rows and ``perm[:n]`` is real.
    n = word_cols[0].shape[0]
    buckets = jnp.where(jnp.arange(n) < n_valid, buckets,
                        jnp.int32(num_buckets))
    # lexsort: LAST key is the primary.  Order: bucket first, then
    # key columns in config order, each (hi, lo) word pair hi-major.
    keys = []
    for w in reversed(order_words):
        keys.append(w[:, 1])
        keys.append(w[:, 0])
    keys.append(buckets)
    perm = lexsort32(keys)
    return jnp.stack([buckets, perm])


def _pad_host_rows(arr: np.ndarray, capacity: int) -> np.ndarray:
    arr = np.asarray(arr)
    if arr.shape[0] == capacity:
        return arr
    pad = np.zeros((capacity - arr.shape[0],) + arr.shape[1:],
                   dtype=arr.dtype)
    return np.concatenate([arr, pad], axis=0)


def route_partition(
    word_cols: Sequence[np.ndarray],
    order_words: Sequence[np.ndarray],
    num_buckets: int,
    pad_to: int = 0,
):
    """Fused route+partition device pass for one spill chunk.

    One kernel computes bucket ids AND the permutation that groups the
    chunk's rows into per-bucket runs (sorted within bucket when
    ``order_words`` is non-empty; original order otherwise) — replacing
    the old two-step of a device ``bucket_ids`` pull followed by a host
    argsort.  Returns ``(bucket_ids, perm)`` as host int32 arrays,
    pulled in ONE stacked device→host transfer through the attributed
    ``sync_guard.pull`` seam.

    ``pad_to`` follows ``bucket_sort_permutation``'s capacity-padding
    contract (one compiled program per capacity/key-count).
    """
    from hyperspace_tpu.execution import sync_guard
    from hyperspace_tpu.telemetry import timeline
    from hyperspace_tpu.utils.xla_cache import ensure_persistent_xla_cache

    ensure_persistent_xla_cache()
    n = int(word_cols[0].shape[0])
    if pad_to and pad_to > 0:
        capacity = -(-max(n, 1) // pad_to) * pad_to
        word_cols = [_pad_host_rows(w, capacity) for w in word_cols]
        order_words = [_pad_host_rows(w, capacity) for w in order_words]
    t0 = timeline.kernel_begin()
    if t0 is not None:
        timeline.record_transfer("h2d", sum(
            int(getattr(a, "nbytes", 0))
            for a in (*word_cols, *order_words)
            if not isinstance(a, jax.Array)))
    out = _route_sort_impl(
        tuple(word_cols), tuple(order_words), n, num_buckets, use_pallas())
    timeline.kernel_end("route_partition", t0, out)
    stacked = sync_guard.pull(out, "route.partition")
    return stacked[0, :n], stacked[1, :n]


def route_partition_mesh(
    word_cols: Sequence[np.ndarray],
    order_words: Sequence[np.ndarray],
    num_buckets: int,
    mesh,
    pad_to: int = 0,
):
    """Sharding-aware entry of the fused route+partition: the SAME
    ``(bucket_ids, perm)`` contract (bit-identical output — layout can
    never depend on the route), computed over ``mesh`` with per-device
    bucket ownership ``bucket_id % n_devices`` and a host gather seam of
    one attributed pull per device (parallel/sharded_build.py)."""
    from hyperspace_tpu.parallel.sharded_build import mesh_route_partition

    return mesh_route_partition(word_cols, order_words, num_buckets,
                                mesh, pad_to=pad_to)


def route_partition_np(
    word_cols: Sequence[np.ndarray],
    order_words: Sequence[np.ndarray],
    num_buckets: int,
):
    """Bit-identical HOST mirror of :func:`route_partition` (the same
    cost model as ``bucket_sort_permutation_np``: below the calibrated
    build threshold a per-chunk device round trip costs pure latency).
    Shares ``bucket_ids_np`` and the identical stable-lexsort ordering,
    so chunk layout can never depend on where it was computed.

    The host lexsort keys on ONE uint64 per column — the same total
    order as the (hi, lo) uint32 pair in half the stable-sort passes
    (numpy is 64-bit native; the 32-bit split exists for the TPU's VPU
    lanes).  ``order_words`` items may be either (n, 2) uint32 word
    pairs or (n,) uint64 codes (``columnar.to_order_codes64``) —
    callers that already hold the joined form skip the round trip."""
    with np.errstate(over="ignore"):
        buckets = bucket_ids_np([np.asarray(w) for w in word_cols],
                                num_buckets)
    keys = []
    for w in reversed(list(order_words)):
        w = np.asarray(w)
        keys.append(w if w.ndim == 1
                    else (w[:, 0].astype(np.uint64) << np.uint64(32))
                    | w[:, 1].astype(np.uint64))
    keys.append(buckets)
    perm = np.lexsort(tuple(keys)).astype(np.int32)
    return buckets.astype(np.int32), perm
