"""Pallas TPU kernels for the per-row hot ops of the data plane.

Two kernels, both tiled over row blocks resident in VMEM:

  - ``hash_buckets``: the fused murmur3-mix → bucket-id chain
    (reference contract ``repartition(numBuckets, cols)`` bucket
    assignment, actions/CreateActionBase.scala:131-132).  The XLA
    fallback (`hyperspace_tpu.ops.hash.combine_hashes`) emits ~10
    elementwise HLOs per key column; the pallas kernel runs the whole
    mix chain in one VMEM pass per row tile — one HBM read per input
    word, one HBM write for the bucket ids, nothing materialized in
    between.
  - ``bucket_histogram``: rows-per-bucket counts via a 2-D one-hot
    compare + row-sum per tile, accumulated across the sequential TPU
    grid.  This avoids ``segment_sum``'s scatter-add lowering, which
    XLA serializes; the one-hot compare is pure VPU work.

Both kernels compile with Mosaic on TPU.  Off-TPU they run in interpret
mode only when asked (``HYPERSPACE_TPU_PALLAS=on``: CPU CI, SURVEY.md §4
"single host" test idiom) and are exact-parity with the XLA paths —
``tests/test_pallas_kernels.py`` asserts bit-equality.

Layout: callers pass (n, 2) uint32 hash-word columns
(`hyperspace_tpu.io.columnar.to_hash_words`).  The wrapper pads n up to
a whole number of (ROWS_PER_TILE × 128) tiles and views each word
column as (rows, 128) — the native int32 VREG shape — so the kernel
never sees a ragged edge; padding rows hash to garbage that the caller
slices off.
"""

from __future__ import annotations

from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu  # noqa: F401  (TPU lowering)

_LANES = 128
# 256 sublanes × 128 lanes × 4 B = 128 KiB per ref per tile — comfortably
# inside the ~16 MiB VMEM budget even with several key columns.
_HASH_TILE_ROWS = 256
# The histogram tile holds a (ROWS, 128) one-hot block: 4096 element rows
# × 128 bucket lanes × 4 B = 2 MiB.
_HIST_TILE_ROWS = 4096

# Same constants as ops/hash.py — numpy scalars so importing this module
# never initializes the JAX backend.
_C1 = np.uint32(0x85EBCA6B)
_C2 = np.uint32(0xC2B2AE35)
_SEED = np.uint32(0x3C074A61)
_THIRTY_ONE = np.uint32(31)


def _interpret() -> bool:
    """Compiled Mosaic kernels on TPU; interpret mode only where a CPU
    run asked for pallas (``HYPERSPACE_TPU_PALLAS=on``, the parity
    tests).  Anything else is a routing bug, not a slow path to hide."""
    import os

    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if os.environ.get("HYPERSPACE_TPU_PALLAS", "").lower() == "on":
        return True
    raise RuntimeError(
        f"pallas kernel reached on the {backend} backend without "
        f"HYPERSPACE_TPU_PALLAS=on: interpret mode runs only when asked")


def _fmix32(h):
    h = h ^ (h >> 16)
    h = h * _C1
    h = h ^ (h >> 13)
    h = h * _C2
    h = h ^ (h >> 16)
    return h


def _hash_kernel(num_buckets: int, n_cols: int, *refs) -> None:
    """refs = [hi_0, lo_0, hi_1, lo_1, ..., out]; every block (T, 128) u32."""
    out_ref = refs[-1]
    h = jnp.full(out_ref.shape, _SEED, dtype=jnp.uint32)
    for c in range(n_cols):
        hi = refs[2 * c][...]
        lo = refs[2 * c + 1][...]
        h = _fmix32(h * _THIRTY_ONE ^ _fmix32(hi))
        h = _fmix32(h * _THIRTY_ONE ^ _fmix32(lo))
    if num_buckets:
        h = h % jnp.uint32(num_buckets)
    out_ref[...] = h


def _pad_to_tiles(flat: jnp.ndarray, tile_elems: int) -> jnp.ndarray:
    n = flat.shape[0]
    padded = -(-n // tile_elems) * tile_elems
    if padded != n:
        flat = jnp.pad(flat, (0, padded - n))
    return flat.reshape(-1, _LANES)


@partial(jax.jit, static_argnames=("num_buckets",))
def hash_buckets(word_cols: Sequence[jnp.ndarray], num_buckets: int = 0
                 ) -> jnp.ndarray:
    """Fused row hash (num_buckets=0) or bucket ids, as (n,) uint32.

    ``word_cols``: per key column (n, 2) uint32 hash words.  Bit-identical
    to ``ops.hash.combine_hashes`` / ``% num_buckets``.
    """
    n = word_cols[0].shape[0]
    tile_elems = _HASH_TILE_ROWS * _LANES
    flats = []
    for w in word_cols:
        flats.append(_pad_to_tiles(w[:, 0], tile_elems))
        flats.append(_pad_to_tiles(w[:, 1], tile_elems))
    rows = flats[0].shape[0]
    grid = rows // _HASH_TILE_ROWS
    spec = pl.BlockSpec((_HASH_TILE_ROWS, _LANES), lambda i: (i, 0))
    out = pl.pallas_call(
        partial(_hash_kernel, num_buckets, len(word_cols)),
        grid=(grid,),
        in_specs=[spec] * len(flats),
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows, _LANES), jnp.uint32),
        interpret=_interpret(),
    )(*flats)
    return out.reshape(-1)[:n]


def _hist_kernel(ids_ref, out_ref) -> None:
    """ids (T, 1) int32 column; out (1, 128) int32 — bucket-block j's counts.

    The ids come in as a COLUMN vector so the one-hot is a lane-broadcast
    compare — Mosaic has no (T, 128) → (T*128, 1) shape cast, but
    broadcasting (T, 1) against a (T, 128) lane iota is native VPU work.
    Grid is (bucket_blocks, row_tiles): the reduction dimension (row
    tiles) is MINORMOST so each output block is revisited on consecutive
    grid steps — the only accumulation order pallas TPU guarantees.
    """
    j = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    ids = ids_ref[...]                                      # (T, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (ids.shape[0], _LANES), 1)
    onehot = (ids == lane + j * _LANES).astype(jnp.int32)   # broadcast compare
    out_ref[...] += jnp.sum(onehot, axis=0, keepdims=True)


@partial(jax.jit, static_argnames=("num_buckets",))
def bucket_histogram(bucket_ids: jnp.ndarray, num_buckets: int) -> jnp.ndarray:
    """(num_buckets,) int32 counts; parity with ``ops.sort.bucket_counts``.

    Padding rows are tagged with bucket id -1, which matches no lane, so
    they vanish from every count.
    """
    ids = bucket_ids.astype(jnp.int32)
    n = ids.shape[0]
    if n == 0:
        # Zero row tiles would mean the kernel (and its output zeroing)
        # never runs — the buffer would be uninitialized device memory.
        return jnp.zeros((num_buckets,), dtype=jnp.int32)
    padded = -(-n // _HIST_TILE_ROWS) * _HIST_TILE_ROWS
    if padded != n:
        ids = jnp.pad(ids, (0, padded - n), constant_values=-1)
    ids = ids.reshape(-1, 1)
    bucket_blocks = -(-num_buckets // _LANES)
    grid = (bucket_blocks, padded // _HIST_TILE_ROWS)
    out = pl.pallas_call(
        _hist_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((_HIST_TILE_ROWS, 1), lambda j, i: (i, 0))],
        out_specs=pl.BlockSpec((1, _LANES), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, bucket_blocks * _LANES), jnp.int32),
        interpret=_interpret(),
    )(ids)
    return out.reshape(-1)[:num_buckets]


def bucket_ids_pallas(word_cols: Sequence[jnp.ndarray], num_buckets: int
                      ) -> jnp.ndarray:
    """Bucket assignment as int32 — drop-in for ``ops.hash.bucket_ids``."""
    return hash_buckets(word_cols, num_buckets).astype(jnp.int32)
