"""Host↔device columnar bridge.

Converts arrow columns into the dtype-monomorphic device representations the
kernels consume:

  - ``to_hash_words``: any column → (n, 2) uint32 words for the bucket-hash
    kernel.  Numerics bitcast on the host (cheap views); strings/binary are
    hashed host-side with pandas' vectorized C hasher (stable across calls)
    because variable-length data can't live in XLA's static-shape world
    (SURVEY.md §7 hard parts: dictionary-encode strings host-side).
  - ``to_order_key``: any column → (n,) numeric order key for the sort
    kernel.  Strings become order-preserving dense ranks via np.unique.
  - ``to_device_numeric``: numeric column → host array for predicate/join
    kernels; None for non-numeric or nullable (those evaluate host-side).

Temporal columns are normalized through ONE helper (``_temporal_to_int64``)
everywhere — build, query, and literal paths must agree on the integer
domain (the column's own storage unit) or identical values would hash to
different buckets.
"""

from __future__ import annotations

from typing import Optional

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

# Sentinel hash words for NULL: all nulls land in one deterministic bucket.
_NULL_WORDS = (np.uint32(0x9E3779B9), np.uint32(0x7F4A7C15))


def _combine(column: "pa.ChunkedArray | pa.Array") -> pa.Array:
    if isinstance(column, pa.ChunkedArray):
        column = column.combine_chunks()
    if pa.types.is_dictionary(column.type):
        column = column.cast(column.type.value_type)
    return column


def _null_mask(column: pa.Array) -> Optional[np.ndarray]:
    """Boolean mask of null positions, or None when the column has no nulls."""
    if column.null_count == 0:
        return None
    return np.asarray(pc.is_null(column).to_numpy(zero_copy_only=False), dtype=bool)


def _temporal_to_int64(column: pa.Array) -> pa.Array:
    """Temporal → int64 in the column's OWN storage unit (date32 stays days,
    timestamp[us] stays micros): unit-consistent for any one column type,
    which is all bucketing/ordering/compare need."""
    t = column.type
    if pa.types.is_date32(t) or pa.types.is_time32(t):
        return column.cast(pa.int32()).cast(pa.int64())
    return column.cast(pa.int64())


def _numeric_int64(column: pa.Array, fill_null_zero: bool) -> np.ndarray:
    """int/bool/temporal column → int64 numpy array in the native domain."""
    t = column.type
    if pa.types.is_temporal(t):
        column = _temporal_to_int64(column)
    elif pa.types.is_boolean(t) or not pa.types.is_int64(t):
        column = column.cast(pa.int64())
    if fill_null_zero and column.null_count > 0:
        column = pc.fill_null(column, 0)
    return column.to_numpy(zero_copy_only=False).astype(np.int64, copy=False)


def is_numeric_type(t: pa.DataType) -> bool:
    return (pa.types.is_integer(t) or pa.types.is_floating(t)
            or pa.types.is_boolean(t) or pa.types.is_temporal(t))


def to_hash_words(column: "pa.ChunkedArray | pa.Array") -> np.ndarray:
    """(n, 2) uint32 hash words; equal values always map to equal words;
    nulls all map to one sentinel word pair (one deterministic bucket)."""
    column = _combine(column)
    t = column.type
    nulls = _null_mask(column)
    if pa.types.is_floating(t):
        if nulls is not None:
            column = pc.fill_null(column, 0.0)
        arr = column.to_numpy(zero_copy_only=False).astype(np.float64)
        arr = np.where(arr == 0.0, 0.0, arr)  # -0.0 == 0.0 must hash equal
        # All NaN bit patterns hash alike (Spark normalizes NaN for
        # joins/grouping; a negative NaN written by another engine must
        # land with the canonical one).
        arr = np.where(np.isnan(arr), np.float64("nan"), arr)
        bits = arr.view(np.uint64)
    elif is_numeric_type(t):
        bits = _numeric_int64(column, fill_null_zero=True).view(np.uint64)
    else:
        # Variable-length (string/binary/decimal): vectorized stable hash.
        import pandas.util

        arr = column.to_numpy(zero_copy_only=False)
        bits = pandas.util.hash_array(np.asarray(arr, dtype=object))
    out = split_words64(bits.view(np.uint64) if bits.dtype != np.uint64 else bits)
    if nulls is not None:
        out[nulls, 0] = _NULL_WORDS[0]
        out[nulls, 1] = _NULL_WORDS[1]
    return out


def to_order_key(column: "pa.ChunkedArray | pa.Array") -> np.ndarray:
    """(n,) numeric key whose ordering equals the column's value ordering.
    Nulls sort with the placeholder value (ordering among them is not
    semantically observable — within-bucket sort is a layout property)."""
    column = _combine(column)
    t = column.type
    if pa.types.is_floating(t):
        if column.null_count > 0:
            column = pc.fill_null(column, 0.0)
        return column.to_numpy(zero_copy_only=False).astype(np.float64)
    if is_numeric_type(t):
        return _numeric_int64(column, fill_null_zero=True)
    # Strings: dense rank (np.unique inverse is rank-ordered).
    arr = column.to_numpy(zero_copy_only=False)
    _, inverse = np.unique(np.asarray(arr, dtype=object), return_inverse=True)
    return inverse.astype(np.int64)


def _monotone_uint64(keys: np.ndarray) -> np.ndarray:
    """Order-preserving map of an int64/float64 key array into uint64.

    int64: flip the sign bit.  float64: IEEE total-order trick (non-negative
    floats get the sign bit set; negative floats are bit-inverted), which
    ranks -0.0 immediately below +0.0 — an unobservable layout property
    (within-bucket sort order, see ``to_order_key``).
    """
    if keys.dtype == np.float64:
        bits = keys.view(np.int64)
        return np.where(bits >= 0,
                        bits.view(np.uint64) + np.uint64(1 << 63),
                        ~bits.view(np.uint64))
    assert keys.dtype == np.int64, keys.dtype
    return keys.view(np.uint64) ^ np.uint64(1 << 63)


def split_words64(values: np.ndarray) -> np.ndarray:
    """(n,) uint64 → (n, 2) uint32 (hi, lo) — the ONE word layout shared by
    hash words, order words, and the shuffle's row-id words."""
    out = np.empty((len(values), 2), dtype=np.uint32)
    out[:, 0] = (values >> np.uint64(32)).astype(np.uint32)
    out[:, 1] = (values & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    return out


def join_words64(hi: np.ndarray, lo: np.ndarray) -> np.ndarray:
    """Inverse of ``split_words64``."""
    return (hi.astype(np.uint64) << np.uint64(32)) | lo.astype(np.uint64)


def to_order_words(column: "pa.ChunkedArray | pa.Array") -> np.ndarray:
    """(n, 2) uint32 monotone words: lexicographic (hi, lo) order equals the
    column's value order.  This keeps the sort kernel pure 32-bit — TPU's
    native lane width — instead of relying on x64 int64 emulation."""
    return split_words64(_monotone_uint64(to_order_key(column)))


def to_order_codes64(column: "pa.ChunkedArray | pa.Array") -> np.ndarray:
    """(n,) uint64 monotone codes — ``to_order_words`` without the
    split into 32-bit words.  The HOST-side sort-key form (numpy is
    64-bit native; the word split serves the TPU lanes): the external
    build's route pass sorts on these and rides them along the spill
    runs as the writer's sort codes."""
    return _monotone_uint64(to_order_key(column))


def to_device_numeric(column: "pa.ChunkedArray | pa.Array") -> Optional[np.ndarray]:
    """Numeric host array suitable for jnp.asarray, or None if non-numeric
    OR nullable — SQL null semantics (null != null, three-valued predicates)
    are handled by the arrow host path, not the device kernels."""
    column = _combine(column)
    t = column.type
    if not is_numeric_type(t) or column.null_count > 0:
        return None
    if pa.types.is_floating(t):
        return column.to_numpy(zero_copy_only=False).astype(np.float64)
    return _numeric_int64(column, fill_null_zero=False)


def from_device_int64(values: np.ndarray, t: pa.DataType) -> pa.Array:
    """Inverse of ``to_device_numeric`` over its int64 domain (integers,
    bools, temporals in their own storage unit): the values as an arrow
    array of type ``t``."""
    values = np.asarray(values, dtype=np.int64)
    if pa.types.is_boolean(t):
        return pa.array(values != 0)
    if pa.types.is_date32(t) or pa.types.is_time32(t):
        return pa.array(values.astype(np.int32)).cast(t)
    return pa.array(values).cast(t)


def literal_to_numeric(value, t: pa.DataType) -> Optional[float]:
    """Normalize a literal to ``to_device_numeric``'s domain for a column of
    type ``t``; None if the literal doesn't fit that domain."""
    if pa.types.is_temporal(t):
        try:
            arr = pa.array([value], type=t)
        except (pa.ArrowInvalid, pa.ArrowTypeError):
            return None
        return int(_temporal_to_int64(arr)[0].as_py())
    if isinstance(value, bool):
        return int(value)
    if isinstance(value, (int, float)):
        return value
    return None
