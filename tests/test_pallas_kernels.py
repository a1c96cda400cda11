"""Parity tests for the pallas TPU kernels (interpret mode on CPU).

The pallas hash/histogram kernels must be bit-identical to the XLA paths:
the bucket an index row lands in is durable on-disk layout, so a kernel
swap that changes one bucket id silently corrupts every existing index.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from hyperspace_tpu.ops.hash import bucket_ids, combine_hashes_xla, use_pallas
from hyperspace_tpu.ops.pallas_kernels import (
    bucket_histogram,
    bucket_ids_pallas,
    hash_buckets,
)
from hyperspace_tpu.ops.sort import _bucket_counts_xla


@pytest.fixture(autouse=True)
def _ask_for_interpret_mode(monkeypatch):
    # Off-TPU, pallas runs in interpret mode only when asked.
    monkeypatch.setenv("HYPERSPACE_TPU_PALLAS", "on")


def _words(n, cols=2, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(
        jnp.asarray(rng.integers(0, 2**32, size=(n, 2), dtype=np.uint32))
        for _ in range(cols))


# Sizes straddling the tile boundaries: sub-tile, exact tiles, ragged edge.
@pytest.mark.parametrize("n", [1, 7, 128, 1000, 32768, 32769, 100_003])
def test_hash_parity(n):
    cols = _words(n)
    expected = np.asarray(combine_hashes_xla(cols))
    actual = np.asarray(hash_buckets(cols, 0))
    np.testing.assert_array_equal(actual, expected)


@pytest.mark.parametrize("num_buckets", [1, 13, 200, 4096])
def test_bucket_ids_parity(num_buckets):
    cols = _words(10_000, cols=3, seed=1)
    expected = np.asarray(
        combine_hashes_xla(cols) % np.uint32(num_buckets)).astype(np.int32)
    actual = np.asarray(bucket_ids_pallas(cols, num_buckets))
    np.testing.assert_array_equal(actual, expected)


@pytest.mark.parametrize("n,num_buckets", [
    (1, 1), (100, 7), (4096, 128), (4097, 129), (50_000, 200), (1000, 4096),
])
def test_histogram_parity(n, num_buckets):
    rng = np.random.default_rng(2)
    ids = jnp.asarray(rng.integers(0, num_buckets, size=n, dtype=np.int32))
    expected = np.asarray(_bucket_counts_xla(ids, num_buckets))
    actual = np.asarray(bucket_histogram(ids, num_buckets))
    np.testing.assert_array_equal(actual, expected)
    assert int(actual.sum()) == n  # padding rows must not be counted


def test_histogram_empty_input():
    ids = jnp.asarray(np.empty(0, dtype=np.int32))
    out = np.asarray(bucket_histogram(ids, 64))
    np.testing.assert_array_equal(out, np.zeros(64, dtype=np.int32))


def test_env_switch(monkeypatch):
    monkeypatch.setenv("HYPERSPACE_TPU_PALLAS", "on")
    assert use_pallas()
    cols = _words(5_000, seed=3)
    via_dispatch = np.asarray(bucket_ids(cols, 64))
    monkeypatch.setenv("HYPERSPACE_TPU_PALLAS", "off")
    assert not use_pallas()
    via_xla = np.asarray(bucket_ids(cols, 64))
    np.testing.assert_array_equal(via_dispatch, via_xla)


def test_unrequested_interpret_mode_is_refused(monkeypatch):
    from hyperspace_tpu.ops.pallas_kernels import _interpret

    assert _interpret()  # asked for by the autouse fixture
    monkeypatch.delenv("HYPERSPACE_TPU_PALLAS")
    with pytest.raises(RuntimeError, match="HYPERSPACE_TPU_PALLAS=on"):
        _interpret()
