"""Kernel unit tests: hash determinism/distribution, sort permutation,
join kernel, predicate compilation."""

import numpy as np
import pyarrow as pa
import pytest

from hyperspace_tpu.io import columnar
from hyperspace_tpu.ops.hash import bucket_ids
from hyperspace_tpu.ops.join import sorted_equi_join, sorted_equi_join_np
from hyperspace_tpu.ops.sort import bucket_counts, bucket_sort_permutation


def _words(values):
    return columnar.to_hash_words(pa.array(values))


def test_bucket_ids_deterministic_and_in_range():
    vals = list(range(1000))
    b1 = np.asarray(bucket_ids([_words(vals)], 16))
    b2 = np.asarray(bucket_ids([_words(vals)], 16))
    assert (b1 == b2).all()
    assert b1.min() >= 0 and b1.max() < 16
    # Equal values get equal buckets regardless of position.
    b3 = np.asarray(bucket_ids([_words([5, 5, 5, 7])], 16))
    assert b3[0] == b3[1] == b3[2]


def test_bucket_distribution_is_balanced():
    vals = np.arange(100_000)
    b = np.asarray(bucket_ids([_words(vals)], 64))
    counts = np.bincount(b, minlength=64)
    # Every bucket populated, no bucket > 2x the mean.
    assert counts.min() > 0
    assert counts.max() < 2 * counts.mean()


def test_string_and_int_hash_consistency():
    # Same string values hash equal across separate arrays/calls.
    a = np.asarray(bucket_ids([_words(["x", "y", "x"])], 8))
    b = np.asarray(bucket_ids([_words(["x"])], 8))
    assert a[0] == a[2] == b[0]


def test_float_negative_zero_hashes_like_zero():
    w = columnar.to_hash_words(pa.array([0.0, -0.0]))
    assert (w[0] == w[1]).all()


def test_bucket_sort_permutation_orders_by_bucket_then_key():
    rng = np.random.default_rng(0)
    vals = rng.integers(0, 1000, size=5000)
    words = _words(vals)
    keys = columnar.to_order_words(pa.array(vals))
    buckets, perm = bucket_sort_permutation([words], [keys], 8)
    buckets, perm = np.asarray(buckets), np.asarray(perm)
    sorted_buckets = buckets[perm]
    assert (np.diff(sorted_buckets) >= 0).all()
    sorted_vals = vals[perm]
    # Within each bucket, values ascend.
    for b in range(8):
        seg = sorted_vals[sorted_buckets == b]
        assert (np.diff(seg) >= 0).all()
    counts = np.asarray(bucket_counts(buckets, 8))
    assert counts.sum() == 5000
    assert (counts == np.bincount(buckets, minlength=8)).all()


def test_order_words_monotone_over_int_and_float():
    """(hi, lo) uint32 word pairs must order exactly like the values — the
    32-bit representation that keeps the sort kernel off x64 emulation."""
    for vals in (
        pa.array([-(2**62), -5, -1, 0, 1, 7, 2**40, 2**62]),
        pa.array([-1e300, -2.5, -0.0, 0.0, 1e-9, 3.14, 1e300]),
    ):
        w = columnar.to_order_words(vals)
        as_u64 = (w[:, 0].astype(np.uint64) << np.uint64(32)) | w[:, 1]
        assert (np.diff(as_u64.astype(object)) >= 0).all()


def test_string_order_key_preserves_order():
    vals = ["pear", "apple", "fig", "apple"]
    key = columnar.to_order_key(pa.array(vals))
    assert key[1] == key[3]                      # equal values equal keys
    order = np.argsort(key, kind="stable")
    assert [vals[i] for i in order] == ["apple", "apple", "fig", "pear"]


def test_sorted_equi_join_matches_naive():
    rng = np.random.default_rng(1)
    left = rng.integers(0, 50, size=300)
    right = rng.integers(0, 50, size=200)
    li, ri = sorted_equi_join(left, right)
    got = sorted(zip(left[li].tolist(), li.tolist(), ri.tolist()))
    expected = sorted(
        (int(lv), i, j)
        for i, lv in enumerate(left)
        for j, rv in enumerate(right)
        if lv == rv
    )
    assert [(v, i, j) for v, i, j in got] == expected


def test_sorted_equi_join_no_matches():
    li, ri = sorted_equi_join(np.array([1, 2, 3]), np.array([10, 20]))
    assert len(li) == 0 and len(ri) == 0


def _naive_pairs(ltab, rtab, l_keys, r_keys):
    lrows = list(zip(*[ltab.column(c).to_pylist() for c in l_keys]))
    rrows = list(zip(*[rtab.column(c).to_pylist() for c in r_keys]))
    return sorted((i, j) for i, lv in enumerate(lrows)
                  for j, rv in enumerate(rrows) if lv == rv)


class TestHashedEquiJoin:
    """Composite/string device join: digest join + exact verification
    (ops/join.hashed_equi_join)."""

    def test_composite_int_string_matches_naive(self):
        import pyarrow as pa

        from hyperspace_tpu.ops.join import hashed_equi_join

        rng = np.random.default_rng(2)
        left = pa.table({
            "a": pa.array(rng.integers(0, 20, 300), type=pa.int64()),
            "b": pa.array([("x", "y", "z")[i % 3] for i in range(300)]),
        })
        right = pa.table({
            "a2": pa.array(rng.integers(0, 20, 200), type=pa.int64()),
            "b2": pa.array([("x", "y", "w")[i % 3] for i in range(200)]),
        })
        for device in (False, True):
            li, ri = hashed_equi_join(left, right, ["a", "b"], ["a2", "b2"],
                                      device=device)
            assert sorted(zip(li.tolist(), ri.tolist())) == \
                _naive_pairs(left, right, ["a", "b"], ["a2", "b2"])

    def test_string_keys_match_naive(self):
        import pyarrow as pa

        from hyperspace_tpu.ops.join import hashed_equi_join

        left = pa.table({"s": pa.array(["ab", "cd", "ef", "ab", "zz"])})
        right = pa.table({"s2": pa.array(["cd", "ab", "qq"])})
        li, ri = hashed_equi_join(left, right, ["s"], ["s2"], device=False)
        assert sorted(zip(li.tolist(), ri.tolist())) == \
            _naive_pairs(left, right, ["s"], ["s2"])

    def test_mixed_numeric_types_coerce(self):
        import pyarrow as pa

        from hyperspace_tpu.ops.join import hashed_equi_join

        left = pa.table({"k": pa.array([1, 2, 3], type=pa.int64())})
        right = pa.table({"k2": pa.array([2.0, 3.0, 4.5], type=pa.float64())})
        li, ri = hashed_equi_join(left, right, ["k"], ["k2"], device=False)
        assert sorted(zip(li.tolist(), ri.tolist())) == [(1, 0), (2, 1)]

    def test_nan_keys_match_like_spark(self):
        import pyarrow as pa

        from hyperspace_tpu.ops.join import hashed_equi_join

        left = pa.table({"k": pa.array([float("nan"), 1.0])})
        right = pa.table({"k2": pa.array([float("nan"), 2.0])})
        li, ri = hashed_equi_join(left, right, ["k"], ["k2"], device=False)
        assert list(zip(li.tolist(), ri.tolist())) == [(0, 0)]

    def test_noncanonical_nan_still_matches(self):
        """NaN bit patterns differ across producers (negative/quiet NaN
        from other engines); all of them must digest alike or the
        verification rescue never sees the pair."""
        import pyarrow as pa

        from hyperspace_tpu.ops.join import hashed_equi_join

        weird_nan = np.frombuffer(
            np.uint64(0xFFF8000000000000).tobytes(), dtype=np.float64)[0]
        assert np.isnan(weird_nan)
        left = pa.table({"k": pa.array([weird_nan, 1.0])})
        right = pa.table({"k2": pa.array([float("nan"), 2.0])})
        li, ri = hashed_equi_join(left, right, ["k"], ["k2"], device=False)
        assert list(zip(li.tolist(), ri.tolist())) == [(0, 0)]

    def test_collisions_removed_by_verification(self, monkeypatch):
        """Even a degenerate digest (everything collides) must produce the
        exact result — the verify pass is the correctness backstop."""
        import pyarrow as pa

        from hyperspace_tpu.ops import join as join_mod

        monkeypatch.setattr(
            join_mod, "key_digests",
            lambda table, cols, null_salt=0:
                np.zeros(table.num_rows, dtype=np.uint64))
        left = pa.table({"s": pa.array(["a", "b", "c"])})
        right = pa.table({"s2": pa.array(["b", "c", "d"])})
        li, ri = join_mod.hashed_equi_join(left, right, ["s"], ["s2"],
                                           device=False)
        assert sorted(zip(li.tolist(), ri.tolist())) == [(1, 0), (2, 1)]

    def test_null_keys_never_match_and_never_blow_up(self):
        """Null keys share to_hash_words' sentinel, so without per-row
        null digests the candidate set would be n_l_nulls x n_r_nulls;
        they must instead produce ZERO candidates and zero matches."""
        import pyarrow as pa

        from hyperspace_tpu.ops.join import hashed_equi_join, key_digests

        left = pa.table({"s": pa.array(["a", None, None, "b"])})
        right = pa.table({"s2": pa.array([None, "b", None])})
        ld = key_digests(left, ["s"], null_salt=1)
        rd = key_digests(right, ["s2"], null_salt=2)
        # Every null row's digest is unique across BOTH sides.
        all_null_digests = [ld[1], ld[2], rd[0], rd[2]]
        assert len(set(int(d) for d in all_null_digests)) == 4
        li, ri = hashed_equi_join(left, right, ["s"], ["s2"], device=False)
        assert list(zip(li.tolist(), ri.tolist())) == [(3, 1)]

    def test_incompatible_types_raise(self):
        import pyarrow as pa

        from hyperspace_tpu.ops.join import (
            UnsupportedJoinKeys,
            hashed_equi_join,
        )

        left = pa.table({"s": pa.array(["1", "2"])})
        right = pa.table({"k": pa.array([1, 2], type=pa.int64())})
        import pytest as _pytest

        with _pytest.raises(UnsupportedJoinKeys):
            hashed_equi_join(left, right, ["s"], ["k"], device=False)


def test_compile_predicate_reuses_literals():
    import jax.numpy as jnp

    from hyperspace_tpu.ops.filter import compile_predicate
    from hyperspace_tpu.plan.expr import col, lit

    expr = (col("a") >= 10) & (col("b") == 3)
    fn, literals = compile_predicate(expr, ["a", "b"])
    assert literals == [10, 3]
    a = jnp.asarray([5, 10, 20])
    b = jnp.asarray([3, 3, 4])
    mask = np.asarray(fn([a, b], literals))
    assert mask.tolist() == [False, True, False]
    # Different literals, same compiled structure.
    mask2 = np.asarray(fn([a, b], [20, 4]))
    assert mask2.tolist() == [False, False, True]


class TestHostHashMirror:
    def test_bucket_ids_np_matches_device_kernel(self):
        """bucket_ids_np (the host mirror bucket pruning uses) must agree
        bit-for-bit with the device kernel that placed the rows — pruning
        must never disagree with placement."""
        import numpy as np

        from hyperspace_tpu.ops.hash import bucket_ids, bucket_ids_np

        rng = np.random.default_rng(3)
        n = 4096
        cols = [rng.integers(0, 2**32, size=(n, 2), dtype=np.uint32)
                for _ in range(3)]
        for nb in (1, 2, 16, 200):
            device = np.asarray(bucket_ids([c for c in cols], nb))
            host = bucket_ids_np(cols, nb)
            assert np.array_equal(device, host), nb

    def test_predicate_cache_reuses_jitted_fn(self):
        from hyperspace_tpu.ops.filter import _PREDICATE_CACHE, compile_predicate
        from hyperspace_tpu.plan.expr import BinOp, Col, Lit

        _PREDICATE_CACHE.clear()
        f1, lits1 = compile_predicate(BinOp("==", Col("x"), Lit(1)), ["x"])
        f2, lits2 = compile_predicate(BinOp("==", Col("x"), Lit(999)), ["x"])
        assert f1 is f2  # same structure, different literal: same program
        assert lits1 == [1] and lits2 == [999]
        f3, _ = compile_predicate(BinOp(">", Col("x"), Lit(1)), ["x"])
        assert f3 is not f1  # different op: different program

    def test_host_join_matches_device_join(self):
        import numpy as np

        from hyperspace_tpu.ops.join import sorted_equi_join, sorted_equi_join_np

        rng = np.random.default_rng(5)
        lk = rng.integers(0, 100, 500).astype(np.int64)
        rk = rng.integers(0, 100, 700).astype(np.int64)
        li_d, ri_d = sorted_equi_join(lk, rk)
        li_h, ri_h = sorted_equi_join_np(lk, rk)
        pairs_d = sorted(zip(lk[li_d].tolist(), rk[ri_d].tolist(),
                             li_d.tolist(), ri_d.tolist()))
        pairs_h = sorted(zip(lk[li_h].tolist(), rk[ri_h].tolist(),
                             li_h.tolist(), ri_h.tolist()))
        assert pairs_d == pairs_h
        # Empty sides
        e = np.empty(0, dtype=np.int64)
        assert sorted_equi_join_np(e, rk)[0].size == 0
        assert sorted_equi_join_np(lk, e)[1].size == 0

    def test_padded_bucket_sort_matches_exact(self):
        """Capacity padding must not change the result: padded rows park
        after all real rows, so buckets[:n]/perm[:n] equal the unpadded
        kernel's output."""
        import numpy as np

        from hyperspace_tpu.ops.sort import bucket_sort_permutation

        rng = np.random.default_rng(9)
        for n in (1, 7, 100, 1000):
            wc = [rng.integers(0, 2**32, size=(n, 2), dtype=np.uint32)]
            ow = [rng.integers(0, 2**32, size=(n, 2), dtype=np.uint32)]
            b0, p0 = bucket_sort_permutation(wc, ow, 8)
            b1, p1 = bucket_sort_permutation(wc, ow, 8, pad_to=256)
            assert np.array_equal(np.asarray(b0), np.asarray(b1)), n
            assert np.array_equal(np.asarray(p0), np.asarray(p1)), n
            assert np.asarray(p1).max() < n  # no padded index leaks


def test_bucket_sort_permutation_host_mirror_parity():
    """bucket_sort_permutation_np (the build's host mirror below
    device_build_min_rows) must reproduce the device kernel's buckets AND
    permutation exactly — the on-disk layout must not depend on where the
    permutation was computed."""
    import numpy as np

    from hyperspace_tpu.io import columnar
    from hyperspace_tpu.ops.sort import (
        bucket_sort_permutation,
        bucket_sort_permutation_np,
    )

    rng = np.random.default_rng(9)
    n = 1000
    import pyarrow as pa

    cols = [pa.array(rng.integers(-500, 500, n), type=pa.int64()),
            pa.array(rng.random(n))]
    word_cols = [np.asarray(columnar.to_hash_words(c)) for c in cols]
    order_words = [np.asarray(columnar.to_order_words(c)) for c in cols]
    for nb in (1, 4, 16):
        db, dp = bucket_sort_permutation(word_cols, order_words, nb,
                                         pad_to=256)
        hb, hp = bucket_sort_permutation_np(word_cols, order_words, nb)
        np.testing.assert_array_equal(np.asarray(db), hb)
        np.testing.assert_array_equal(np.asarray(dp), hp)


@pytest.mark.parametrize("dtypes", [
    ("uint32", "uint32"), ("int32", "bool"), ("int64",), ("int64", "int32"),
    ("uint32", "int64", "uint32"),
])
def test_lexsort32_matches_jnp_lexsort(dtypes):
    """The looped word sort is the same stable permutation as one
    multi-operand lexsort, ties (few distinct values) included."""
    import jax
    import jax.numpy as jnp

    from hyperspace_tpu.ops.hash import lexsort32

    rng = np.random.default_rng(len(dtypes))
    n = 5000
    with jax.enable_x64(True):
        keys = []
        for dt in dtypes:
            if dt == "bool":
                keys.append(jnp.asarray(rng.integers(0, 2, n).astype(bool)))
            elif dt.startswith("u"):
                keys.append(jnp.asarray(rng.integers(0, 7, n).astype(dt)))
            else:
                vals = rng.integers(-4, 4, n) * (2**40 if dt == "int64"
                                                 else 2**20)
                keys.append(jnp.asarray(vals.astype(dt)))
        np.testing.assert_array_equal(np.asarray(lexsort32(keys)),
                                      np.asarray(jnp.lexsort(keys)))


def _ranking_keys(case, rng, size):
    """Keys for the ranking parity cases: few distinct values, so both
    sides hold duplicates."""
    if case == "int32":
        return rng.integers(-6, 6, size).astype(np.int32)
    if case in ("int64_extremes", "int32_vs_int64"):
        pool = np.array([-2**63, -2**63 + 1, -1, 0, 1, 2**40,
                         2**63 - 2, 2**63 - 1], dtype=np.int64)
        return rng.choice(pool, size)
    if case == "float64_vs_wide_int64":
        # Right keys 2^60 + a few: distinct as int64, one value as float64.
        pool = np.array([2**60, 2**60 + 1, 2**60 + 255, 2**60 - 1, -2**60,
                         -2**60 + 3, 0, 1], dtype=np.int64)
        return rng.choice(pool, size)
    if case == "bool":
        return rng.integers(0, 2, size).astype(bool)
    if case == "float64":
        pool = np.array([0.0, -0.0, np.nan, -np.nan, np.inf, -np.inf, 1.5,
                         -2.0, np.float64(np.uint64(0x7ff8000000000001)
                                          .view(np.float64))])
        return rng.choice(pool, size)
    raise AssertionError(case)


@pytest.mark.parametrize("case", [
    "int32", "int64_extremes", "bool", "float64", "int32_vs_int64",
    "float64_vs_wide_int64", "no_matches",
])
def test_sort_ranking_matches_searchsorted(case):
    """The join's sort-based ranking gives exactly what binary searches
    into the stably sorted right keys give — ranges, right permutation
    and the expanded (left, right) pair sequence, every slot of the
    padded capacity included — with padding past ``n_left`` /
    ``n_right`` on both sides."""
    import jax

    from hyperspace_tpu.ops.join import _expand, _join_pairs, _join_ranges

    rng = np.random.default_rng(7)
    n_left, n_right, cap_left, cap_right = 50, 100, 64, 128
    if case == "no_matches":
        lk = rng.integers(0, 10, cap_left)
        rk = rng.integers(20, 30, cap_right)
        lk[n_left:] = 25  # padding matches nothing, even with real keys
    else:
        lk = _ranking_keys(case, rng, cap_left)
        rk = _ranking_keys(case, rng, cap_right)
        if case == "int32_vs_int64":
            lk = rng.integers(-3, 3, cap_left).astype(np.int32)
            rk = np.where(rng.random(cap_right) < 0.5, rk,
                          rng.integers(-3, 3, cap_right))
        if case == "float64_vs_wide_int64":
            lk = lk.astype(np.float64)
    # The numpy rendering: fill the right padding with the largest key,
    # stable argsort, two binary searches, clamp to the real rows.
    if rk.dtype.kind == "f":
        fill = np.nan
    elif rk.dtype == bool:
        fill = True
    else:
        fill = np.iinfo(rk.dtype).max
    rk_f = np.where(np.arange(cap_right) < n_right, rk, fill)
    r_perm = np.argsort(rk_f, kind="stable")
    lo = np.minimum(np.searchsorted(rk_f[r_perm], lk, "left"), n_right)
    hi = np.where(np.arange(cap_left) < n_left, np.minimum(
        np.searchsorted(rk_f[r_perm], lk, "right"), n_right), lo)
    total = int((hi - lo).sum())
    with jax.enable_x64(True):
        got = _join_ranges(lk, rk, n_left, n_right)
        np.testing.assert_array_equal(np.asarray(got[0]), r_perm)
        np.testing.assert_array_equal(np.asarray(got[1]), lo)
        np.testing.assert_array_equal(np.asarray(got[2]), hi)
        assert int(got[3]) == total
        for cap in (max(total, 1), 2 * max(total, 1)):
            counts = hi - lo
            starts = np.cumsum(counts) - counts
            slots = np.arange(cap)
            li_want = np.minimum(np.searchsorted(starts + counts, slots,
                                                 "right"), cap_left - 1)
            pos_want = lo[li_want] + slots - starts[li_want]
            li, pos = _expand(got[1], got[2], cap)
            np.testing.assert_array_equal(np.asarray(li), li_want)
            np.testing.assert_array_equal(np.asarray(pos), pos_want)
            li, ri = _join_pairs(got[0], got[1], got[2], cap)
            np.testing.assert_array_equal(
                np.asarray(ri), r_perm[np.clip(pos_want, 0, cap_right - 1)])
    # The pairs are the host join's over the real rows, in its order.
    li_host, ri_host = sorted_equi_join_np(lk[:n_left], rk[:n_right])
    np.testing.assert_array_equal(np.asarray(li)[:total], li_host)
    np.testing.assert_array_equal(np.asarray(ri)[:total], ri_host)
