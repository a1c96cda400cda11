"""Distribution-layer tests on the virtual 8-device CPU mesh.

The analog of the reference exercising shuffles/bucketing through local-mode
Spark with multiple executor threads (SparkInvolvedSuite.scala:31-36): the
same shard_map programs that run over ICI on a TPU slice run here over 8
host devices, so routing, capacity overflow, and co-partitioning invariants
are all validated without TPU hardware.
"""

import numpy as np
import pyarrow as pa
import pytest

import jax

from hyperspace_tpu.io import columnar
from hyperspace_tpu.ops.hash import bucket_ids
from jax import enable_x64 as _enable_x64
from hyperspace_tpu.ops.sort import bucket_sort_permutation
from hyperspace_tpu.parallel import (
    bucket_shuffle,
    build_mesh,
    copartitioned_join,
    copartitioned_join_ragged,
    distributed_bucket_sort_permutation,
)


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) == 8, "conftest must provide 8 virtual devices"
    return build_mesh()


def _key_arrays(values):
    col = pa.array(values)
    return columnar.to_hash_words(col), columnar.to_order_words(col)


class TestBucketShuffle:
    def test_zero_rows(self, mesh):
        """Empty source build must not crash the distributed path."""
        empty = np.empty((0, 2), np.uint32)
        result, payload = bucket_shuffle([empty], [empty], 8, mesh)
        assert result.perm.size == 0
        assert int(result.device_row_counts.sum()) == 0
        assert payload is None
        result, payload = bucket_shuffle(
            [empty], [empty], 8, mesh,
            payload_words=np.empty((0, 3), np.uint32))
        assert payload.shape == (0, 3)

    @pytest.mark.parametrize("pallas", ["off", "on"])
    def test_matches_single_device_assignment(self, mesh, pallas,
                                              monkeypatch):
        # "on": the pallas hash kernel inside shard_map (interpret mode
        # here, Mosaic on a TPU).
        monkeypatch.setenv("HYPERSPACE_TPU_PALLAS", pallas)
        rng = np.random.default_rng(0)
        vals = rng.integers(0, 10_000, size=5_000)
        hw, ow = _key_arrays(vals)
        num_buckets = 16

        result, _ = bucket_shuffle([hw], [ow], num_buckets, mesh)
        expected = np.asarray(bucket_ids([hw], num_buckets))

        assert sorted(result.perm.tolist()) == list(range(len(vals)))
        # Every routed row carries the same bucket the single-chip kernel
        # assigns.
        got = np.empty(len(vals), np.int32)
        got[result.perm] = result.buckets_sorted
        np.testing.assert_array_equal(got, expected)

    def test_rows_sorted_by_bucket_then_key(self, mesh):
        rng = np.random.default_rng(1)
        vals = rng.integers(-50_000, 50_000, size=3_000)
        hw, ow = _key_arrays(vals)
        result, _ = bucket_shuffle([hw], [ow], 8, mesh)

        counts = result.device_row_counts
        offset = 0
        for d, c in enumerate(counts):
            chunk_buckets = result.buckets_sorted[offset:offset + c]
            chunk_vals = vals[result.perm[offset:offset + c]]
            # Device d owns exactly bucket d (8 buckets over 8 devices).
            assert (chunk_buckets == d).all()
            assert (np.diff(chunk_vals) >= 0).all()
            offset += c

    def test_device_ownership_is_contiguous_ranges(self, mesh):
        rng = np.random.default_rng(2)
        vals = rng.integers(0, 1_000, size=2_000)
        hw, ow = _key_arrays(vals)
        num_buckets = 20  # 20 buckets over 8 devices: ceil = 3 per device
        result, _ = bucket_shuffle([hw], [ow], num_buckets, mesh)
        offset = 0
        for d, c in enumerate(result.device_row_counts):
            chunk = result.buckets_sorted[offset:offset + c]
            assert ((chunk // 3) == d).all()
            offset += c

    def test_overflow_retry_with_skewed_keys(self, mesh):
        # All rows share one key → one bucket → one destination device; the
        # initial balanced capacity must overflow and the retry must still
        # deliver every row.
        vals = np.full(2_000, 42, dtype=np.int64)
        hw, ow = _key_arrays(vals)
        result, _ = bucket_shuffle([hw], [ow], 16, mesh, slack=1.1)
        assert sorted(result.perm.tolist()) == list(range(len(vals)))
        assert len(np.unique(result.buckets_sorted)) == 1
        assert result.capacity > 16 // 8  # grew past the balanced estimate

    def test_payload_rides_the_shuffle(self, mesh):
        rng = np.random.default_rng(3)
        vals = rng.integers(0, 500, size=1_000)
        payload = np.arange(1_000, dtype=np.uint32)[:, None] * np.uint32(7)
        hw, ow = _key_arrays(vals)
        result, routed = bucket_shuffle([hw], [ow], 8, mesh,
                                        payload_words=payload)
        np.testing.assert_array_equal(routed[:, 0],
                                      payload[result.perm, 0])

    def test_matches_single_chip_kernel_order(self, mesh):
        """Global (bucket, key) order equals the single-chip fused kernel's —
        the writer contract is identical on 1 chip and N chips."""
        rng = np.random.default_rng(4)
        table = pa.table({"k": rng.integers(0, 200, size=4_000),
                          "v": rng.normal(size=4_000)})
        buckets_d, perm_d = distributed_bucket_sort_permutation(
            table, ["k"], 16, mesh)
        hw = columnar.to_hash_words(table.column("k"))
        ow = columnar.to_order_words(table.column("k"))
        buckets_s, perm_s = bucket_sort_permutation([hw], [ow], 16)
        np.testing.assert_array_equal(buckets_d, np.asarray(buckets_s))
        # Permutations may differ within equal (bucket, key) ties; the sorted
        # (bucket, key) sequences must be identical.
        np.testing.assert_array_equal(
            np.asarray(table.column("k"))[perm_d],
            np.asarray(table.column("k"))[np.asarray(perm_s)])

    def test_string_keys(self, mesh):
        words = ["apple", "banana", "cherry", "dates"] * 250
        hw, ow = _key_arrays(words)
        result, _ = bucket_shuffle([hw], [ow], 8, mesh)
        assert sorted(result.perm.tolist()) == list(range(len(words)))
        arr = np.asarray(words, dtype=object)
        offset = 0
        for c in result.device_row_counts:
            chunk = arr[result.perm[offset:offset + c]]
            assert list(chunk) == sorted(chunk)
            offset += c


class TestCopartitionedJoin:
    def test_dense_matches_numpy_reference(self, mesh):
        rng = np.random.default_rng(5)
        D = 8
        # Co-partition: device d holds keys ≡ d (mod 8) on both sides.
        left = np.stack([rng.integers(0, 40, size=64) * D + d for d in range(D)])
        right = np.stack([rng.integers(0, 40, size=96) * D + d for d in range(D)])
        li, ri = copartitioned_join(left, right, mesh)

        lk = left.reshape(-1)
        rk = right.reshape(-1)
        got = sorted(zip(lk[li].tolist(), rk[ri].tolist()))
        expected = sorted((a, b) for a in lk for b in rk if a == b)
        assert got == expected
        np.testing.assert_array_equal(lk[li], rk[ri])

    def test_ragged_shards(self, mesh):
        rng = np.random.default_rng(6)
        D = 8
        left = [rng.integers(0, 30, size=int(rng.integers(1, 50))) * D + d
                for d in range(D)]
        right = [rng.integers(0, 30, size=int(rng.integers(1, 70))) * D + d
                 for d in range(D)]
        dev, ll, rl = copartitioned_join_ragged(left, right, mesh)
        got = sorted((int(left[d][a]), int(right[d][b]))
                     for d, a, b in zip(dev, ll, rl))
        expected = sorted((int(a), int(b))
                          for d in range(D)
                          for a in left[d] for b in right[d] if a == b)
        assert got == expected

    def test_padding_never_matches_nan_or_inf_keys(self, mesh):
        """Regression: padding slots are excluded by validity, not sentinel
        values — a valid inf/NaN key must not pull padding into its match
        window (the sentinel approach returned out-of-range right indices)."""
        left = [np.array([np.inf])] + [np.array([float(d)]) for d in range(1, 8)]
        right = [np.array([np.inf, np.nan])] + \
            [np.array([float(d)] * 4) for d in range(1, 8)]
        dev, ll, rl = copartitioned_join_ragged(left, right, mesh)
        for d, a, b in zip(dev, ll, rl):
            assert a < len(left[d]) and b < len(right[d])
        got = sorted((int(d), int(a), int(b)) for d, a, b in zip(dev, ll, rl))
        expected = sorted((d, a, b)
                          for d in range(8)
                          for a, lv in enumerate(left[d])
                          for b, rv in enumerate(right[d]) if lv == rv)
        assert got == expected

    def test_no_matches(self, mesh):
        left = np.zeros((8, 4), np.int64)
        right = np.ones((8, 4), np.int64)
        li, ri = copartitioned_join(left, right, mesh)
        assert li.size == 0 and ri.size == 0


class TestMeshFilter:
    def test_mask_parity_with_single_device(self, mesh):
        """The sharded elementwise program must produce the identical mask,
        including with a row count not divisible by the device count."""
        import jax

        from hyperspace_tpu.ops.filter import compile_predicate
        from hyperspace_tpu.parallel import eval_predicate_on_mesh
        from hyperspace_tpu.plan.expr import col, lit

        expr = (col("a") >= lit(100)) & (col("b") < lit(0.5))
        fn, literals = compile_predicate(expr, ["a", "b"])
        rng = np.random.default_rng(5)
        n = 10_003  # deliberately not a multiple of 8
        a = rng.integers(0, 200, n)
        b = rng.random(n)
        with _enable_x64():
            want = np.asarray(fn([a, b], literals))
            got = eval_predicate_on_mesh(fn, [a, b], literals, mesh)
        np.testing.assert_array_equal(got, want)
        assert got.shape == (n,)

    def test_executor_routes_large_filters_to_mesh(self, tmp_path,
                                                   monkeypatch):
        """Above mesh_filter_min_rows with >1 device, the filter evaluates
        through the sharded path — with exact answers."""
        import pyarrow.parquet as pq

        from hyperspace_tpu import HyperspaceSession, col
        from hyperspace_tpu.parallel import filter as mesh_filter

        calls = []
        real = mesh_filter.eval_predicate_on_mesh

        def spy(fn, cols, lits, mesh=None):
            calls.append(len(cols))
            return real(fn, cols, lits, mesh)

        monkeypatch.setattr(mesh_filter, "eval_predicate_on_mesh", spy)
        d = tmp_path / "data"
        d.mkdir()
        n = 5_000
        pq.write_table(pa.table({
            "k": pa.array(np.arange(n, dtype=np.int64)),
            "v": pa.array(np.arange(n, dtype=np.int64) * 2),
        }), str(d / "p.parquet"))
        s = HyperspaceSession(system_path=str(tmp_path / "ix"))
        s.conf.device_filter_min_rows = 1
        s.conf.mesh_filter_min_rows = 1
        ds = s.read.parquet(str(d)).filter(col("k") >= 4_990).select("k", "v")
        out = ds.collect()
        assert calls, "mesh filter path did not fire"
        assert out.num_rows == 10
        assert out.column("k").to_pylist() == list(range(4_990, 5_000))


class TestDistributedCreate:
    def test_create_action_uses_mesh_and_answers_match(self, tmp_path):
        """End-to-end: index built with parallel_build=on over 8 CPU devices
        must produce the same query answers as the single-chip build."""
        import pyarrow.parquet as pq

        from hyperspace_tpu import (
            Hyperspace,
            HyperspaceSession,
            IndexConfig,
            col,
            lit,
        )

        rng = np.random.default_rng(7)
        src = tmp_path / "src"
        src.mkdir()
        table = pa.table({
            "id": rng.integers(0, 1_000, size=5_000),
            "name": pa.array([f"name-{i % 97}" for i in range(5_000)]),
        })
        pq.write_table(table, str(src / "part-0.parquet"))

        session = HyperspaceSession(system_path=str(tmp_path / "indexes"))
        session.conf.num_buckets = 8
        session.conf.parallel_build = "on"
        hs = Hyperspace(session)
        df = session.read.parquet(str(src / "part-0.parquet"))
        hs.create_index(df, IndexConfig("idx", ["id"], ["name"]))

        session.enable_hyperspace()
        q = df.filter(col("id") == lit(500)).select("id", "name")
        with_index = q.collect().to_pandas().sort_values("name").reset_index(drop=True)
        session.disable_hyperspace()
        without = q.collect().to_pandas().sort_values("name").reset_index(drop=True)
        assert with_index.equals(without)

    def test_zorder_build_under_mesh_keeps_global_layout(self, tmp_path):
        """With parallel_build=on, a zorder build must NOT take the hash
        shuffle (it would fragment the curve into per-partition samples and
        gut pruning, or with one logical bucket send every row to one
        device): the layout is the host argsort of the global Morton codes
        — identical on 1 chip or N — written as bucket 0, and
        second-dimension sketch pruning keeps its power."""
        import pyarrow.parquet as pq

        from hyperspace_tpu import Hyperspace, HyperspaceSession, IndexConfig, col
        from hyperspace_tpu.io.parquet import bucket_id_of_file

        rng = np.random.default_rng(8)
        src = tmp_path / "src"
        src.mkdir()
        n = 8_000
        pq.write_table(pa.table({
            "x": pa.array(rng.integers(0, 1 << 16, n), type=pa.int64()),
            "y": pa.array(rng.random(n) * 1000),
        }), str(src / "part-0.parquet"))
        session = HyperspaceSession(system_path=str(tmp_path / "indexes"))
        session.conf.parallel_build = "on"
        session.conf.index_max_rows_per_file = n // 64
        hs = Hyperspace(session)
        df = session.read.parquet(str(src))
        hs.create_index(df, IndexConfig("zd", ["x", "y"], layout="zorder"))
        entry = session.index_collection_manager.get_index("zd")
        assert entry.num_buckets == 1
        files = [f.name for f in entry.content.file_infos()]
        assert all(bucket_id_of_file(f) == 0 for f in files)
        session.enable_hyperspace()
        q = (df.filter((col("y") >= 100.0) & (col("y") < 150.0))
             .select("x", "y"))
        plan = q.optimized_plan()
        scans = [s for s in plan.leaf_relations() if s.relation.index_scan_of]
        assert scans, plan.tree_string()
        kept, total = scans[0].relation.data_skipping_stats
        assert kept < total
        got = q.collect()
        session.disable_hyperspace()
        keys = [("x", "ascending"), ("y", "ascending")]
        assert got.sort_by(keys).equals(q.collect().sort_by(keys))


class TestMeshBucketedJoin:
    def _indexed_pair(self, tmp_path, n=4000):
        import os

        import pyarrow.parquet as pq

        from hyperspace_tpu import Hyperspace, HyperspaceSession, IndexConfig

        rng = np.random.default_rng(12)
        ld, rd = str(tmp_path / "l"), str(tmp_path / "r")
        ldf = {"k": rng.integers(0, 500, n).astype(np.int64),
               "lv": rng.random(n)}
        rdf = {"k": np.arange(500, dtype=np.int64),
               "rv": rng.random(500)}
        for d, data in ((ld, ldf), (rd, rdf)):
            os.makedirs(d)
            pq.write_table(pa.table(data), os.path.join(d, "p.parquet"))
        s = HyperspaceSession(system_path=str(tmp_path / "ix"))
        s.conf.num_buckets = 8
        hs = Hyperspace(s)
        hs.create_index(s.read.parquet(ld), IndexConfig("ml", ["k"], ["lv"]))
        hs.create_index(s.read.parquet(rd), IndexConfig("mr", ["k"], ["rv"]))
        s.enable_hyperspace()
        return s, ld, rd

    def test_executor_dispatches_query_join_over_mesh(self, tmp_path):
        """With 8 devices and the threshold lowered, the EXECUTOR routes a
        rewritten bucket-aligned join through copartitioned_join_ragged —
        and the result matches the host-pool path exactly."""
        from hyperspace_tpu import col

        s, ld, rd = self._indexed_pair(tmp_path)

        def q():
            return (s.read.parquet(ld)
                    .join(s.read.parquet(rd), col("k") == col("k"))
                    .select("k", "lv", "rv"))

        s.conf.mesh_join_min_rows = 1
        mesh_out = q().collect()
        mesh_stats = s.last_execution_stats
        assert [j["strategy"] for j in mesh_stats["joins"]] \
            == ["bucketed-mesh"], mesh_stats
        assert mesh_stats["joins"][0]["devices"] == 8

        s.conf.mesh_join_min_rows = 1 << 60
        host_out = q().collect()
        host_stats = s.last_execution_stats
        assert [j["strategy"] for j in host_stats["joins"]] == ["bucketed"]

        keys = [(c, "ascending") for c in ("k", "lv", "rv")]
        assert mesh_out.sort_by(keys).equals(host_out.sort_by(keys))
        assert mesh_out.num_rows > 0

    def test_below_threshold_probe_reuses_materialized_buckets(self, tmp_path):
        """A below-threshold mesh probe must not re-execute bucket plans on
        the host path (scan stats record each bucket's files exactly once
        per side)."""
        from hyperspace_tpu import col

        s, ld, rd = self._indexed_pair(tmp_path)
        s.conf.mesh_join_min_rows = 1 << 60  # probe materializes, falls back
        ds = (s.read.parquet(ld)
              .join(s.read.parquet(rd), col("k") == col("k"))
              .select("k", "lv", "rv"))
        out = ds.collect()
        stats = s.last_execution_stats
        assert [j["strategy"] for j in stats["joins"]] == ["bucketed"]
        assert out.num_rows > 0
        # 8 buckets per side; each executed once (no duplicate scans).
        index_scans = [sc for sc in stats["scans"] if sc["is_index"]]
        assert len(index_scans) == 16, stats["scans"]


class TestHierarchicalShuffle:
    """Two-stage (DCN then ICI) shuffle over a 2-axis mesh — must be
    bit-identical to the flat 1-axis shuffle on the same devices."""

    @pytest.mark.parametrize("pallas", ["off", "on"])
    @pytest.mark.parametrize("shape", [(2, 4), (4, 2), (8, 1), (1, 8)])
    def test_matches_flat_shuffle(self, mesh, shape, pallas, monkeypatch):
        monkeypatch.setenv("HYPERSPACE_TPU_PALLAS", pallas)
        from hyperspace_tpu.parallel import (
            build_mesh_2d,
            hierarchical_bucket_shuffle,
        )

        rng = np.random.default_rng(5)
        n = 512
        keys = pa.array(rng.integers(-1000, 1000, n), type=pa.int64())
        hw = [np.asarray(columnar.to_hash_words(keys))]
        ow = [np.asarray(columnar.to_order_words(keys))]
        payload = rng.integers(0, 2**32, (n, 3), dtype=np.uint32)
        flat, flat_pl = bucket_shuffle(hw, ow, 16, mesh,
                                       payload_words=payload)
        mesh2d = build_mesh_2d(shape[0], shape[1])
        hier, hier_pl = hierarchical_bucket_shuffle(hw, ow, 16, mesh2d,
                                                    payload_words=payload)
        np.testing.assert_array_equal(flat.perm, hier.perm)
        np.testing.assert_array_equal(flat.buckets_sorted,
                                      hier.buckets_sorted)
        np.testing.assert_array_equal(flat.device_row_counts,
                                      hier.device_row_counts)
        np.testing.assert_array_equal(flat_pl, hier_pl)

    def test_overflow_retry_with_skew(self):
        """Every row hashes to ONE bucket: both stage buffers overflow at
        the balanced estimate and must retry to completion."""
        from hyperspace_tpu.parallel import (
            build_mesh_2d,
            hierarchical_bucket_shuffle,
        )

        n = 256
        keys = pa.array(np.full(n, 42), type=pa.int64())
        hw = [np.asarray(columnar.to_hash_words(keys))]
        ow = [np.asarray(columnar.to_order_words(keys))]
        mesh2d = build_mesh_2d(2, 4)
        result, _ = hierarchical_bucket_shuffle(hw, ow, 16, mesh2d)
        assert result.perm.shape[0] == n
        assert np.array_equal(np.sort(result.perm), np.arange(n))
        # One bucket -> one owning device holds every row.
        assert sorted(result.device_row_counts, reverse=True)[0] == n

    def test_zero_rows(self):
        from hyperspace_tpu.parallel import (
            build_mesh_2d,
            hierarchical_bucket_shuffle,
        )

        hw = [np.zeros((0, 2), np.uint32)]
        ow = [np.zeros((0, 2), np.uint32)]
        result, _ = hierarchical_bucket_shuffle(hw, ow, 8,
                                                build_mesh_2d(2, 4))
        assert result.perm.shape[0] == 0
        assert result.device_row_counts.sum() == 0

    def test_rejects_wrong_mesh(self, mesh):
        from hyperspace_tpu.parallel import hierarchical_bucket_shuffle

        with pytest.raises(ValueError, match="dcn"):
            hierarchical_bucket_shuffle(
                [np.zeros((4, 2), np.uint32)],
                [np.zeros((4, 2), np.uint32)], 8, mesh)
