"""Mesh-sharded kernel suite: the rule-driven sharding layer, the
sharded build route, the bucket-owned query kernels, and the executor's
mesh dispatch.

Consolidates the ``dryrun_multichip`` smoke (formerly in
tests/test_graft_entry.py) with proper unit coverage: rule-table units,
shard/gather round-trips, per-device bucket-ownership bit-equality
against the host mirrors, the locked-XLA-flags subprocess fallback, and
the acceptance loop — ``mesh.enabled`` on vs off produces byte-identical
index data (per-bucket sha256) and equal query answers.

The conftest provisions a virtual 8-device CPU mesh, so every in-process
test exercises real shardings.
"""

import hashlib
import os
import subprocess
import sys
from collections import defaultdict

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from hyperspace_tpu import Hyperspace, HyperspaceSession, IndexConfig, col
from hyperspace_tpu.io import columnar
from hyperspace_tpu.io.columnar import split_words64
from hyperspace_tpu.io.parquet import bucket_id_of_file
from hyperspace_tpu.ops.hash import bucket_ids_np, route_partition_np
from hyperspace_tpu.parallel.mesh import (
    PARTITION_RULES,
    SHARD_AXIS,
    active_mesh,
    build_mesh,
    make_shard_and_gather_fns,
    match_partition_rules,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Rule table
# ---------------------------------------------------------------------------
class TestPartitionRules:
    def test_data_planes_shard_rowwise(self):
        specs = match_partition_rules(
            ("hash_words", "order_words", "row_words", "valid",
             "key_words", "value_cols"))
        for name, spec in specs.items():
            assert spec == __import__("jax").sharding.PartitionSpec(
                SHARD_AXIS), name

    def test_per_device_planes_shard(self):
        specs = match_partition_rules(("counts", "overflow", "n_valid"))
        import jax

        for spec in specs.values():
            assert spec == jax.sharding.PartitionSpec(SHARD_AXIS)

    def test_unknown_names_replicate_via_catchall(self):
        import jax

        specs = match_partition_rules(("some_threshold",))
        assert specs["some_threshold"] == jax.sharding.PartitionSpec()

    def test_first_match_wins(self):
        import jax

        P = jax.sharding.PartitionSpec
        rules = ((r"^x$", P()), (r".", P(SHARD_AXIS)))
        specs = match_partition_rules(("x", "y"), rules)
        assert specs["x"] == P()
        assert specs["y"] == P(SHARD_AXIS)

    def test_no_match_raises_without_catchall(self):
        import jax

        P = jax.sharding.PartitionSpec
        with pytest.raises(ValueError, match="No partition rule"):
            match_partition_rules(("zzz",), ((r"^x$", P()),))

    def test_catalog_covers_engine_planes(self):
        # The shipped table must place every plane the kernels use.
        names = ("hash_words", "order_words", "row_words", "valid",
                 "payload", "counts", "overflow", "n_valid",
                 "key_words", "value_cols")
        specs = match_partition_rules(names, PARTITION_RULES)
        assert set(specs) == set(names)


# ---------------------------------------------------------------------------
# Shard / gather fns
# ---------------------------------------------------------------------------
class TestShardGather:
    def test_round_trip_bit_equal(self):
        mesh = build_mesh(8)
        rng = np.random.default_rng(0)
        arr = rng.integers(0, 2**32, size=(64, 2), dtype=np.uint32)
        specs = match_partition_rules(("hash_words",))
        shard_fns, gather_fns = make_shard_and_gather_fns(mesh, specs)
        sharded = shard_fns["hash_words"](arr)
        assert sharded.sharding.is_fully_replicated is False
        back = gather_fns["hash_words"](sharded)
        assert np.array_equal(back, arr)

    def test_shard_places_one_slice_per_device(self):
        mesh = build_mesh(8)
        arr = np.arange(8 * 4, dtype=np.uint32).reshape(32, 1)
        shard_fns, _ = make_shard_and_gather_fns(
            mesh, match_partition_rules(("valid",)))
        sharded = shard_fns["valid"](arr)
        starts = sorted((s.index[0].start or 0)
                        for s in sharded.addressable_shards)
        assert starts == [i * 4 for i in range(8)]

    def test_gather_routes_through_sync_guard(self):
        # The gather seam must be the attributed pull: under the armed
        # runtime guard a raw conversion would raise, the seam must not.
        from hyperspace_tpu.execution import sync_guard

        class _Conf:
            device_guard_enabled = True

        mesh = build_mesh(8)
        arr = np.arange(16, dtype=np.uint32)
        shard_fns, gather_fns = make_shard_and_gather_fns(
            mesh, match_partition_rules(("valid",)))
        sharded = shard_fns["valid"](arr)
        sync_guard.arm(_Conf())
        try:
            out = gather_fns["valid"](sharded)
        finally:
            sync_guard.arm(type("C", (), {"device_guard_enabled": False})())
        assert np.array_equal(out, arr)


# ---------------------------------------------------------------------------
# active_mesh conf gate
# ---------------------------------------------------------------------------
class TestActiveMesh:
    def _conf(self, **kw):
        from hyperspace_tpu.config import HyperspaceConf

        c = HyperspaceConf()
        for k, v in kw.items():
            setattr(c, k, v)
        return c

    def test_auto_spans_local_devices(self):
        mesh = active_mesh(self._conf())
        assert mesh is not None
        assert mesh.devices.size == 8

    def test_off_disables(self):
        assert active_mesh(self._conf(mesh_enabled="off")) is None
        assert active_mesh(self._conf(mesh_enabled="false")) is None

    def test_max_devices_caps_span(self):
        mesh = active_mesh(self._conf(mesh_max_devices=4))
        assert mesh is not None and mesh.devices.size == 4

    def test_one_device_cap_means_no_mesh(self):
        assert active_mesh(self._conf(mesh_max_devices=1)) is None
        assert active_mesh(self._conf(mesh_enabled="on",
                                      mesh_max_devices=1)) is None

    def test_invalid_mode_raises(self):
        from hyperspace_tpu.exceptions import HyperspaceError

        with pytest.raises(HyperspaceError):
            active_mesh(self._conf(mesh_enabled="sideways"))


# ---------------------------------------------------------------------------
# Sharded route+partition: bit-equality + ownership
# ---------------------------------------------------------------------------
class TestMeshRoutePartition:
    @pytest.mark.parametrize("pallas", ["off", "on"])
    @pytest.mark.parametrize("n", [8, 37, 1000, 4096])
    def test_bit_equal_vs_host_mirror(self, n, pallas, monkeypatch):
        from hyperspace_tpu.parallel.sharded_build import (
            mesh_route_partition,
        )

        # "on": the pallas hash kernel inside shard_map (interpret mode
        # here, Mosaic on a TPU).
        monkeypatch.setenv("HYPERSPACE_TPU_PALLAS", pallas)

        rng = np.random.default_rng(n)
        mesh = build_mesh(8)
        hw = [rng.integers(0, 2**32, size=(n, 2), dtype=np.uint32)
              for _ in range(2)]
        codes = [rng.integers(0, 2**64, size=n, dtype=np.uint64)
                 for _ in range(2)]
        b_np, p_np = route_partition_np(hw, codes, 16)
        b_mesh, p_mesh = mesh_route_partition(
            hw, [split_words64(c) for c in codes], 16, mesh, pad_to=64)
        assert np.array_equal(b_np, b_mesh)
        assert np.array_equal(p_np, p_mesh)

    def test_grouped_only_mode_bit_equal(self):
        # Rank-mapped key types route grouped-only (no order words):
        # original row order within bucket must survive the mesh.
        from hyperspace_tpu.parallel.sharded_build import (
            mesh_route_partition,
        )

        rng = np.random.default_rng(5)
        mesh = build_mesh(8)
        hw = [rng.integers(0, 2**32, size=(513, 2), dtype=np.uint32)]
        b_np, p_np = route_partition_np(hw, [], 12)
        b_mesh, p_mesh = mesh_route_partition(hw, [], 12, mesh, pad_to=64)
        assert np.array_equal(b_np, b_mesh)
        assert np.array_equal(p_np, p_mesh)

    def test_one_gather_pull_per_device(self):
        from hyperspace_tpu.parallel.sharded_build import (
            mesh_route_partition,
        )
        from hyperspace_tpu.telemetry import metrics

        rng = np.random.default_rng(9)
        mesh = build_mesh(8)
        hw = [rng.integers(0, 2**32, size=(256, 2), dtype=np.uint32)]
        before = metrics.snapshot().get("exec.mesh.gather.pulls", 0)
        mesh_route_partition(hw, [], 16, mesh, pad_to=64)
        after = metrics.snapshot().get("exec.mesh.gather.pulls", 0)
        assert after - before == 8

    def test_mod_ownership_covers_every_bucket(self):
        # bucket % n_devices is the ownership the route writes with: the
        # permutation's bucket runs must come out ascending (the stable
        # host merge), proving no bucket was split across owners.
        from hyperspace_tpu.parallel.sharded_build import (
            mesh_route_partition,
        )

        rng = np.random.default_rng(11)
        mesh = build_mesh(8)
        hw = [rng.integers(0, 2**32, size=(512, 2), dtype=np.uint32)]
        buckets, perm = mesh_route_partition(hw, [], 20, mesh, pad_to=64)
        sorted_buckets = buckets[perm]
        assert np.all(np.diff(sorted_buckets) >= 0)
        assert np.array_equal(np.sort(perm), np.arange(512))
        assert np.array_equal(buckets, bucket_ids_np(hw, 20))


# ---------------------------------------------------------------------------
# Bucket-owned mesh kernels (join / aggregate / join+agg)
# ---------------------------------------------------------------------------
class TestMeshQueryKernels:
    def test_sorted_equi_join_mesh_matches_host(self):
        from hyperspace_tpu.ops.join import (
            sorted_equi_join_mesh,
            sorted_equi_join_np,
        )

        rng = np.random.default_rng(3)
        mesh = build_mesh(8)
        lk = rng.integers(0, 200, size=4_000).astype(np.int64)
        rk = rng.integers(0, 200, size=1_500).astype(np.int64)
        li_h, ri_h = sorted_equi_join_np(lk, rk)
        li_m, ri_m = sorted_equi_join_mesh(lk, rk, mesh)
        host = sorted(zip(li_h.tolist(), ri_h.tolist()))
        meshp = sorted(zip(li_m.tolist(), ri_m.tolist()))
        assert host == meshp

    def test_mesh_grouped_aggregate_matches_single_device(self):
        from hyperspace_tpu.ops.aggregate import (
            grouped_aggregate,
            grouped_aggregate_mesh,
        )

        rng = np.random.default_rng(4)
        mesh = build_mesh(8)
        n = 4_000
        keys = rng.integers(0, 113, size=n).astype(np.int64)
        ints = rng.integers(0, 10_000, size=n).astype(np.int64)
        floats = rng.random(n)
        kw = [np.asarray(columnar.to_order_words(
            pa.chunked_array([pa.array(keys)])))]
        ops = ["sum", "count_all", "min", "max", "mean"]
        vals = [ints, ints, ints, floats]
        f1, c1, r1 = grouped_aggregate(kw, vals, ops)
        f2, c2, r2 = grouped_aggregate_mesh(kw, vals, ops, mesh,
                                            pad_to=64)
        assert np.array_equal(np.asarray(f1), np.asarray(f2))
        assert np.array_equal(np.asarray(c1), np.asarray(c2))
        for a, b in zip(r1, r2):
            a, b = np.asarray(a), np.asarray(b)
            if a.dtype.kind == "f":
                assert np.allclose(a, b, rtol=1e-12)
            else:
                assert np.array_equal(a, b)

    def test_join_group_aggregate_mesh_matches_fused(self):
        from hyperspace_tpu.ops.filter import build_value_fn
        from hyperspace_tpu.ops.join_agg import (
            join_group_aggregate,
            join_group_aggregate_mesh,
        )
        from hyperspace_tpu.plan.expr import Col

        rng = np.random.default_rng(6)
        mesh = build_mesh(8)
        n_l, n_r = 3_000, 500
        l_key = rng.integers(0, 400, size=n_l).astype(np.int64)
        r_key = np.arange(400, dtype=np.int64)
        group = rng.integers(0, 7, size=n_r).astype(np.int64)
        qty = rng.integers(1, 50, size=n_l).astype(np.int64)
        columns = [l_key, qty, r_key, group]
        sides = ["l", "l", "r", "r"]
        fn, lits = build_value_fn(Col("qty"),
                                  ["l_key", "qty", "r_key", "group"])
        f1 = join_group_aggregate(
            l_key, r_key, columns, sides, [3], ["sum", "count_all"],
            [fn], [lits])
        f2 = join_group_aggregate_mesh(
            l_key, r_key, columns, sides, [3], ["sum", "count_all"],
            [fn], [lits], mesh, pad_to=64)
        # Same group-key values in the same (ascending-key) order with
        # the same exact integer reductions.
        assert np.array_equal(np.asarray(f1[2]), np.asarray(f2[2]))
        assert np.array_equal(np.asarray(f1[2][0]),
                              np.unique(group[np.unique(l_key)]))
        assert np.array_equal(np.asarray(f1[0]), np.asarray(f2[0]))
        for a, b in zip(f1[1], f2[1]):
            assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_mesh_kernels_attribute_per_device(self):
        # kernel_end(devices=...) must land one exec.device.<id>.kernel_ms
        # counter per mesh device (the per-device skew view).
        from hyperspace_tpu.ops.join import sorted_equi_join_mesh
        from hyperspace_tpu.telemetry import metrics, timeline

        rng = np.random.default_rng(8)
        mesh = build_mesh(8)
        lk = rng.integers(0, 50, size=512).astype(np.int64)
        rk = rng.integers(0, 50, size=512).astype(np.int64)
        timeline.enable_timeline()
        try:
            before = metrics.snapshot()
            sorted_equi_join_mesh(lk, rk, mesh)
            after = metrics.snapshot()
        finally:
            timeline.disable_timeline()
        for dev in range(8):
            key = f"exec.device.{dev}.kernel_ms"
            assert after.get(key, 0) > before.get(key, 0), key


# ---------------------------------------------------------------------------
# End-to-end: sharded build + executor dispatch, mesh on vs off
# ---------------------------------------------------------------------------
def _write_source(tmp_path, n=6_000, files=4, string_keys=False):
    rng = np.random.default_rng(42)
    src = tmp_path / "src"
    src.mkdir(exist_ok=True)
    if string_keys:
        k = pa.array([f"k-{v:05d}" for v in
                      rng.integers(0, n // 4, size=n)])
    else:
        k = pa.array(rng.integers(0, n // 4, size=n), type=pa.int64())
    table = pa.table({
        "k": k,
        "g": pa.array(rng.integers(0, 9, size=n), type=pa.int64()),
        "v": pa.array(rng.integers(0, 1000, size=n), type=pa.int64()),
    })
    step = -(-n // files)
    for f in range(files):
        pq.write_table(table.slice(f * step, step),
                       str(src / f"part-{f:05d}.parquet"))
    return str(src)


def _spill_session(tmp_path, name, mesh_enabled):
    s = HyperspaceSession(system_path=str(tmp_path / name))
    s.conf.num_buckets = 16
    s.conf.device_batch_rows = 1024      # force the spill path
    s.conf.device_build_min_rows = 0     # force the device/mesh route
    s.conf.mesh_enabled = mesh_enabled
    return s


def _bucket_digests(session, index_name):
    entry = session.index_collection_manager.get_index(index_name)
    out = defaultdict(list)
    for f in entry.content.file_infos():
        with open(f.name, "rb") as fh:
            out[bucket_id_of_file(f.name)].append(
                hashlib.sha256(fh.read()).hexdigest())
    return {b: sorted(d) for b, d in out.items()}


class TestMeshBuildEndToEnd:
    def test_sharded_spill_build_bit_equal_per_bucket_sha256(self, tmp_path):
        """THE acceptance loop: the mesh-sharded spill build's index tree
        is byte-identical to mesh.enabled=off (per-bucket sha256)."""
        src = _write_source(tmp_path)
        digests = {}
        for mode in ("off", "auto"):
            s = _spill_session(tmp_path, f"ix_{mode}", mode)
            hs = Hyperspace(s)
            hs.create_index(s.read.parquet(src),
                            IndexConfig("mx", ["k"], ["g", "v"]))
            report = hs.last_build_report()
            assert report.spill_bytes > 0, "build did not spill"
            if mode == "auto":
                assert report.mesh_devices == 8
                assert report.to_dict()["device_kernel_ms"], \
                    "per-device kernel ms missing from the report"
            else:
                assert report.mesh_devices == 0
            digests[mode] = _bucket_digests(s, "mx")
        assert digests["off"] == digests["auto"]

    def test_string_key_build_bit_equal(self, tmp_path):
        # Rank-mapped keys take the grouped-only route; the mesh must
        # preserve the chunk-order tie contract the finalize re-sort
        # depends on.
        src = _write_source(tmp_path, string_keys=True)
        digests = {}
        for mode in ("off", "auto"):
            s = _spill_session(tmp_path, f"sx_{mode}", mode)
            hs = Hyperspace(s)
            hs.create_index(s.read.parquet(src),
                            IndexConfig("sx", ["k"], ["v"]))
            digests[mode] = _bucket_digests(s, "sx")
        assert digests["off"] == digests["auto"]

    def test_serial_pipeline_and_mesh_agree(self, tmp_path):
        # Three-way: forced-serial single-device, pipelined single-device,
        # pipelined mesh — one layout.
        src = _write_source(tmp_path, n=4_000)
        digests = {}
        for tag, mesh_mode, pipelined in (
                ("serial", "off", False), ("piped", "off", True),
                ("mesh", "auto", True)):
            s = _spill_session(tmp_path, f"tx_{tag}", mesh_mode)
            s.conf.build_pipeline_enabled = pipelined
            hs = Hyperspace(s)
            hs.create_index(s.read.parquet(src),
                            IndexConfig("tx", ["k"], ["v"]))
            digests[tag] = _bucket_digests(s, "tx")
        assert digests["serial"] == digests["piped"] == digests["mesh"]

    def test_ledger_record_carries_device_kernel_ms(self, tmp_path):
        src = _write_source(tmp_path, n=3_000)
        s = _spill_session(tmp_path, "lx", "auto")
        hs = Hyperspace(s)
        hs.create_index(s.read.parquet(src),
                        IndexConfig("lx", ["k"], ["v"]))
        records = hs.perf_history().to_pylist()
        import json as _json

        mine = [r for r in records if "lx" in r.get("name", "")]
        assert mine, "no ledger record for the build"
        rec = _json.loads(mine[-1]["recordJson"])
        assert rec.get("device_kernel_ms"), rec.keys()
        assert rec.get("properties", {}).get("mesh_devices") == 8


class TestExecutorMeshDispatch:
    @pytest.fixture()
    def env(self, tmp_path):
        src = _write_source(tmp_path, n=5_000)
        s = HyperspaceSession(system_path=str(tmp_path / "ix"))
        s.conf.num_buckets = 16
        hs = Hyperspace(s)
        df = s.read.parquet(src)
        hs.create_index(df, IndexConfig("qx", ["k"], ["g", "v"]))
        s.enable_hyperspace()
        return s, df

    def test_mesh_aggregate_strategy_and_answers(self, env):
        s, df = env
        q = lambda: df.group_by("g").agg(  # noqa: E731
            sv=("v", "sum"), c=("", "count_all")).collect()
        s.conf.mesh_agg_min_rows = 1
        s.conf.device_agg_min_rows = 0
        mesh_out = q()
        strategies = [a["strategy"]
                      for a in s.last_execution_stats["aggregates"]]
        assert "mesh-segment" in strategies, strategies
        s.conf.mesh_enabled = "off"
        host_out = q()
        strategies = [a["strategy"]
                      for a in s.last_execution_stats["aggregates"]]
        assert "mesh-segment" not in strategies, strategies
        keys = [("g", "ascending")]
        assert mesh_out.sort_by(keys).equals(host_out.sort_by(keys))

    def test_mesh_off_answers_match_pre_change_path(self, env):
        # mesh.enabled=off must reproduce the single-device path's
        # answers byte-for-byte (arrow equality) on a filter query.
        s, df = env
        q = lambda: df.filter(col("v") < 500).collect()  # noqa: E731
        s.conf.mesh_enabled = "off"
        base = q()
        s.conf.mesh_enabled = "auto"
        s.conf.mesh_filter_min_rows = 1
        s.conf.device_filter_min_rows = 0
        meshed = q()
        assert [f["strategy"]
                for f in s.last_execution_stats["filters"]] \
            == ["device-mesh"]
        assert meshed.equals(base)


# ---------------------------------------------------------------------------
# dryrun_multichip smoke (moved from tests/test_graft_entry.py) + the
# locked-XLA-flags subprocess fallback
# ---------------------------------------------------------------------------
def _run_dryrun(code: str, extra_env=None) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    # Simulate the driver: no pytest conftest, no pre-set virtual mesh;
    # the child is pinned to the CPU so it never contends for a chip.
    env.pop("XLA_FLAGS", None)
    env.pop("HS_DEVICE_BATCH_ROWS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=600)


def test_dryrun_multichip_fresh_process():
    r = _run_dryrun("import __graft_entry__ as g; g.dryrun_multichip(8)")
    assert r.returncode == 0, r.stderr[-2000:]


def test_dryrun_multichip_after_backend_init():
    # entry() may have initialized the backend first; the dryrun must
    # still provision the 8-device CPU mesh.
    r = _run_dryrun(
        "import jax\n"
        "import __graft_entry__ as g\n"
        "jax.devices()\n"
        "g.dryrun_multichip(8)\n")
    assert r.returncode == 0, r.stderr[-2000:]


def test_dryrun_multichip_locked_xla_flags_falls_back_to_subprocess():
    """A process whose XLA flags were LOCKED at 2 devices (first backend
    init) cannot re-provision 8 in-process on every jax version; the
    dryrun must detect the shortfall and complete via its fresh-child
    fallback instead of failing."""
    r = _run_dryrun(
        "import os\n"
        "os.environ['XLA_FLAGS'] = "
        "'--xla_force_host_platform_device_count=2'\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "assert len(jax.devices()) == 2\n"
        "import __graft_entry__ as g\n"
        "g.dryrun_multichip(8)\n",
        extra_env={"JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[-2000:]
