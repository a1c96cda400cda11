"""The cells added beside ``test_cells.py``'s on the CPU at a tiny size:
``sf1_q3_dates`` (a fresh DATE per query) and ``sf1_rf1_hybrid_q3`` (Q3
over Delta tables that an ingest job appends to while the index stays
unrefreshed).  The engine agrees with the reference, the control (the
reference in float32) fails, and each planted fault of the hybrid path
fails the number that guards against it."""

import json
import time

import pytest

import harness
from conftest import TINY

# At TINY an RF1 batch is 3 orders, in a file whose footer outweighs its
# rows: a backlog of 3 batches of 100 orders, and 3 more in the window,
# stays under the hybrid scan's appended-bytes ratio (so every answer
# reads through it, as at SF1) and moves Q3's top ten.
HYBRID_TINY = {"backlog_batches": 3, "orders_per_sf": 50_000,
               "commit_interval_s": 1.0}


def _run(cell, seconds=3.0, **kw):
    kw.setdefault("overrides", TINY)
    if cell == "sf1_rf1_hybrid_q3":
        kw.setdefault("traffic_overrides", HYBRID_TINY)
    return harness.run_cell(cell, 2**31 + 17, seconds, False,
                            started=time.perf_counter(), **kw)


@pytest.mark.parametrize("cell", ["sf1_q3_dates", "sf1_rf1_hybrid_q3"])
def test_engine_agrees_and_control_fails(cell, capsys):
    r = _run(cell, control=True)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert not harness._within(r["control_checks"]), r["control_checks"]
    reported = {m["name"] for m in harness.load_benchmark()["end_to_end"]
                if harness._applies(m, cell)}
    assert set(r["metrics"]) == reported == {"analytic_qps", "setup_s"}
    logged = {}
    for line in capsys.readouterr().out.splitlines():
        logged.update(json.loads(line))
    if cell == "sf1_rf1_hybrid_q3":
        # Both indexes answer with the appended files, on the fused
        # path, and the window reads several versions.
        assert logged["executor"]["index_scans"] == 2
        assert logged["executor"]["joins"] == ["device-fused-agg"]
        assert logged["window"]["versions_read"] > 1
        assert logged["window"]["appended_files_per_query"] > 0


def test_plan_cache_that_ignores_versions_fails_stale(monkeypatch):
    """Cached plans served across commits: each answer is the old
    version's, so it is stale, though right for the version it states."""
    from hyperspace_tpu.execution.plan_cache import PlanCache

    real = PlanCache.get

    def ignoring(self, key, versions=None):
        entry = self._lru.peek(key)
        return real(self, key, versions if entry is None else entry[3])

    monkeypatch.setattr(PlanCache, "get", ignoring)
    r = _run("sf1_rf1_hybrid_q3")
    assert r["checks"]["q3_stale_answers"]["value"] > 0, r["checks"]
    assert not r["correct"]


# The cell's own RF1 batches (1,500 orders per SF): a batch seldom moves
# Q3's top ten, so only the witness of rows read can tell a stale plan.
HYBRID_TINY_RF1 = {"backlog_batches": 4, "orders_per_sf": 1500,
                   "commit_interval_s": 0.5}


def test_cached_plan_under_a_fresh_label_fails_unstated(monkeypatch,
                                                        capsys):
    """Cached plans served across commits, each answer labelled with the
    version resolved for the query rather than the one its plan read."""
    from hyperspace_tpu.execution.plan_cache import PlanCache
    from hyperspace_tpu.sources import snapshots

    real_get, real_record = PlanCache.get, snapshots.record_version

    def ignoring(self, key, versions=None):
        entry = self._lru.peek(key)
        return real_get(self, key, versions if entry is None else entry[3])

    def freshest(table, version):
        scope = snapshots._SCOPE.get()
        if scope is None or scope.versions.get(table, -1) <= version:
            real_record(table, version)

    r = _run("sf1_rf1_hybrid_q3", traffic_overrides=HYBRID_TINY_RF1)
    assert r["correct"], r["checks"]
    assert r["checks"]["q3_unstated_rows"]["value"] == 0
    logged = {}
    for line in capsys.readouterr().out.splitlines():
        logged.update(json.loads(line))
    assert logged["executor"]["joins"] == ["device-fused-agg"]
    assert logged["window"]["appended_files_per_query"] > 0
    assert logged["window"]["versions_read"] > 1

    monkeypatch.setattr(PlanCache, "get", ignoring)
    monkeypatch.setattr(snapshots, "record_version", freshest)
    r = _run("sf1_rf1_hybrid_q3", traffic_overrides=HYBRID_TINY_RF1)
    assert r["checks"]["q3_stale_answers"]["value"] == 0, r["checks"]
    assert r["checks"]["q3_unstated_rows"]["value"] > 0, r["checks"]
    assert not r["correct"]


def test_index_alone_without_the_backlog_fails_wrong(monkeypatch):
    """Hybrid scan that drops the appended files: the index answers
    alone, and the backlog's rows are missing."""
    from hyperspace_tpu.rules import hybrid

    real = hybrid.hybrid_file_lists
    monkeypatch.setattr(hybrid, "hybrid_file_lists",
                        lambda entry, scan: ([], real(entry, scan)[1]))
    r = _run("sf1_rf1_hybrid_q3")
    assert r["checks"]["q3_answers_wrong"]["value"] > 0, r["checks"]
    assert not r["correct"]


def test_answer_labelled_with_another_version_fails_wrong(monkeypatch):
    """Every answer states the base version (before the backlog) while
    it read the latest."""
    from hyperspace_tpu.sources import snapshots

    real = snapshots.with_source_versions
    base = TINY["files_per_table"] - 1
    monkeypatch.setattr(
        snapshots, "with_source_versions",
        lambda table, versions: real(table, dict.fromkeys(versions, base)))
    r = _run("sf1_rf1_hybrid_q3")
    assert r["checks"]["q3_answers_wrong"]["value"] > 0, r["checks"]
    assert not r["correct"]


def _hybrid_request(snapshot_ms, appended_ms):
    """A served hybrid Q3 as the program traces it: the snapshot listing
    before planning, the appended branch inside the scan side."""
    from test_layer_metrics import _request, _span

    req = _request(2.0, 10.0, 4.0, 30.0, 5.0)
    req.children.insert(0, _span("source.snapshot", snapshot_ms))
    req.children[2].children[0].children.append(
        _span("exec.hybrid_appended", appended_ms,
              _span("exec.scan", appended_ms / 2)))
    return req


@pytest.fixture
def traced():
    return harness.Ctx(layer={
        "spans": [_hybrid_request(1.0, 20.0), _hybrid_request(3.0, 40.0)],
        "metrics_before": {"serve.plan_cache.source_stale": 5.0},
        "metrics_after": {"serve.plan_cache.source_stale": 6.0}})


@pytest.mark.parametrize("name,want", [
    ("hybrid_appended_ms.hybrid", 30.0),   # (20 + 40) over 2 queries
    ("source_snapshot_ms.hybrid", 2.0),    # (1 + 3) over 2 queries
    ("source_stale_share.hybrid", 50.0),   # 1 drop over 2 queries
])
def test_hybrid_readers(traced, name, want):
    assert harness.layer_reader(name)(traced) == pytest.approx(want)


@pytest.mark.parametrize("name", ["hybrid_appended_ms.hybrid",
                                  "source_snapshot_ms.hybrid",
                                  "source_stale_share.hybrid"])
def test_program_without_hybrid_spans_gives_none(traced, name):
    """The parent commit opens neither span and has no such counter."""
    from test_layer_metrics import _request

    traced.layer.update(spans=[_request(2.0, 10.0, 4.0, 30.0, 5.0)],
                        metrics_before={}, metrics_after={})
    assert harness.layer_reader(name)(traced) is None
