"""Fault-tolerant multi-host build (parallel/multihost_build.py; docs/21).

Two layers, mirroring docs/21's failure-mode matrix:

  - the **WorkClaims protocol** (lifecycle/lease.py), over BOTH LogStore
    backends: done records are final; an expired claim is reclaimed by
    exactly one racer (the CAS, not luck, picks the winner); a fenced
    zombie's renew/complete lose deterministically and land journal
    ``fence`` records; torn claim writes read as absent and are
    reclaimed over the burned generation; and a holder whose measured
    store RTT ate its margin stands down BEFORE wall-clock expiry.
  - the **end-to-end build**: two subprocess hosts produce a per-bucket
    byte-identical index to the single-process build, and a SIGKILLed
    host mid-route costs one claim TTL, not the build — the survivor
    completes the same bytes and the journal proves exactly ONE
    ``claim.commit`` per build.
"""

from __future__ import annotations

import hashlib
import json
import os
import signal
import threading
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from hyperspace_tpu import Hyperspace, HyperspaceSession, IndexConfig
from hyperspace_tpu.exceptions import HyperspaceError
from hyperspace_tpu.io.parquet import bucket_id_of_file
from hyperspace_tpu.lifecycle import journal as lifecycle_journal
from hyperspace_tpu.lifecycle.lease import WorkClaims
from hyperspace_tpu.parallel import multihost_build
from hyperspace_tpu.io.log_store import store_for

BOTH_STORES = ["hyperspace_tpu.io.log_store.PosixLogStore",
               "hyperspace_tpu.io.log_store.EmulatedObjectStore"]


def _session(tmp_path, store_class=BOTH_STORES[0]):
    s = HyperspaceSession(system_path=str(tmp_path / "ix"))
    s.conf.set("hyperspace.index.logStoreClass", store_class)
    return s


def _claims(s, owner, ttl_s=0.5):
    store = store_for(s.conf, os.path.join(str(s.conf.system_path),
                                           "_claims_test"))
    return WorkClaims(store, s.conf, owner=owner, ttl_s=ttl_s)


def _claim_events(conf):
    return [r for r in lifecycle_journal.records(conf)
            if r.get("decision") == "claim"]


# ---------------------------------------------------------------------------
# WorkClaims protocol (in-process, both backends)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("store_class", BOTH_STORES)
class TestWorkClaims:
    def test_claim_complete_is_final(self, tmp_path, store_class):
        s = _session(tmp_path, store_class)
        a = _claims(s, "a", ttl_s=5.0)
        b = _claims(s, "b", ttl_s=5.0)
        claim = a.try_claim("chunk-00000")
        assert claim is not None and claim["epoch"] == 1
        assert b.try_claim("chunk-00000") is None      # live holder
        assert a.renew(claim)                          # extends, bumps gen
        assert a.complete(claim, {"rows": 7})
        assert a.result("chunk-00000") == {"rows": 7}
        assert b.try_claim("chunk-00000") is None      # done is FINAL
        assert b.pending(["chunk-00000", "chunk-00001"]) == ["chunk-00001"]
        modes = [e["mode"] for e in _claim_events(s.conf)]
        assert "acquire" in modes and "complete" in modes

    def test_expired_reclaim_fences_zombie(self, tmp_path, store_class):
        s = _session(tmp_path, store_class)
        a = _claims(s, "a", ttl_s=0.3)
        b = _claims(s, "b", ttl_s=5.0)
        stale = a.try_claim("group-000")
        assert stale is not None
        time.sleep(0.4)                                # a's TTL runs out
        taken = b.try_claim("group-000")
        assert taken is not None and taken["epoch"] == 2
        # The zombie wakes: both its renew and its complete lose the CAS.
        assert a.renew(stale) is False
        assert a.complete(stale, {"rows": 1}) is False
        assert b.complete(taken, {"rows": 2})
        assert b.result("group-000") == {"rows": 2}    # the winner's bytes
        modes = [e["mode"] for e in _claim_events(s.conf)]
        assert "reclaim" in modes and modes.count("fence") == 2

    def test_double_reclaim_single_winner(self, tmp_path, store_class):
        """Two racers both observe the SAME expired generation; the CAS
        lets exactly one through — the loser gets None, not a claim."""
        s = _session(tmp_path, store_class)
        a = _claims(s, "a", ttl_s=0.2)
        b = _claims(s, "b", ttl_s=5.0)
        c = _claims(s, "c", ttl_s=5.0)
        assert a.try_claim("chunk-00003") is not None
        time.sleep(0.3)
        stale_read = c.get("chunk-00003")              # c reads FIRST ...
        won = b.try_claim("chunk-00003")               # ... then b commits
        assert won is not None and won["epoch"] == 2
        c.get = lambda item: stale_read                # c acts on its read
        assert c.try_claim("chunk-00003") is None      # CAS loss, no claim
        rec, _g = b.get("chunk-00003")
        assert rec["holder"] == "b"

    def test_torn_claim_reads_absent_then_reclaimed(self, tmp_path,
                                                    store_class):
        s = _session(tmp_path, store_class)
        a = _claims(s, "a", ttl_s=5.0)
        # A torn put burned a real generation with unparseable bytes.
        assert a.store.put_if_generation_match(
            WorkClaims.PREFIX + "chunk-00001", b"\x00torn not json", 0)
        rec, gen = a.get("chunk-00001")
        assert rec is None and gen >= 1                # absent, gen burned
        claim = a.try_claim("chunk-00001")
        assert claim is not None
        assert claim["epoch"] > gen                    # monotonic past it
        assert a.complete(claim, {})
        modes = [e["mode"] for e in _claim_events(s.conf)]
        assert "reclaim" in modes                      # takeover, not fresh

    def test_rtt_margin_stands_down_before_expiry(self, tmp_path,
                                                  store_class):
        """Clock-skew / slow-store stand-down: when the measured store
        RTT eats the safety margin, ``holds`` goes False while the wall
        clock still shows a live claim — the holder renews (or
        discards) instead of committing into a possible takeover."""
        s = _session(tmp_path, store_class)
        a = _claims(s, "a", ttl_s=0.9)
        b = _claims(s, "b", ttl_s=5.0)
        claim = a.try_claim("group-001")
        assert claim is not None
        a._lat_ewma_s = 10.0                           # degraded store link
        assert a.margin_s() == pytest.approx(0.3)      # clamped to TTL/3
        time.sleep(0.65)                               # inside the margin
        assert time.time() < claim["expires_at"]       # NOT yet expired...
        assert not a.holds(claim)                      # ...but stands down
        assert b.try_claim("group-001") is None        # successor waits
        assert a.renew(claim)                          # CAS still ours
        assert a.holds(claim)                          # fresh TTL again


# ---------------------------------------------------------------------------
# End-to-end: N subprocess hosts, one index
# ---------------------------------------------------------------------------
N_ROWS = 24000


@pytest.fixture(scope="module")
def mh_source(tmp_path_factory):
    root = tmp_path_factory.mktemp("mh_src")
    rng = np.random.default_rng(7)
    t = pa.table({
        "k": pa.array(rng.integers(0, 500, size=N_ROWS), type=pa.int64()),
        "g": pa.array(rng.integers(0, 7, size=N_ROWS), type=pa.int64()),
        "v": pa.array(rng.integers(0, 1000, size=N_ROWS), type=pa.int64()),
    })
    step = -(-N_ROWS // 3)
    for f in range(3):
        pq.write_table(t.slice(f * step, step),
                       os.path.join(str(root), f"part-{f:05d}.parquet"))
    return str(root)


def _mh_session(tmp_path, src, hosts):
    s = HyperspaceSession(system_path=str(tmp_path / f"ix_h{hosts}"))
    s.conf.num_buckets = 8
    s.conf.device_batch_rows = 4096
    s.conf.device_build_min_rows = 0       # host route path on every host
    s.conf.multihost_build_hosts = hosts
    s.conf.multihost_build_claim_ttl_s = 1.5
    s.conf.multihost_build_poll_s = 0.02
    hs = Hyperspace(s)
    hs.create_index(s.read.parquet(src), IndexConfig("mh", ["k"], ["g", "v"]))
    return s, hs


def _bucket_digests(s):
    entry = s.index_collection_manager.get_index("mh")
    out = {}
    for fi in entry.content.file_infos():
        with open(fi.name, "rb") as fh:
            out.setdefault(bucket_id_of_file(fi.name), []).append(
                hashlib.sha256(fh.read()).hexdigest())
    return {b: sorted(v) for b, v in out.items()}


@pytest.fixture(scope="module")
def single_host_digests(mh_source, tmp_path_factory):
    s, _hs = _mh_session(tmp_path_factory.mktemp("mh_single"), mh_source, 0)
    return _bucket_digests(s)


def test_two_host_build_bit_equal(tmp_path, mh_source, single_host_digests):
    s, hs = _mh_session(tmp_path, mh_source, 2)
    assert _bucket_digests(s) == single_host_digests
    props = hs.last_build_report().properties
    assert props["multihost_hosts"] == 2
    assert props["multihost_chunks"] >= 2
    assert props["multihost_groups"] >= 2
    assert props["multihost_route_wall_s"] > 0
    # Exactly one commit record for the whole build.
    commits = [e for e in _claim_events(s.conf) if e["mode"] == "commit"]
    assert len(commits) == 1
    # Scratch is gone; no claims left behind for the doctor to grade.
    assert multihost_build.scan_build_claims(s.conf) == []


def test_sigkill_mid_route_survivor_completes(tmp_path, mh_source,
                                              single_host_digests,
                                              monkeypatch):
    """SIGKILL one host once routing is underway: the survivor reclaims
    the victim's expired claims and lands the byte-identical index,
    with exactly one journalled commit."""
    killed = {}
    orig_spawn = multihost_build.spawn_hosts

    def spawn_and_kill(conf, build_id, n):
        procs = orig_spawn(conf, build_id, n)
        store = multihost_build._store(conf, build_id)
        watch = WorkClaims(store, conf, owner="watcher", ttl_s=1.0)

        def watcher():
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline and not killed:
                done = sum(
                    1 for key in store.list_keys(WorkClaims.PREFIX)
                    if (rec := watch.get(key[len(WorkClaims.PREFIX):])[0])
                    and rec.get("done")
                    and rec["item"].startswith("chunk-"))
                if done >= 1 and procs[0].poll() is None:
                    os.kill(procs[0].pid, signal.SIGKILL)
                    killed["after_chunks"] = done
                    return
                time.sleep(0.02)

        threading.Thread(target=watcher, daemon=True).start()
        return procs

    monkeypatch.setattr(multihost_build, "spawn_hosts", spawn_and_kill)
    s, _hs = _mh_session(tmp_path, mh_source, 2)
    assert killed, "watcher never fired; the drill proved nothing"
    assert _bucket_digests(s) == single_host_digests
    events = _claim_events(s.conf)
    commits = [e for e in events if e["mode"] == "commit"]
    assert len(commits) == 1               # exactly-once, journal-proven
    # Every item's done record exists exactly once (the claim table is
    # the ledger; one done record per item is what made commit safe).
    done_items = [e["item"] for e in events if e["mode"] == "complete"]
    assert len(done_items) == len(set(done_items))
    assert multihost_build.scan_build_claims(s.conf) == []


@pytest.mark.parametrize("platforms", [None, "tpu", ""])
def test_hosts_refuse_without_an_explicit_cpu_pin(monkeypatch, platforms):
    """No host child silently defaults to the CPU: without the explicit
    JAX_PLATFORMS=cpu pin the build refuses and names the reason."""
    if platforms is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", platforms)
    with pytest.raises(HyperspaceError, match="chip of their own"):
        multihost_build.require_cpu_hosts(2)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    multihost_build.require_cpu_hosts(2)
