"""Cross-process maintenance lease (lifecycle/lease.py; docs/20).

The churn acceptance loop: N processes over one index tree elect
exactly ONE maintenance executor through the LogStore CAS seam, over
BOTH backends; a SIGKILLed holder's lease expires and is taken over
within TTL + slack; a fenced zombie's renew is rejected; and the
lifecycle journal proves zero double-executed maintenance actions —
every acquire / takeover / renew / fence / release is a durable
journal event.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from hyperspace_tpu import Hyperspace, HyperspaceSession, IndexConfig
from hyperspace_tpu.lifecycle import journal as lifecycle_journal
from hyperspace_tpu.lifecycle import lease
from hyperspace_tpu.telemetry import metrics

BOTH_STORES = ["hyperspace_tpu.io.log_store.PosixLogStore",
               "hyperspace_tpu.io.log_store.EmulatedObjectStore"]


def _session(tmp_path, store_class, ttl_s=0.5):
    s = HyperspaceSession(system_path=str(tmp_path / "ix"))
    s.conf.set("hyperspace.index.logStoreClass", store_class)
    s.conf.set("hyperspace.lifecycle.lease.enabled", True)
    s.conf.set("hyperspace.lifecycle.lease.ttlS", ttl_s)
    return s


def _counter(name):
    return metrics.registry().counter(name)


def _lease_events(conf):
    return [r for r in lifecycle_journal.records(conf)
            if r.get("decision") == "lease"]


# ---------------------------------------------------------------------------
# Protocol (in-process, both backends)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("store_class", BOTH_STORES)
class TestLeaseProtocol:
    def test_acquire_standby_renew(self, tmp_path, store_class):
        s = _session(tmp_path, store_class, ttl_s=5.0)
        a = lease.MaintenanceLease(s.conf, owner="a")
        b = lease.MaintenanceLease(s.conf, owner="b")
        assert a.ensure() is True          # fresh acquire
        assert a.holds()
        assert b.ensure() is False         # live holder: standby
        assert not b.holds()
        assert a.ensure() is True          # renew extends
        rec = lease.status(s.conf)
        assert rec["holder"] == "a" and rec["epoch"] == 1 and rec["fresh"]
        events = [e["mode"] for e in _lease_events(s.conf)]
        assert "acquire" in events and "renew" in events

    def test_expiry_takeover_fences_zombie(self, tmp_path, store_class):
        s = _session(tmp_path, store_class, ttl_s=0.3)
        a = lease.MaintenanceLease(s.conf, owner="a")
        b = lease.MaintenanceLease(s.conf, owner="b")
        assert a.ensure() is True
        time.sleep(0.4)                    # a's lease expires un-renewed
        assert not a.holds()               # local wall clock gates it too
        fenced0 = _counter("lease.fenced")
        assert b.ensure() is True          # takeover bumps the epoch
        assert b.epoch == 2
        rec = lease.status(s.conf)
        assert rec["holder"] == "b" and rec["epoch"] == 2
        # The zombie's renew CASes against a stale generation: REJECTED,
        # and the zombie stands down instead of acting on the old epoch.
        assert a.renew() is False
        assert not a.holds()
        assert _counter("lease.fenced") == fenced0 + 1
        events = [e["mode"] for e in _lease_events(s.conf)]
        assert "takeover" in events and "fence" in events
        # b is unaffected by the zombie's rejected write.
        assert b.ensure() is True

    def test_release_hands_off_instantly(self, tmp_path, store_class):
        s = _session(tmp_path, store_class, ttl_s=30.0)
        a = lease.MaintenanceLease(s.conf, owner="a")
        b = lease.MaintenanceLease(s.conf, owner="b")
        assert a.ensure() is True
        b_denied = b.ensure()
        assert b_denied is False
        a.release()
        assert not a.holds()
        # No TTL wait: the released record reads expired immediately.
        assert b.ensure() is True
        assert b.epoch == 2

    def test_torn_record_reads_absent(self, tmp_path, store_class):
        from hyperspace_tpu.io.log_store import store_for

        s = _session(tmp_path, store_class)
        store = store_for(s.conf, lease.lease_root(s.conf))
        assert store.put_if_generation_match(
            lease.LEASE_KEY, b"\x00garbage not json", 0)
        assert lease.status(s.conf) is None
        a = lease.MaintenanceLease(s.conf, owner="a")
        assert a.ensure() is True          # garbage is up for grabs


# ---------------------------------------------------------------------------
# Daemon gate
# ---------------------------------------------------------------------------
class TestDaemonGate:
    def _env(self, tmp_path):
        src = str(tmp_path / "src")
        os.makedirs(src)
        n = 2000
        rng = np.random.default_rng(3)
        pq.write_table(pa.table({
            "k": pa.array(np.arange(n, dtype=np.int64)),
            "d": pa.array(rng.integers(0, 50, n), type=pa.int64()),
            "v": rng.random(n),
        }), os.path.join(src, "part-00000000.parquet"))
        s = HyperspaceSession(system_path=str(tmp_path / "ix"))
        s.conf.num_buckets = 4
        s.conf.set("hyperspace.lifecycle.lease.enabled", True)
        s.conf.set("hyperspace.lifecycle.lease.ttlS", 30.0)
        hs = Hyperspace(s)
        hs.create_index(s.read.parquet(src),
                        IndexConfig("lix", ["k"], ["v"]))
        return s, hs, src

    def test_standby_cycle_skips_and_journals(self, tmp_path):
        s, hs, src = self._env(tmp_path)
        other = lease.MaintenanceLease(s.conf, owner="somebody-else")
        assert other.ensure() is True
        recs = hs.maintenance_cycle()
        assert len(recs) == 1
        assert recs[0]["outcome"] == "skipped"
        assert "lease standby" in recs[0]["reason"]
        assert "somebody-else" in recs[0]["reason"]
        # Once the holder releases, the next cycle acquires and works.
        other.release()
        recs = hs.maintenance_cycle()
        assert all(r.get("outcome") != "skipped" for r in recs)
        rec = lease.status(s.conf)
        assert rec is not None and rec["holder"] != "somebody-else"


# ---------------------------------------------------------------------------
# Churn: SIGKILL the holder mid-renew, both backends
# ---------------------------------------------------------------------------
_HOLDER_CHILD = r"""
import json, os, sys, time
from hyperspace_tpu import HyperspaceSession
from hyperspace_tpu.lifecycle import lease

system_path, store_class, ttl = sys.argv[1:4]
s = HyperspaceSession(system_path=system_path)
s.conf.set("hyperspace.index.logStoreClass", store_class)
s.conf.set("hyperspace.lifecycle.lease.enabled", True)
s.conf.set("hyperspace.lifecycle.lease.ttlS", float(ttl))
hold = lease.MaintenanceLease(s.conf, owner="holder-child")
deadline = time.time() + 30
while not hold.ensure() and time.time() < deadline:
    time.sleep(0.02)
assert hold.holds(), "child never acquired the lease"
print(json.dumps({"pid": os.getpid(), "epoch": hold.epoch}), flush=True)
while True:          # renew hot, so SIGKILL lands mid-renew-loop
    hold.ensure()
    time.sleep(0.02)
"""


@pytest.mark.parametrize("store_class", BOTH_STORES)
class TestLeaseChurn:
    def test_sigkill_holder_takeover_no_double_execution(
            self, tmp_path, store_class):
        src = str(tmp_path / "src")
        os.makedirs(src)
        rng = np.random.default_rng(5)
        n = 2000
        pq.write_table(pa.table({
            "k": pa.array(np.arange(n, dtype=np.int64)),
            "d": pa.array(rng.integers(0, 50, n), type=pa.int64()),
            "v": rng.random(n),
        }), os.path.join(src, "part-00000000.parquet"))
        ttl = 1.0
        s = HyperspaceSession(system_path=str(tmp_path / "ix"))
        s.conf.num_buckets = 4
        s.conf.set("hyperspace.index.logStoreClass", store_class)
        s.conf.set("hyperspace.lifecycle.lease.enabled", True)
        s.conf.set("hyperspace.lifecycle.lease.ttlS", ttl)
        hs = Hyperspace(s)
        hs.create_index(s.read.parquet(src),
                        IndexConfig("lix", ["k"], ["v"]))
        # A pending refresh: appended source the eventual holder must
        # cover exactly once.
        t = pa.table({
            "k": pa.array(np.arange(n, n + 200, dtype=np.int64)),
            "d": pa.array(rng.integers(0, 50, 200), type=pa.int64()),
            "v": rng.random(200),
        })
        pq.write_table(t, os.path.join(src, "part-00010000.parquet"))

        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.Popen(
            [sys.executable, "-c", _HOLDER_CHILD, str(tmp_path / "ix"),
             store_class, str(ttl)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env)
        try:
            line = proc.stdout.readline()
            assert line, proc.stderr.read()
            child = json.loads(line)
            # While the child holds, every local cycle stands by.
            recs = hs.maintenance_cycle()
            assert len(recs) == 1 and recs[0]["outcome"] == "skipped"
            assert "holder-child" in recs[0]["reason"]
            # SIGKILL mid-renew: no release is written; the lease must
            # expire on its own and be taken over within TTL + slack.
            os.kill(child["pid"], signal.SIGKILL)
            proc.wait(timeout=30)
            took_over = False
            deadline = time.monotonic() + ttl + 10.0
            while time.monotonic() < deadline:
                recs = hs.maintenance_cycle()
                if recs and all(r.get("outcome") != "skipped"
                                for r in recs):
                    took_over = True
                    break
                time.sleep(0.2)
            assert took_over, "lease never taken over after SIGKILL"
        finally:
            proc.kill()
            proc.wait(timeout=30)

        rec = lease.status(s.conf)
        assert rec["holder"] != "holder-child"
        assert rec["epoch"] > child["epoch"]
        records = lifecycle_journal.records(s.conf)
        # Journal-asserted: the pending refresh executed EXACTLY once —
        # the standby never ran it while the child held the lease, and
        # the takeover ran it once.
        done_actions = [r for r in records
                        if r.get("decision") == "refresh"
                        and r.get("outcome") == "done"]
        assert len(done_actions) == 1, done_actions
        # And the lease history shows the takeover (epoch bumped past
        # the child's) with the child's own acquire before it.
        events = _lease_events(s.conf)
        holders = {e["holder"] for e in events}
        assert "holder-child" in holders
        takeovers = [e for e in events if e["mode"] == "takeover"]
        assert any(e["epoch"] > child["epoch"] for e in takeovers)
