"""Seeded fleet chaos drill: randomized faults, deterministic schedule,
checked invariants.

SIGKILL drills (tests/test_fleet_serving.py) prove the
fleet survives CLEAN deaths; this module composes every failure mode the
repo can inject into one reproducible storm against a live 3-server
fleet under sustained mixed load:

  - **SIGKILL + restart** — the clean death, now with the server coming
    BACK on the same port (the pool's stale half-open sockets are the
    satellite-1 case).
  - **SIGSTOP / SIGCONT** — the gray failure: the process is alive (TCP
    accepts, heartbeats stale) but serves nothing; only a deadline
    saves the caller, and the late response after SIGCONT must be
    discarded, not cross-wired.
  - **Wire faults** (interop/netfaults.py) — refused / reset /
    black-hole / slow / torn-frame armed at the client seams
    mid-drill, and at the server seams via a child bounced with
    ``hyperspace.system.faultInjection.*`` conf.
  - **Maintenance churn** — every child runs lease-elected maintenance
    cycles while the drill appends source data, so exactly-once
    execution is contested, not vacuous.
  - **Build-host death** — ``kill-build-host`` runs a concurrent
    2-host multi-host index build (parallel/multihost_build.py) and
    SIGKILLs one of its hosts once claims exist; the survivor must
    finish a byte-identical index with exactly one journalled commit
    while the serving fleet's own invariants keep holding.

The schedule is a PURE function of the seed (:func:`build_schedule`):
same seed ⇒ identical event list, which is what makes a chaos failure
reproducible instead of an anecdote.  Execution timing is wall-clock
(events fire at their offsets), but no invariant depends on timing —
they are end-state properties:

  1. zero lost requests: every request the load threads sent got an
     answer (retry/hedge/failover absorbed every fault);
  2. bit-equal answers: every answer matches the host-side reference;
  3. exactly-once maintenance: the appended data's refresh landed in
     the lifecycle journal with outcome ``done`` exactly once;
  4. metrics accounting: ``client.hedge.wins ≤ client.hedge.sent``,
     ``client.failover ≤ client.retry``, breaker closes ≤ opens, and
     the ``client.breaker.open_now`` gauge within [0, servers];
  5. every ``kill-build-host`` drill completed: a host really died,
     the survivor's index is byte-identical to the single-host
     baseline, and the claim journal shows exactly one commit.

Entry points: ``tools/chaos.py`` (CLI) and tests/test_chaos.py (smoke
+ schedule determinism).
"""

from __future__ import annotations

import json
import os
import random
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

_CHILD = r"""
import json, os, sys, threading, time
from hyperspace_tpu import Hyperspace, HyperspaceSession
from hyperspace_tpu.interop import QueryServer
from hyperspace_tpu.io import faults

system_path, port, conf_json = sys.argv[1], int(sys.argv[2]), sys.argv[3]
s = HyperspaceSession(system_path=system_path)
for key, value in json.loads(conf_json).items():
    s.conf.set(key, value)
# Conf set after construction: re-apply the fault arming the session
# constructor would have done — this is how a bounced child comes back
# with a wire fault armed.
faults.install_from_conf(s.conf)
hs = Hyperspace(s)
server = QueryServer(s, port=port, handle_sigterm=True).start()

def maintain():
    while True:
        try:
            hs.maintenance_cycle()
        except BaseException:
            pass
        time.sleep(0.25)

threading.Thread(target=maintain, daemon=True).start()
print(json.dumps({"port": server.address[1], "pid": os.getpid()}),
      flush=True)
server.drained.wait()
sys.exit(0)
"""

# Client-seam wire faults the schedule can arm in the DRIVER process
# (site, kind); shaping comes from the plan defaults scaled for a drill.
_CLIENT_FAULTS: List[Tuple[str, str]] = [
    ("net.connect", "refused"),
    ("net.connect", "black-hole"),
    ("net.send", "reset"),
    ("net.send", "torn-frame"),
    ("net.recv", "black-hole"),
    ("net.recv", "slow"),
]
# Server-seam faults a bounced child comes back armed with.
_SERVER_FAULTS: List[Tuple[str, str]] = [
    ("net.send", "torn-frame"),
    ("net.send", "reset"),
    ("net.accept", "reset"),
]


def build_schedule(seed: int, duration_s: float,
                   servers: int) -> List[Dict[str, Any]]:
    """The drill's event list — a pure function of its arguments (fixed
    seed ⇒ identical schedule).  Events target one server at a time
    with recovery built in, so the invariants stay achievable: the
    fleet is degraded continuously but never fully dark."""
    rng = random.Random(int(seed))
    events: List[Dict[str, Any]] = []
    t = min(1.0, duration_s * 0.15)  # let the warm fleet serve first
    appended = False
    while t < duration_s * 0.9:
        roll = rng.random()
        target = rng.randrange(servers)
        if not appended and t >= duration_s * 0.35:
            events.append({"t": round(t, 3), "op": "append"})
            appended = True
            t += duration_s * 0.05
            continue
        if roll < 0.30:
            events.append({"t": round(t, 3), "op": "kill",
                           "server": target,
                           "down_s": round(rng.uniform(0.3, 0.8), 3)})
            t += 1.2
        elif roll < 0.55:
            events.append({"t": round(t, 3), "op": "stop",
                           "server": target,
                           "stop_s": round(rng.uniform(0.4, 1.0), 3)})
            t += 1.4
        elif roll < 0.72:
            site, kind = _CLIENT_FAULTS[
                rng.randrange(len(_CLIENT_FAULTS))]
            events.append({"t": round(t, 3), "op": "client-fault",
                           "site": site, "kind": kind,
                           "at": rng.randrange(1, 4),
                           "count": rng.randrange(1, 4)})
            t += 0.8
        elif roll < 0.80:
            # Concurrent multi-host index build with one of ITS hosts
            # SIGKILLed mid-route: the claim protocol (not the serving
            # fleet) must absorb this one — the survivor finishes the
            # byte-identical index while the drill's load keeps running.
            events.append({"t": round(t, 3), "op": "kill-build-host",
                           "victim": rng.randrange(2)})
            t += 1.6
        else:
            site, kind = _SERVER_FAULTS[
                rng.randrange(len(_SERVER_FAULTS))]
            events.append({"t": round(t, 3), "op": "bounce-armed",
                           "server": target, "site": site, "kind": kind,
                           "at": rng.randrange(2, 6),
                           "count": rng.randrange(1, 3)})
            t += 1.4
    if not appended:
        events.append({"t": round(duration_s * 0.5, 3), "op": "append"})
        events.sort(key=lambda e: e["t"])
    return events


def _build_drill(workdir: str, src: str, tag: int,
                 victim: int) -> Dict[str, Any]:
    """One ``kill-build-host`` drill: a 2-host multi-host build of
    ``src`` with host ``victim`` SIGKILLed once claims exist, graded
    byte-equal against a single-host build of the same snapshot and
    exactly-once against its claim journal."""
    import hashlib

    from hyperspace_tpu import Hyperspace, HyperspaceSession, IndexConfig
    from hyperspace_tpu.io.parquet import bucket_id_of_file
    from hyperspace_tpu.lifecycle import journal as lifecycle_journal
    from hyperspace_tpu.lifecycle.lease import WorkClaims
    from hyperspace_tpu.parallel import multihost_build

    def build(path: str, hosts: int):
        sess = HyperspaceSession(system_path=path)
        sess.conf.num_buckets = 4
        sess.conf.multihost_build_hosts = hosts
        sess.conf.multihost_build_claim_ttl_s = 1.0
        sess.conf.multihost_build_poll_s = 0.02
        Hyperspace(sess).create_index(
            sess.read.parquet(src), IndexConfig("bix", ["k"], ["v"]))
        return sess

    def digests(sess) -> Dict[int, List[str]]:
        entry = sess.index_collection_manager.get_index("bix")
        out: Dict[int, List[str]] = {}
        for fi in entry.content.file_infos():
            with open(fi.name, "rb") as fh:
                out.setdefault(bucket_id_of_file(fi.name), []).append(
                    hashlib.sha256(fh.read()).hexdigest())
        return {b: sorted(v) for b, v in out.items()}

    base = build(os.path.join(workdir, f"bix-base-{tag}"), 0)
    want = digests(base)

    killed: Dict[str, Any] = {}
    orig = multihost_build.spawn_hosts

    def spawn_and_kill(conf, build_id, n):
        procs = orig(conf, build_id, n)
        store = multihost_build._store(conf, build_id)

        def reaper():
            deadline = time.monotonic() + 30.0
            while time.monotonic() < deadline:
                if store.list_keys(WorkClaims.PREFIX):
                    break
                time.sleep(0.02)
            p = procs[min(victim, len(procs) - 1)]
            if p.poll() is None:
                try:
                    os.kill(p.pid, signal.SIGKILL)
                except OSError:
                    pass
            killed["pid"] = p.pid

        threading.Thread(target=reaper, daemon=True).start()
        return procs

    multihost_build.spawn_hosts = spawn_and_kill
    try:
        mh = build(os.path.join(workdir, f"bix-mh-{tag}"), 2)
    finally:
        multihost_build.spawn_hosts = orig
    bit_equal = digests(mh) == want
    commits = sum(
        1 for r in lifecycle_journal.records(mh.conf)
        if r.get("decision") == "claim" and r.get("mode") == "commit")
    return {"tag": tag, "victim": victim, "killed": bool(killed),
            "bit_equal": bit_equal, "commits": commits,
            "ok": bool(killed) and bit_equal and commits == 1}


def _alert_drill(session, deadline_s: float = 30.0) -> Dict[str, Any]:
    """The SLO-alert invariant (docs/16): armed wire faults must FIRE
    the availability fast-burn alert with an incident bundle captured,
    and disarming must RESOLVE it.  Runs an in-process server on the
    driver session so the alert engine, the serve counters, and the
    armed ``net.send`` seam all live in one metrics registry; the
    probe client speaks the wire protocol over a RAW socket so the
    armed seam tears only the SERVER's sends, not the probe's."""
    from hyperspace_tpu.interop.server import QueryServer
    from hyperspace_tpu.io import faults
    from hyperspace_tpu.telemetry import alerts as alerts_mod
    from hyperspace_tpu.telemetry import flight_recorder

    out: Dict[str, Any] = {"fired": False, "resolved": False,
                           "bundle_ok": False, "ok": False}
    # Tiny windows so the fast-burn rule decides in drill time, not SRE
    # time; pending/resolve damping of 1 keeps the round-trip short.
    for key, value in (
            ("hyperspace.alerts.enabled", True),
            ("hyperspace.alerts.intervalS", 0.1),
            ("hyperspace.alerts.availabilityTarget", 0.9),
            ("hyperspace.alerts.fastShortS", 0.4),
            ("hyperspace.alerts.fastLongS", 0.8),
            ("hyperspace.alerts.fastFactor", 1.5),
            ("hyperspace.alerts.pendingEvals", 1),
            ("hyperspace.alerts.resolveEvals", 1)):
        session.conf.set(key, value)

    def probe(port: int, read: bool = True,
              timeout_s: float = 1.5) -> None:
        # Fire-and-forget during the fault phase (read=False): the
        # armed seam eats the response anyway, and not blocking on a
        # read that will never come keeps the bad-event rate high.
        sock = socket.create_connection(("127.0.0.1", port),
                                        timeout=timeout_s)
        try:
            sock.sendall(b'{"verb": "metrics"}\n')
            if read:
                sock.recv(65536)
        finally:
            try:
                sock.close()
            except OSError:
                pass

    def state_of(engine, name: str) -> str:
        return engine.current_states().get(name, {}).get("state", "")

    server = QueryServer(session, port=0).start()  # starts the engine
    engine = alerts_mod.engine_for(session)
    port = server.address[1]
    deadline = time.monotonic() + deadline_s
    try:
        # Good traffic first: the burn windows need a baseline.
        settle = time.monotonic() + 0.6
        while time.monotonic() < settle:
            probe(port)
            time.sleep(0.02)
        # Arm the wire fault: every response send black-holes, so each
        # probe lands as a ``serve.send_timeouts`` bad event.
        faults.install(faults.FaultPlan(
            site="net.send", kind="black-hole", at=1, count=10 ** 6,
            hang_s=0.01))
        while (state_of(engine, "availability") != "firing"
               and time.monotonic() < deadline):
            try:
                probe(port, read=False)
            except OSError:
                pass  # the fault eats the answer — that IS the drill
            time.sleep(0.02)
        out["fired"] = state_of(engine, "availability") == "firing"
        # The bundle commits right AFTER the state flips (capture runs
        # outside the engine's state lock), so give it a beat to land.
        bundle_key = ""
        while not bundle_key and time.monotonic() < deadline:
            bundle_key = engine.current_states().get(
                "availability", {}).get("bundle_key") or ""
            if not bundle_key:
                time.sleep(0.05)
        out["bundle_key"] = bundle_key
        faults.clear()
        out["bundle_ok"] = bool(bundle_key) and any(
            b.get("key") == bundle_key and "incident" in b
            for b in flight_recorder.bundles(session.conf))
        # Disarm + good traffic: the alert must come back down.
        while (state_of(engine, "availability") == "firing"
               and time.monotonic() < deadline):
            try:
                probe(port)
            except OSError:
                pass
            time.sleep(0.02)
        out["resolved"] = \
            state_of(engine, "availability") in ("resolved", "")
    finally:
        faults.clear()
        try:
            server.stop()
        except Exception as exc:  # noqa: BLE001 — teardown best-effort,
            out["teardown_error"] = str(exc)  # but visible in the report
        engine.stop()
        session.conf.set("hyperspace.alerts.enabled", False)
    out["ok"] = (out["fired"] and out["bundle_ok"] and out["resolved"])
    return out


class _Fleet:
    """The drill's process harness: spawn/kill/stop/bounce children on
    stable ports over one shared index tree."""

    def __init__(self, system_path: str, servers: int,
                 base_conf: Dict[str, Any]) -> None:
        self.system_path = system_path
        self.base_conf = base_conf
        self.procs: List[Optional[subprocess.Popen]] = [None] * servers
        self.ports: List[int] = [0] * servers
        self.pids: List[int] = [0] * servers

    def spawn(self, i: int, extra_conf: Optional[Dict[str, Any]] = None,
              timeout_s: float = 60.0) -> None:
        conf = dict(self.base_conf)
        if extra_conf:
            conf.update(extra_conf)
        proc = subprocess.Popen(
            [sys.executable, "-c", _CHILD, self.system_path,
             str(self.ports[i]), json.dumps(conf)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        line = proc.stdout.readline()
        if not line:
            raise RuntimeError(
                f"chaos child {i} failed to start: {proc.stderr.read()}")
        info = json.loads(line)
        self.procs[i] = proc
        self.ports[i] = info["port"]
        self.pids[i] = info["pid"]

    def kill(self, i: int) -> None:
        proc = self.procs[i]
        if proc is not None:
            try:
                os.kill(self.pids[i], signal.SIGKILL)
            except OSError:
                pass
            proc.wait(timeout=30)

    def stop_cont(self, i: int, stop_s: float) -> None:
        try:
            os.kill(self.pids[i], signal.SIGSTOP)
            time.sleep(stop_s)
        finally:
            try:
                os.kill(self.pids[i], signal.SIGCONT)
            except OSError:
                pass

    def endpoints(self) -> List[Tuple[str, int]]:
        return [("127.0.0.1", p) for p in self.ports]

    def teardown(self) -> None:
        for i, proc in enumerate(self.procs):
            if proc is None:
                continue
            try:
                os.kill(self.pids[i], signal.SIGCONT)
            except OSError:
                pass
            try:
                proc.kill()
                proc.wait(timeout=30)
            except OSError:
                pass


def _percentile(values: List[float], q: float) -> float:
    if not values:
        return 0.0
    ordered = sorted(values)
    idx = min(len(ordered) - 1, int(round(q * (len(ordered) - 1))))
    return ordered[idx]


def run_chaos(seed: int = 0, duration_s: float = 6.0, servers: int = 3,
              workdir: Optional[str] = None, load_threads: int = 2,
              rows: int = 400, deadline_ms: float = 20000.0,
              lease_ttl_s: float = 1.0) -> Dict[str, Any]:
    """Run the drill; returns the report dict (key ``ok`` plus
    ``violations`` naming any invariant that failed — the caller
    decides whether to raise)."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    from hyperspace_tpu import Hyperspace, HyperspaceSession, IndexConfig
    from hyperspace_tpu.interop import FleetQueryClient
    from hyperspace_tpu.interop import netfaults
    from hyperspace_tpu.io import faults
    from hyperspace_tpu.lifecycle import journal as lifecycle_journal
    from hyperspace_tpu.telemetry import metrics

    owns_workdir = workdir is None
    if owns_workdir:
        workdir = tempfile.mkdtemp(prefix="hs_chaos_")
    data = os.path.join(workdir, "src")
    os.makedirs(data, exist_ok=True)
    n = int(rows)
    pq.write_table(pa.table({
        "k": pa.array(np.arange(n, dtype=np.int64)),
        "v": pa.array(np.arange(n, dtype=np.int64) * 3 + 1),
    }), os.path.join(data, "part-00000000.parquet"))
    # A STABLE snapshot for the kill-build-host drills: the mid-drill
    # append mutates ``data``, and the build drill's byte-equality
    # baseline must see the same files as its 2-host leg.
    bsrc = os.path.join(workdir, "bsrc")
    os.makedirs(bsrc, exist_ok=True)
    # hslint: allow[io-seam] drill-source snapshot copy, not index data
    shutil.copy(os.path.join(data, "part-00000000.parquet"),
                os.path.join(bsrc, "part-00000000.parquet"))
    # The mid-drill append adds keys >= n, so every load-thread answer
    # stays bit-equal across the append: point probes stay below n and
    # the aggregate filters to k < n.  The appended rows exist to make
    # the maintenance refresh contested, not to move the answers.
    expected = {k: 3 * k + 1 for k in range(n)}
    expected_sum = sum(expected.values())

    system_path = os.path.join(workdir, "ix")
    s = HyperspaceSession(system_path=system_path)
    s.conf.num_buckets = 4
    s.conf.set("hyperspace.fleet.telemetry.enabled", True)
    s.conf.set("hyperspace.fleet.telemetry.publishIntervalS", 0.2)
    s.conf.set("hyperspace.lifecycle.lease.enabled", True)
    s.conf.set("hyperspace.lifecycle.lease.ttlS", lease_ttl_s)
    hs = Hyperspace(s)
    hs.create_index(s.read.parquet(data), IndexConfig("cix", ["k"], ["v"]))

    base_conf = {
        "hyperspace.fleet.telemetry.enabled": True,
        "hyperspace.fleet.telemetry.publishIntervalS": 0.2,
        "hyperspace.lifecycle.lease.enabled": True,
        "hyperspace.lifecycle.lease.ttlS": lease_ttl_s,
    }
    schedule = build_schedule(seed, duration_s, servers)
    report: Dict[str, Any] = {"seed": int(seed),
                              "duration_s": float(duration_s),
                              "servers": int(servers),
                              "schedule": schedule}
    c0 = {name: metrics.registry().counter(name) for name in (
        "client.retry", "client.failover", "client.hedge.sent",
        "client.hedge.wins", "client.breaker.open",
        "client.breaker.close", "client.pool.evicted")}

    fleet = _Fleet(system_path, servers, base_conf)
    stop = threading.Event()
    build_drills: List[Dict[str, Any]] = []
    build_state: Dict[str, Any] = {"thread": None, "count": 0,
                                   "skipped": 0}
    stats_lock = threading.Lock()
    stats = {"sent": 0, "answered": 0, "mismatch": 0, "lost": 0}
    clean_lat: List[float] = []
    fault_lat: List[float] = []
    in_fault_phase = threading.Event()

    def point_spec(k: int) -> Dict[str, Any]:
        return {"source": {"format": "parquet", "path": data},
                "filter": {"op": "==", "col": "k", "value": int(k)},
                "select": ["k", "v"]}

    agg_spec = {"source": {"format": "parquet", "path": data},
                "filter": {"op": "<", "col": "k", "value": n},
                "aggs": {"t": ["v", "sum"]}}

    fc = None
    try:
        for i in range(servers):
            fleet.spawn(i)
        # Pay each child's cold first-query cost (plan compile, index
        # open) OUTSIDE the measured windows, per endpoint — otherwise
        # the clean baseline is empty or dominated by warm-up.
        from hyperspace_tpu.interop import QueryClient
        for address in fleet.endpoints():
            warm = QueryClient(address)
            try:
                warm.query(point_spec(0))
                warm.query(agg_spec)
            finally:
                warm.close()
        fc = FleetQueryClient(
            fleet.endpoints(), conf=s.conf,
            max_attempts=max(6, 2 * servers),
            hedge_enabled=True, breaker_enabled=True,
            breaker_failures=3, breaker_cooldown_ms=500.0)

        def load(worker: int) -> None:
            lrng = random.Random(seed * 1000 + worker)
            while not stop.is_set():
                k = lrng.randrange(n)
                mixed = lrng.random() < 0.1
                spec = agg_spec if mixed else point_spec(k)
                t0 = time.monotonic()
                try:
                    table = fc.query(spec, deadline_ms=deadline_ms)
                except Exception:  # noqa: BLE001 — a lost request is
                    with stats_lock:  # the invariant, not a crash
                        stats["sent"] += 1
                        stats["lost"] += 1
                    continue
                elapsed = (time.monotonic() - t0) * 1000.0
                got = table.column("t" if mixed else "v").to_pylist()
                want = [expected_sum] if mixed else [expected[k]]
                with stats_lock:
                    stats["sent"] += 1
                    stats["answered"] += 1
                    if got != want:
                        stats["mismatch"] += 1
                    (fault_lat if in_fault_phase.is_set()
                     else clean_lat).append(elapsed)

        threads = [threading.Thread(target=load, args=(w,), daemon=True)
                   for w in range(load_threads)]
        for t in threads:
            t.start()

        # Clean warm-up: a latency baseline before any fault fires.
        time.sleep(max(0.5, schedule[0]["t"] if schedule else 0.5))
        in_fault_phase.set()
        t_start = time.monotonic()
        for event in schedule:
            delay = event["t"] - (time.monotonic() - t_start)
            if delay > 0:
                time.sleep(delay)
            op = event["op"]
            if op == "append":
                extra = pa.table({
                    "k": pa.array(np.arange(n, n + 50, dtype=np.int64)),
                    "v": pa.array(
                        np.arange(n, n + 50, dtype=np.int64) * 3 + 1),
                })
                # Write-then-rename: a server scanning the source dir
                # mid-append must see the whole file or no file, never
                # a torn parquet footer.
                tmp = os.path.join(workdir, "part-00010000.parquet.tmp")
                pq.write_table(extra, tmp)
                faults.atomic_replace(tmp, os.path.join(
                    data, "part-00010000.parquet"), "data.write")
            elif op == "kill":
                fleet.kill(event["server"])
                time.sleep(event["down_s"])
                fleet.spawn(event["server"])
            elif op == "stop":
                fleet.stop_cont(event["server"], event["stop_s"])
            elif op == "client-fault":
                faults.install(faults.FaultPlan(
                    site=event["site"], kind=event["kind"],
                    at=event["at"], count=event["count"],
                    latency_ms=40.0, hang_s=0.3))
                time.sleep(0.4)
                faults.clear()
            elif op == "kill-build-host":
                prev = build_state["thread"]
                if prev is not None and prev.is_alive():
                    build_state["skipped"] += 1
                else:
                    tag = build_state["count"]
                    build_state["count"] += 1

                    def _drill(tag=tag, victim=event["victim"]):
                        try:
                            build_drills.append(
                                _build_drill(workdir, bsrc, tag, victim))
                        except Exception as exc:  # noqa: BLE001 — a
                            # crashed drill IS the violation, not ours
                            build_drills.append(
                                {"tag": tag, "ok": False,
                                 "error": str(exc)})

                    th = threading.Thread(target=_drill, daemon=True)
                    build_state["thread"] = th
                    th.start()
            elif op == "bounce-armed":
                fleet.kill(event["server"])
                fleet.spawn(event["server"], extra_conf={
                    "hyperspace.system.faultInjection.enabled": True,
                    "hyperspace.system.faultInjection.site":
                        event["site"],
                    "hyperspace.system.faultInjection.kind":
                        event["kind"],
                    "hyperspace.system.faultInjection.at": event["at"],
                    "hyperspace.system.faultInjection.count":
                        event["count"],
                })
        # Let the fleet settle and the last retries land.
        time.sleep(1.0)
        th = build_state["thread"]
        if th is not None:
            th.join(timeout=90.0)
        stop.set()
        for t in threads:
            t.join(timeout=deadline_ms / 1000.0 + 5.0)

        # Drive maintenance to completion from the driver too: the
        # appended data's refresh must land EXACTLY once fleet-wide.
        refresh_done = 0
        deadline = time.monotonic() + lease_ttl_s + 15.0
        while time.monotonic() < deadline:
            try:
                hs.maintenance_cycle()
            except Exception as exc:  # noqa: BLE001 — contested cycles
                # may lose CAS races; the journal decides who executed.
                report["driver_maintenance_error"] = str(exc)
            refresh_done = sum(
                1 for r in lifecycle_journal.records(s.conf)
                if r.get("decision") == "refresh"
                and r.get("outcome") == "done"
                and r.get("index") == "cix")
            if refresh_done:
                break
            time.sleep(0.3)
        report["maintenance_refresh_done"] = refresh_done
    finally:
        stop.set()
        faults.clear()
        netfaults.clear_parked()
        # Gauge before close: close() zeroes open_now (no client, no
        # routing table), and the invariant grades the drill's view.
        open_now = float(
            metrics.snapshot().get("client.breaker.open_now", 0.0) or 0.0)
        if fc is not None:
            fc.close()
        fleet.teardown()

    deltas = {name: metrics.registry().counter(name) - base
              for name, base in c0.items()}
    report.update({
        "sent": stats["sent"], "answered": stats["answered"],
        "lost": stats["lost"], "mismatch": stats["mismatch"],
        "clean_p50_ms": round(_percentile(clean_lat, 0.50), 2),
        "clean_p99_ms": round(_percentile(clean_lat, 0.99), 2),
        "fault_p99_ms": round(_percentile(fault_lat, 0.99), 2),
        "hedge_sent": deltas["client.hedge.sent"],
        "hedge_wins": deltas["client.hedge.wins"],
        "hedge_win_rate": round(
            deltas["client.hedge.wins"]
            / max(1.0, deltas["client.hedge.sent"]), 3),
        "breaker_opens": deltas["client.breaker.open"],
        "breaker_closes": deltas["client.breaker.close"],
        "breaker_open_now": open_now,
        "pool_evicted": deltas["client.pool.evicted"],
        "retries": deltas["client.retry"],
        "failovers": deltas["client.failover"],
        "build_drills": build_drills,
        "build_drills_skipped": build_state["skipped"],
    })
    # SLO-alert invariant, after the fleet is torn down: the driver's
    # own serve counters are untouched by the storm above, so the
    # availability objective grades EXACTLY the drill's armed fault.
    try:
        report["alert_drill"] = _alert_drill(s)
    except Exception as exc:  # noqa: BLE001 — a crashed drill IS the
        report["alert_drill"] = {"ok": False,  # violation, not ours
                                 "error": str(exc)}
    violations: List[str] = []
    if stats["lost"]:
        violations.append(f"{stats['lost']} lost request(s)")
    if stats["mismatch"]:
        violations.append(f"{stats['mismatch']} non-bit-equal answer(s)")
    if stats["sent"] != stats["answered"] + stats["lost"]:
        violations.append("request accounting does not add up")
    if report["maintenance_refresh_done"] != 1:
        violations.append(
            f"maintenance refresh executed "
            f"{report['maintenance_refresh_done']}x (want exactly 1)")
    if deltas["client.hedge.wins"] > deltas["client.hedge.sent"]:
        violations.append("hedge wins exceed hedges sent")
    if deltas["client.failover"] > deltas["client.retry"]:
        violations.append("failovers exceed retries")
    if deltas["client.breaker.close"] > deltas["client.breaker.open"]:
        violations.append("breaker closes exceed opens")
    if not 0 <= open_now <= servers:
        violations.append(
            f"breaker open_now gauge {open_now} outside [0, {servers}]")
    bad_builds = sum(1 for d in build_drills if not d.get("ok"))
    if bad_builds:
        violations.append(
            f"{bad_builds} kill-build-host drill(s) failed "
            f"(non-bit-equal, missing kill, or commits != 1)")
    if not report["alert_drill"].get("ok"):
        ad = report["alert_drill"]
        violations.append(
            "alert drill failed: "
            f"fired={ad.get('fired')} bundle_ok={ad.get('bundle_ok')} "
            f"resolved={ad.get('resolved')} "
            f"error={ad.get('error', '')!r}")
    report["violations"] = violations
    report["ok"] = not violations
    return report


def main(argv: Optional[List[str]] = None) -> int:
    import argparse

    parser = argparse.ArgumentParser(
        description="Seeded fleet chaos drill (see interop/chaos.py)")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--duration", type=float, default=6.0)
    parser.add_argument("--servers", type=int, default=3)
    parser.add_argument("--threads", type=int, default=2)
    parser.add_argument("--schedule-only", action="store_true",
                        help="print the deterministic schedule and exit")
    args = parser.parse_args(argv)
    if args.schedule_only:
        print(json.dumps(build_schedule(
            args.seed, args.duration, args.servers), indent=2))
        return 0
    report = run_chaos(seed=args.seed, duration_s=args.duration,
                       servers=args.servers, load_threads=args.threads)
    print(json.dumps(report, indent=2, default=str))
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
