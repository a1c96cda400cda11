"""Cross-process maintenance lease: exactly one daemon per index tree.

N serving processes over one shared index tree each run a
:class:`~hyperspace_tpu.lifecycle.daemon.MaintenanceDaemon`; without
coordination they race the same refresh (wasted builds at best,
optimistic-concurrency churn at worst).  The lease elects ONE executor
through the PR 2 :class:`~hyperspace_tpu.io.log_store.LogStore` CAS
seam — no new infrastructure, the same ``put_if_generation_match``
primitive that arbitrates index-log ids, over both backends.

One JSON record at ``<systemPath>/_hyperspace_lease/maintenance``:

  ``{"v": 1, "holder": "<host>-<pid>-<start_ms>", "epoch": N,
     "acquired_at": ts, "expires_at": ts}``

Protocol (conf ``hyperspace.lifecycle.lease.enabled`` / ``.ttlS``):

  - **Acquire**: read the record with its generation; if absent,
    unparseable (a torn put burned the key), or expired past its
    ``expires_at``, CAS a fresh record at the observed generation with
    ``epoch + 1``.  CAS loss means another candidate won — idle-poll.
  - **Renew**: the holder CASes a new ``expires_at`` against the
    generation of its OWN last commit.  A renew that loses the CAS
    means the lease was taken over while this process was paused,
    swapped out, or partitioned: the holder is **fenced** — it must
    treat itself as a loser immediately, never acting on the stale
    epoch.  Wall-clock expiry is also checked locally, so a holder
    that cannot reach the store stops acting after TTL even though
    nobody fenced it yet.
  - **Store-latency margin**: the holder measures every lease store
    round-trip (EWMA) and treats its own expiry as
    ``expires_at - margin`` where the margin covers a couple of
    slow store operations (clamped to at most TTL/3).  A renew whose
    CAS black-holes on a degraded link therefore stops the holder
    BEFORE the wall-clock TTL a successor acquires against — the
    window where a zombie still believes it holds while the new
    epoch is already executing is closed by measurement, not luck.
  - **Epoch fencing**: every takeover bumps ``epoch``; a zombie's
    renew can never succeed (its generation is stale) and anything it
    might stamp with its old epoch is distinguishable after the fact.
  - **Release**: a stopping holder commits the record back with
    ``expires_at = 0`` so the next candidate takes over on its next
    poll instead of waiting out the TTL.

Every acquire / takeover / renew / fence / release event lands in the
lifecycle journal (decision kind ``lease``) — the same durable,
cross-process record every other maintenance decision gets, and what
the churn test asserts double-execution freedom against.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, Optional

LEASE_DIR = "_hyperspace_lease"
LEASE_KEY = "maintenance"
RECORD_VERSION = 1


def enabled(conf) -> bool:
    return bool(getattr(conf, "lifecycle_lease_enabled", False))


def ttl_s(conf) -> float:
    return max(0.1, float(getattr(conf, "lifecycle_lease_ttl_s", 30.0)))


def lease_root(conf) -> str:
    from hyperspace_tpu.index.path_resolver import PathResolver

    return os.path.join(PathResolver(conf).system_path, LEASE_DIR)


def _store(conf):
    from hyperspace_tpu.io.log_store import store_for

    return store_for(conf, lease_root(conf))


def _parse(payload: Optional[bytes]) -> Optional[Dict[str, Any]]:
    if not payload:
        return None
    try:
        rec = json.loads(payload.decode("utf-8"))
    except (ValueError, UnicodeDecodeError):
        return None  # torn put burned the key; treat as up for grabs
    return rec if isinstance(rec, dict) else None


def status(conf) -> Optional[Dict[str, Any]]:
    """The current lease record (plus ``fresh``: not yet expired), or
    None when absent/unreadable — what ``fleet.daemons`` grades
    against.  Never raises."""
    try:
        payload, _gen = _store(conf).read_with_generation(LEASE_KEY)
    except Exception:  # noqa: BLE001 — an unreadable lease reads absent
        return None
    rec = _parse(payload)
    if rec is None:
        return None
    rec = dict(rec)
    rec["fresh"] = float(rec.get("expires_at", 0.0)) > time.time()
    return rec


class MaintenanceLease:
    """One process's handle on the maintenance lease.  All methods are
    exception-safe towards "not holding": a store failure never
    crashes the daemon, it just parks it this cycle."""

    def __init__(self, conf, owner: Optional[str] = None) -> None:
        from hyperspace_tpu.telemetry import fleet

        self.conf = conf
        self.owner = owner or fleet.process_identity()
        self.epoch = 0
        self._held = False
        self._gen = 0            # generation of OUR last committed record
        self._expires_at = 0.0   # local wall-clock view of our expiry
        self._lat_ewma_s = 0.0   # measured lease store round-trip EWMA

    # -- state ---------------------------------------------------------------
    def margin_s(self) -> float:
        """How early (before wall-clock expiry) this holder stops
        acting: two measured store round-trips of headroom — a renew
        slower than that is already at risk of landing after a
        takeover — clamped to [2% TTL, TTL/3] so a cold EWMA still
        leaves a beat and a pathological one can't eat the lease."""
        ttl = ttl_s(self.conf)
        return min(ttl / 3.0, max(2.0 * self._lat_ewma_s, 0.02 * ttl))

    def holds(self) -> bool:
        """Held AND not within ``margin_s`` of our own wall-clock
        expiry — a holder that lost contact with the store must stop
        acting BEFORE a successor can legitimately take over, with the
        margin covering the store latency its own renews have been
        measuring."""
        return self._held and \
            time.time() < self._expires_at - self.margin_s()

    def _observe_latency(self, elapsed_s: float) -> None:
        self._lat_ewma_s = elapsed_s if self._lat_ewma_s <= 0.0 \
            else 0.7 * self._lat_ewma_s + 0.3 * elapsed_s

    # -- protocol ------------------------------------------------------------
    def ensure(self) -> bool:
        """The per-cycle entry point: renew when holding, otherwise try
        to acquire.  True iff this process may execute maintenance."""
        try:
            if self._held:
                return self.renew()
            return self.try_acquire()
        except Exception as e:  # noqa: BLE001 — a store failure parks the
            # daemon for a cycle; it must never kill it.
            self._note("error", outcome="error", error=str(e))
            self._held = False
            return False

    def try_acquire(self) -> bool:
        from hyperspace_tpu.telemetry import metrics

        store = _store(self.conf)
        t0 = time.monotonic()
        payload, gen = store.read_with_generation(LEASE_KEY)
        self._observe_latency(time.monotonic() - t0)
        rec = _parse(payload)
        now = time.time()
        if rec is not None and float(rec.get("expires_at", 0.0)) > now:
            return False  # live holder; idle-poll
        prior_epoch = int(rec.get("epoch", 0)) if rec is not None else 0
        takeover = rec is not None
        t0 = time.monotonic()
        committed = store.put_if_generation_match(
            LEASE_KEY, self._record(prior_epoch + 1, now), gen)
        self._observe_latency(time.monotonic() - t0)
        if not committed:
            metrics.inc("lease.conflicts")
            return False  # another candidate won this round
        self.epoch = prior_epoch + 1
        self._held = True
        self._gen = gen + 1
        self._expires_at = now + ttl_s(self.conf)
        metrics.inc("lease.acquires")
        if takeover:
            metrics.inc("lease.takeovers")
            self._note("takeover",
                       reason=f"expired lease epoch {prior_epoch} "
                              f"(holder {rec.get('holder', '?')}) taken "
                              f"over as epoch {self.epoch}")
        else:
            self._note("acquire", reason=f"fresh lease, epoch {self.epoch}")
        return True

    def renew(self) -> bool:
        from hyperspace_tpu.telemetry import metrics

        if not self._held:
            return False
        store = _store(self.conf)
        now = time.time()
        t0 = time.monotonic()
        renewed = store.put_if_generation_match(
            LEASE_KEY, self._record(self.epoch, now), self._gen)
        self._observe_latency(time.monotonic() - t0)
        if renewed:
            self._gen += 1
            self._expires_at = now + ttl_s(self.conf)
            metrics.inc("lease.renews")
            self._note("renew", reason=f"epoch {self.epoch}")
            return True
        # CAS lost: the lease moved under us while this process was
        # paused/partitioned — we are FENCED.  Stop acting immediately;
        # the next cycle competes as an ordinary candidate.
        self._held = False
        self._gen = 0
        metrics.inc("lease.fenced")
        self._note("fence", outcome="error",
                   reason=f"renew lost the CAS at epoch {self.epoch}; "
                          f"lease taken over — standing down")
        return False

    def release(self) -> None:
        """Hand off cleanly: commit the record back expired so the next
        candidate takes over on its next poll, not after a full TTL."""
        from hyperspace_tpu.telemetry import metrics

        if not self._held:
            return
        try:
            store = _store(self.conf)
            rec = self._record(self.epoch, time.time())
            rec_d = json.loads(rec.decode("utf-8"))
            rec_d["expires_at"] = 0.0
            store.put_if_generation_match(
                LEASE_KEY, json.dumps(rec_d).encode("utf-8"), self._gen)
            metrics.inc("lease.releases")
            self._note("release", reason=f"epoch {self.epoch} released")
        except Exception as e:  # noqa: BLE001 — best-effort; TTL expiry
            # is the backstop when release IO fails.
            self._note("error", outcome="error", error=str(e))
        finally:
            self._held = False
            self._gen = 0

    # -- internals -----------------------------------------------------------
    def _record(self, epoch: int, now: float) -> bytes:
        return json.dumps({
            "v": RECORD_VERSION,
            "holder": self.owner,
            "epoch": epoch,
            "acquired_at": now,
            "expires_at": now + ttl_s(self.conf),
        }).encode("utf-8")

    def _note(self, event: str, reason: str = "", outcome: str = "done",
              error: str = "") -> None:
        from hyperspace_tpu.lifecycle import journal

        rec = {
            "decision": "lease",
            "index": "",
            "mode": event,
            "reason": reason,
            "outcome": outcome,
            "holder": self.owner,
            "epoch": self.epoch,
        }
        if error:
            rec["error"] = error[:500]
        journal.append(self.conf, rec)


class WorkClaims:
    """A crash-recoverable work-claim table: the maintenance lease's
    TTL + epoch-fencing protocol generalized from ONE singleton lease to
    a SET of named work items (the multi-host build's chunk and
    bucket-group claims, ``parallel/multihost_build.py``).

    One JSON record per item at ``<store root>/claim-<item>`` (keys
    stay flat: both LogStore backends list one level):

      pending: ``{"v": 1, "item", "holder", "epoch", "acquired_at",
                  "expires_at", "done": false}``
      done:    ``{"v": 1, "item", "holder", "epoch", "done": true,
                  "completed_at", "result": {...}}``

    The protocol is the lease's, per item:

      - ``try_claim`` CASes a fresh record over absent / torn / expired
        pending records (an epoch bump per takeover); a done record is
        final and never reclaimed.
      - ``renew`` CASes against the holder's own last generation; a CAS
        loss means the item was reclaimed while this process was paused
        — the holder is **fenced** and must discard its work.
      - ``complete`` commits the done record through the SAME CAS, so a
        fenced zombie's completion loses deterministically: exactly one
        done record per item ever lands, which is what makes the
        downstream commit exactly-once.
      - ``holds`` applies the store-RTT margin (``margin_s``): a holder
        whose clock runs slow, or whose store link degraded, stands
        down ``margin_s`` BEFORE wall-clock expiry — before a successor
        can legitimately reclaim against the wall clock.

    Acquire / reclaim / fence / complete events land in the lifecycle
    journal under decision kind ``claim`` — the durable record the
    exactly-once tests assert against, like ``lease.fenced``."""

    PREFIX = "claim-"

    def __init__(self, store, conf, owner: Optional[str] = None,
                 ttl_s: float = 10.0, index: str = "") -> None:
        from hyperspace_tpu.telemetry import fleet

        self.store = store
        self.conf = conf
        self.owner = owner or fleet.process_identity()
        self.ttl_s = max(0.1, float(ttl_s))
        self.index = index
        self._lat_ewma_s = 0.0

    # -- state ---------------------------------------------------------------
    def margin_s(self) -> float:
        """Same headroom rule as :meth:`MaintenanceLease.margin_s`: two
        measured store round-trips, clamped to [2% TTL, TTL/3]."""
        return min(self.ttl_s / 3.0,
                   max(2.0 * self._lat_ewma_s, 0.02 * self.ttl_s))

    def holds(self, claim: Dict[str, Any]) -> bool:
        """The claim is still safely ours: within ``margin_s`` of local
        expiry the holder must stand down even though nobody fenced it
        yet — the successor acquires against the wall clock, and the
        margin covers the store latency our own operations measured."""
        return time.time() < \
            float(claim.get("expires_at", 0.0)) - self.margin_s()

    def _observe_latency(self, elapsed_s: float) -> None:
        self._lat_ewma_s = elapsed_s if self._lat_ewma_s <= 0.0 \
            else 0.7 * self._lat_ewma_s + 0.3 * elapsed_s

    def _key(self, item: str) -> str:
        return self.PREFIX + item

    def get(self, item: str):
        """(record-or-None, generation) — a torn put reads as (None, g)
        with its REAL burned generation, so reclaim CASes over it."""
        t0 = time.monotonic()
        payload, gen = self.store.read_with_generation(self._key(item))
        self._observe_latency(time.monotonic() - t0)
        return _parse(payload), gen

    def result(self, item: str) -> Optional[Dict[str, Any]]:
        """The completed item's result payload, or None while pending."""
        rec, _gen = self.get(item)
        if rec is not None and rec.get("done"):
            return rec.get("result", {})
        return None

    def pending(self, items) -> list:
        """The subset of ``items`` with no done record yet."""
        return [it for it in items if self.result(it) is None]

    # -- protocol ------------------------------------------------------------
    def try_claim(self, item: str) -> Optional[Dict[str, Any]]:
        """Claim one item if it is absent, torn, or expired.  Returns a
        claim handle (``{"item", "epoch", "gen", "expires_at"}``) to
        pass to renew/complete, or None (done, fresh holder, or a CAS
        loss to a racing claimant)."""
        from hyperspace_tpu.telemetry import metrics

        rec, gen = self.get(item)
        now = time.time()
        if rec is not None:
            if rec.get("done"):
                return None  # final; never reclaimed
            if float(rec.get("expires_at", 0.0)) > now:
                return None  # live holder; poll again later
        # A torn record hides its epoch, but every commit bumps the
        # generation by at least one, so gen+1 stays monotonic past any
        # epoch the burned record could have carried.
        prior_epoch = int(rec.get("epoch", gen)) if rec is not None else gen
        epoch = prior_epoch + 1
        body = json.dumps({
            "v": RECORD_VERSION, "item": item, "holder": self.owner,
            "epoch": epoch, "acquired_at": now,
            "expires_at": now + self.ttl_s, "done": False,
        }).encode("utf-8")
        t0 = time.monotonic()
        committed = self.store.put_if_generation_match(
            self._key(item), body, gen)
        self._observe_latency(time.monotonic() - t0)
        if not committed:
            metrics.inc("claims.conflicts")
            return None
        claim = {"item": item, "epoch": epoch, "gen": gen + 1,
                 "acquired_at": now, "expires_at": now + self.ttl_s}
        if rec is not None or gen:
            metrics.inc("claims.reclaims")
            holder = rec.get("holder", "?") if rec is not None else "?"
            self._note("reclaim", item, epoch,
                       reason=f"expired/torn claim (holder {holder}) "
                              f"taken over as epoch {epoch}")
        else:
            metrics.inc("claims.acquires")
            self._note("acquire", item, epoch,
                       reason=f"fresh claim, epoch {epoch}")
        return claim

    def renew(self, claim: Dict[str, Any]) -> bool:
        """Extend our claim; False ⇒ FENCED (reclaimed under us) — the
        caller must abandon the item's work immediately."""
        from hyperspace_tpu.telemetry import metrics

        now = time.time()
        body = json.dumps({
            "v": RECORD_VERSION, "item": claim["item"],
            "holder": self.owner, "epoch": claim["epoch"],
            "acquired_at": now, "expires_at": now + self.ttl_s,
            "done": False,
        }).encode("utf-8")
        t0 = time.monotonic()
        renewed = self.store.put_if_generation_match(
            self._key(claim["item"]), body, claim["gen"])
        self._observe_latency(time.monotonic() - t0)
        if renewed:
            claim["gen"] += 1
            claim["expires_at"] = now + self.ttl_s
            return True
        metrics.inc("claims.fenced")
        self._note("fence", claim["item"], claim["epoch"], outcome="error",
                   reason=f"renew lost the CAS at epoch {claim['epoch']}; "
                          f"claim reclaimed — standing down")
        return False

    def complete(self, claim: Dict[str, Any],
                 result: Optional[Dict[str, Any]] = None) -> bool:
        """Commit the item's done record through the claim's CAS.  False
        ⇒ fenced: another holder reclaimed the item, and whatever this
        one produced must be discarded (its staged files are orphans)."""
        from hyperspace_tpu.telemetry import metrics

        body = json.dumps({
            "v": RECORD_VERSION, "item": claim["item"],
            "holder": self.owner, "epoch": claim["epoch"], "done": True,
            "acquired_at": claim.get("acquired_at", 0.0),
            "completed_at": time.time(), "result": result or {},
        }).encode("utf-8")
        t0 = time.monotonic()
        committed = self.store.put_if_generation_match(
            self._key(claim["item"]), body, claim["gen"])
        self._observe_latency(time.monotonic() - t0)
        if committed:
            claim["gen"] += 1
            metrics.inc("claims.completes")
            self._note("complete", claim["item"], claim["epoch"],
                       reason=f"epoch {claim['epoch']} done")
            return True
        metrics.inc("claims.fenced")
        self._note("fence", claim["item"], claim["epoch"], outcome="error",
                   reason=f"complete lost the CAS at epoch "
                          f"{claim['epoch']}; output discarded")
        return False

    # -- internals -----------------------------------------------------------
    def _note(self, event: str, item: str, epoch: int, reason: str = "",
              outcome: str = "done") -> None:
        from hyperspace_tpu.lifecycle import journal

        journal.append(self.conf, {
            "decision": "claim",
            "index": self.index,
            "mode": event,
            "reason": reason,
            "outcome": outcome,
            "holder": self.owner,
            "epoch": epoch,
            "item": item,
        })
