"""The action state machine: generic begin → op → end protocol with
optimistic concurrency.

Reference contract: actions/Action.scala:35-105 —
  - ``base_id`` is captured from the latest log id when the action starts (:35)
  - ``begin()`` writes a *transient*-state entry at ``base_id + 1`` (:49-55);
    the create-if-absent write is what detects concurrent writers
  - ``op()`` does the actual work (:58)
  - ``end()`` writes the *final*-state entry at ``base_id + 2``, deleting and
    recreating the ``latestStable`` pointer (:60-75)
  - ``run()`` wraps the protocol with validation, telemetry, and
    NoChangesException no-op handling (:84-105)

An action that dies mid-flight leaves the transient entry as the latest log
record; subsequent actions refuse to run and the user recovers with
``cancel()`` (actions/CancelAction.scala:25-58) — or, with
``hyperspace.index.autoRecovery.enabled``, the next lifecycle call through
the collection manager performs that rollback implicitly
(index/manager.py).  Crash points are exercised under injected faults
(io/faults.py, tests/test_concurrency.py's TestCrashRecovery).

Beyond the reference: ``run()`` is an **optimistic transaction loop**
(the Delta-style commit model).  A ``ConcurrentWriteError`` no longer
necessarily aborts the action — when the collection manager armed
``hyperspace.index.concurrency.maxRetries``, the action REBASES
(recaptures ``base_id`` / the previous entry from the state the winning
writer left behind), re-validates, and retries the whole
begin→op→end sequence after a jittered backoff.  A retry whose
re-validation finds nothing left to do (the winner did our work) exits
through the normal NoChangesError no-op path; one that finds a
structurally impossible state (e.g. create over a now-ACTIVE index)
surfaces the validation error.  Work a failed attempt already wrote
(an uncommitted ``v__=N`` data dir, a stale transient entry below the
winner's commits) is exactly the state cancel()/auto-recovery and
vacuum already clean up.
"""

from __future__ import annotations

import random
import time
from typing import Optional, Type

from hyperspace_tpu.exceptions import ConcurrentWriteError, HyperspaceError, NoChangesError
from hyperspace_tpu.index.log_entry import IndexLogEntry, States
from hyperspace_tpu.index.log_manager import IndexLogManager
from hyperspace_tpu.io import faults
from hyperspace_tpu.telemetry.events import _IndexActionEvent, emit_event
from hyperspace_tpu.utils.retry import RetryPolicy


class Action:
    # Subclasses set these.
    transient_state: str = ""
    final_state: str = ""
    event_class: Optional[Type[_IndexActionEvent]] = None
    # Conflict-retry budget + backoff schedule for the optimistic
    # transaction loop.  Class-level default 0 keeps directly-constructed
    # actions on the reference's abort-on-conflict contract; the
    # collection manager overrides the INSTANCE attributes from
    # ``hyperspace.index.concurrency.maxRetries`` / the io.retry backoff
    # keys (index/manager._dispatch).
    concurrency_max_retries: int = 0
    conflict_backoff: RetryPolicy = RetryPolicy()

    def __init__(self, log_manager: IndexLogManager) -> None:
        self.log_manager = log_manager
        # base_id MUST be captured eagerly (Action.scala:35 is a val): the
        # optimistic-concurrency check works only if begin()/end() write at
        # ids derived from the state this action validated against.
        latest = self.log_manager.get_latest_id()
        self._base_id: int = 0 if latest is None else latest
        self.previous_log_entry: Optional[IndexLogEntry] = self.log_manager.get_latest_log()
        # Conflicts absorbed by the transaction loop this run (observable
        # by tests and telemetry consumers).
        self.conflict_retries: int = 0
        # Per-run performance attribution (telemetry/build_report.py):
        # owned by the ACTION so spill worker threads can record into it
        # without contextvar propagation.  run() finalizes and publishes.
        from hyperspace_tpu.telemetry.build_report import BuildReport

        self.build_report = BuildReport(action=type(self).__name__)

    # -- protocol pieces ----------------------------------------------------
    @property
    def base_id(self) -> int:
        return self._base_id

    @property
    def index_name(self) -> str:
        if self.previous_log_entry is not None:
            return self.previous_log_entry.name
        return ""

    def validate(self) -> None:
        """Precondition check; raise HyperspaceError (or NoChangesError for
        benign no-ops) before any state is written."""

    def op(self) -> None:
        raise NotImplementedError

    def log_entry(self) -> IndexLogEntry:
        """The entry to commit at end(); built after op() so it can reference
        freshly written index data."""
        raise NotImplementedError

    # -- protocol -----------------------------------------------------------
    def begin(self) -> None:
        entry = self.log_entry_for_begin()
        entry.state = self.transient_state
        self.log_manager.write_log_or_raise(self.base_id + 1, entry)

    def log_entry_for_begin(self) -> IndexLogEntry:
        """Entry written at begin(); by default the previous entry (actions on
        existing indexes).  CreateAction overrides to build a fresh one."""
        if self.previous_log_entry is None:
            raise HyperspaceError("No existing index log entry for this action")
        import copy

        return copy.deepcopy(self.previous_log_entry)

    def end(self) -> None:
        entry = self.log_entry()
        entry.state = self.final_state
        self.log_manager.delete_latest_stable_log()
        self.log_manager.write_log_or_raise(self.base_id + 2, entry)
        self.log_manager.create_latest_stable_log(self.base_id + 2)

    def _rebase(self) -> None:
        """Recapture the optimistic-concurrency baseline after a conflict:
        the next attempt must validate against — and write at ids derived
        from — the state the WINNING writer committed, or the retry would
        just re-collide (or worse, resurrect state the winner superseded).
        Subclasses with richer captured state (refresh's previous stable
        entry + file-id tracker) extend this."""
        latest = self.log_manager.get_latest_id()
        self._base_id = 0 if latest is None else latest
        self.previous_log_entry = self.log_manager.get_latest_log()

    def run(self) -> str:
        """Action.scala:84-105, wrapped in the conflict-retrying
        transaction loop (concurrency_max_retries=0 ⇒ reference
        behavior: first conflict aborts).  Returns the outcome —
        ``"ok"`` for a committed run, ``"noop"`` for a benign
        NoChangesError no-op — so dispatchers (the refresh summary, the
        maintenance daemon) can tell the two apart without re-reading
        the log.

        Every turn of the loop is telemetry-visible: a ``CONFLICT_RETRY
        n/max`` ActionEvent per absorbed conflict (attempt number +
        conflict reason in ``state``/``message``) and the
        ``action.conflict.retries`` counter, so PR 2's silent rebases can
        be audited per action after the fact."""

        def emit(state: str, message: str = "") -> None:
            if self.event_class is not None:
                emit_event(self.event_class(
                    index_name=self.index_name, state=state, message=message))

        rng = random.Random()
        # The report times run() itself: construction-to-run gaps (refresh
        # diffing in __init__) are not this run's wall clock.
        report = self.build_report
        report._t0 = time.perf_counter()
        report.started_at = time.time()
        report.index = self.index_name
        # Timeline profiler (telemetry/timeline.py): apply this session's
        # conf and, when enabled, sample memory in the background for the
        # run's duration — per-phase high-water marks instead of one
        # end-of-action peak.  The finally covers InjectedCrash too: a
        # leaked sampler thread would outlive the simulated kill.
        from hyperspace_tpu.telemetry import timeline
        from hyperspace_tpu.telemetry import build_report as _br

        sampler = None
        session = getattr(self, "session", None)
        if session is not None:
            timeline.configure_from_conf(session.conf)
            if _br.profiling_enabled(session.conf):
                sampler = timeline.start_sampler(session.conf, report)
        try:
            return self._run_transaction(emit, rng)
        finally:
            if sampler is not None:
                sampler.stop()

    def _run_transaction(self, emit, rng) -> str:
        """The conflict-retrying transaction loop proper (split from
        ``run()`` so the sampler's try/finally wraps the whole thing)."""
        from hyperspace_tpu.telemetry.trace import span

        with span(f"action.{type(self).__name__}",
                  index=self.index_name) as sp:
            try:
                while True:
                    try:
                        outcome = self._attempt(emit)
                        if outcome == "ok":
                            # A committed index change makes every cached
                            # optimize result suspect: bump the serving
                            # layer's plan-cache generation so the next
                            # served query re-plans against the new state
                            # (execution/plan_cache.py).
                            from hyperspace_tpu.execution import plan_cache

                            plan_cache.bump_generation()
                        sp.set(conflict_retries=self.conflict_retries)
                        self._finish_report(outcome, "", sp)
                        return outcome
                    except ConcurrentWriteError as e:
                        if self.conflict_retries >= \
                                self.concurrency_max_retries:
                            emit("FAILURE", "concurrent modification")
                            raise
                        self.conflict_retries += 1
                        emit(f"CONFLICT_RETRY "
                             f"{self.conflict_retries}/"
                             f"{self.concurrency_max_retries}",
                             f"concurrent write at base_id={self.base_id}: "
                             f"{e}")
                        # Jittered backoff so two rebased racers don't
                        # re-collide in lockstep (and a stale object-store
                        # listing gets its visibility window to pass before
                        # the re-validation).
                        time.sleep(self.conflict_backoff.delay_s(
                            self.conflict_retries - 1, rng))
                        self._rebase()
            except Exception as e:
                # Failed runs still report (a crashed SPILL phase is
                # exactly when attribution matters); InjectedCrash is a
                # BaseException and skips this like a real kill -9 would.
                self._finish_report("error", str(e), sp)
                raise

    def _finish_report(self, outcome: str, error: str, sp) -> None:
        """Finalize + publish this run's BuildReport; export metrics, tag
        the action span, and append the perf-ledger record.
        Diagnostics must never fail the action — everything here is
        best-effort."""
        from hyperspace_tpu.telemetry import build_report as br
        from hyperspace_tpu.telemetry import perf_ledger

        report = self.build_report
        report.conflict_retries = self.conflict_retries
        report.index = report.index or self.index_name
        session = getattr(self, "session", None)
        conf = session.conf if session is not None else None
        try:
            profiled = conf is None or br.profiling_enabled(conf)
            if profiled:
                report.sample_memory()
            report.finish(outcome, error)
            br.publish(report, session)
            if profiled:
                report.export_metrics()
                report.attach_to_span(sp)
            if conf is not None and profiled:
                perf_ledger.append(conf, {
                    "kind": "action", "name": f"{report.action}"
                    f"({report.index})" if report.index else report.action,
                    **{k: v for k, v in report.to_dict().items()
                       if k not in ("started_at",)},
                    "fingerprint": perf_ledger.fingerprint(conf)})
        except Exception:  # noqa: BLE001 — diagnostics must never fail
            # the action; count the swallowed failure so a broken report/
            # ledger path is at least visible in the registry.
            from hyperspace_tpu.telemetry import metrics

            metrics.inc("build.report.errors")

    def _attempt(self, emit) -> str:
        """One turn of the transaction loop; returns the outcome
        (``"ok"``/``"noop"``) for the build report.  The ``validate`` and
        ``commit`` phases are timed here so a report's phase sum accounts
        for the whole protocol, not just op()'s build work."""
        report = self.build_report
        try:
            with report.phase("validate"):
                self.validate()
        except NoChangesError as e:
            emit(States.ACTIVE, f"No-op: {e}")
            return "noop"
        try:
            with report.phase("commit"):
                self.begin()
            self.op()
            # Crash checkpoint (io/faults.py): the work is done but the
            # final entry is not committed — the state a killed process
            # leaves behind, which cancel()/auto-recovery must roll back.
            # InjectedCrash is a BaseException, so the handlers below
            # (like a real kill -9) never see it.
            faults.check("action.commit")
            with report.phase("commit"):
                self.end()
            emit(self.final_state)
            return "ok"
        except ConcurrentWriteError:
            raise  # run()'s transaction loop arbitrates: retry or FAILURE
        except Exception as e:
            emit("FAILURE", str(e))
            raise
