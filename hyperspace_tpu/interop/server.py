"""Arrow-IPC query server: one JSON request line in, one IPC stream out.

Wire protocol (deliberately minimal so any language can speak it with a
socket plus an Arrow library — no HTTP/gRPC dependency):

  client -> server   one JSON object (the interop/query.py spec),
                     UTF-8, terminated by a newline; may carry a
                     client-minted trace context (``trace_id`` /
                     ``request_id``, 16 hex chars each) the server
                     adopts — malformed ids are replaced by
                     server-minted ones, never rejected
  server -> client   the status line ``OK trace=<trace_id>\\n`` followed
                     by an Arrow IPC STREAM of the result
                     (self-delimiting), or
                     ``ERR <CODE> <message> trace=<trace_id>\\n`` and
                     the connection closes — every response echoes the
                     adopted/minted trace id, so a failure is
                     correlatable from either side (the flight
                     recorder's ``slow_queries``/``trace`` verbs answer
                     for it afterwards)

Error codes split RETRYABLE conditions from permanent ones:

  ``BUSY``      retryable — the server shed the request (admission queue
                full, connection capacity, overload watermark, draining)
  ``DEADLINE``  retryable — the request's deadline expired before the
                result was ready
  ``BADREQ``    permanent — the request itself is malformed
  ``FAILED``    permanent — the engine failed executing a valid request

Pre-taxonomy servers sent bare ``ERR <message>``; :func:`parse_wire_error`
(used by :class:`QueryClient`) still accepts that form, mapping it to
``FAILED``.

Connections are PIPELINED: after a successful response the client may send
the next request on the same connection (an error closes it, keeping
framing unambiguous).  Execution is ADMISSION-CONTROLLED (ROADMAP item 2):
socket IO runs on per-connection threads (bounded by
``hyperspace.serving.maxConnections`` — beyond it the accept loop answers
``ERR BUSY`` without spawning a thread), while query execution runs on a
fixed pool of ``hyperspace.serving.workers`` threads fed by a bounded
admission queue (``hyperspace.serving.queueDepth``).  When the queue is
full — or the process is past a memory/queue-wait watermark — new
requests shed FAST with ``ERR BUSY`` instead of piling onto a saturated
server: under overload the answer degrades to "retry later", never to a
hang, a thread leak, or a torn frame (only the connection's own handler
thread ever writes to its socket, one complete response per request).

Per-request deadlines (spec key ``deadline_ms``, or the conf default
``hyperspace.serving.defaultDeadlineMs``) propagate into
``dataset.collect`` via utils/deadline.py and abort cleanly at executor
phase boundaries; expiry surfaces as ``ERR DEADLINE``.  Repeat queries
skip the optimizer via the plan cache (execution/plan_cache.py), keyed
by the advisor's structural fingerprint + literal digest.  ``drain()``
(or SIGTERM with ``handle_sigterm=True``) stops accepting, finishes
in-flight requests within ``hyperspace.serving.drainGraceS``, then
closes.

The server executes against ONE session, so enabled indexes and conf
govern rewrites exactly as for local use — this is the parity surface for
the reference's py4j bindings / .NET sample
(python/hyperspace/hyperspace.py:9, examples/csharp/Program.cs): a JVM or
.NET client sends the JSON spec and reads the stream with its own Arrow
implementation.
"""

from __future__ import annotations

import json
import os
import queue
import random
import socket
import socketserver
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import pyarrow as pa

from hyperspace_tpu.interop import netfaults

MAX_REQUEST_BYTES = 1 << 20  # a query spec, not a data upload


REQUEST_TIMEOUT_S = 30.0  # an idle connection must not pin a thread + fd

# -- wire error taxonomy ------------------------------------------------------
ERR_BUSY = "BUSY"
ERR_DEADLINE = "DEADLINE"
ERR_BADREQ = "BADREQ"
ERR_FAILED = "FAILED"
KNOWN_WIRE_CODES = (ERR_BUSY, ERR_DEADLINE, ERR_BADREQ, ERR_FAILED)
RETRYABLE_WIRE_CODES = frozenset({ERR_BUSY, ERR_DEADLINE})


class WireError(Exception):
    """Server-side: an error with an explicit wire code (the handler maps
    everything else through :func:`_classify_error`).  ``retry_after_ms``
    rides BUSY sheds as a ``retry-after-ms=<n>`` token on the status
    line — the server's backoff hint, derived from the queue-wait
    EWMA."""

    def __init__(self, code: str, message: str,
                 retry_after_ms: Optional[int] = None) -> None:
        super().__init__(message)
        self.code = code
        self.message = message
        self.retry_after_ms = retry_after_ms


class QueryFailedError(RuntimeError):
    """Client-side: the server answered ``ERR ...``.  ``code`` is one of
    ``BUSY``/``DEADLINE``/``BADREQ``/``FAILED`` (bare pre-taxonomy errors
    map to ``FAILED``); ``retryable`` is True for overload/deadline sheds
    — back off and retry on a FRESH connection (errors close the one they
    arrived on).  ``trace_id`` is the server-echoed trace context (None
    against a pre-trace server): quote it to ``slow_queries()`` / the
    ``trace`` verb to pull the request's full flight record."""

    def __init__(self, code: str, message: str, payload: str,
                 trace_id: Optional[str] = None,
                 retry_after_ms: Optional[int] = None) -> None:
        super().__init__(f"Query failed: {payload}")
        self.code = code
        self.message = message
        self.trace_id = trace_id
        #: Server backoff hint from a ``retry-after-ms=<n>`` status-line
        #: token (BUSY sheds; None against a pre-hint server).
        self.retry_after_ms = retry_after_ms

    @property
    def retryable(self) -> bool:
        return self.code in RETRYABLE_WIRE_CODES


class ServerBusyError(QueryFailedError):
    """The server shed this request (``ERR BUSY``): overload, not a bug.
    Retry with backoff on a new connection — ``retry_after_ms`` is the
    server's suggested wait, derived from its recent queue-wait EWMA
    (None when the server predates the hint)."""


_TRACE_ECHO_RE = None  # compiled lazily; interop/query.py owns the format


def _split_trace_echo(text: str) -> Tuple[str, Optional[str]]:
    """Strip a trailing ``trace=<16 hex>`` token (the server's trace-id
    echo) off a status line, returning ``(rest, trace_id-or-None)``."""
    global _TRACE_ECHO_RE
    if _TRACE_ECHO_RE is None:
        import re

        _TRACE_ECHO_RE = re.compile(r"^(.*?)\s*\btrace=([0-9a-f]{16})\s*$")
    m = _TRACE_ECHO_RE.match(text)
    if m is None:
        return text, None
    return m.group(1), m.group(2)


_RETRY_AFTER_RE = None  # compiled lazily, like the trace echo


def _split_retry_after(text: str) -> Tuple[str, Optional[int]]:
    """Strip a trailing ``retry-after-ms=<n>`` token (the BUSY backoff
    hint) off a status line, returning ``(rest, ms-or-None)``."""
    global _RETRY_AFTER_RE
    if _RETRY_AFTER_RE is None:
        import re

        _RETRY_AFTER_RE = re.compile(
            r"^(.*?)\s*\bretry-after-ms=(\d+)\s*$")
    m = _RETRY_AFTER_RE.match(text)
    if m is None:
        return text, None
    return m.group(1), int(m.group(2))


def parse_wire_error(line: str) -> QueryFailedError:
    """An ``ERR ...`` status line → the typed client error.  Accepts both
    the coded form (``ERR BUSY queue full``) and the pre-taxonomy bare
    form (``ERR something broke`` → code FAILED), so a new client keeps
    working against an old server; a trailing ``trace=<id>`` echo and a
    ``retry-after-ms=<n>`` hint are lifted into ``.trace_id`` /
    ``.retry_after_ms`` either way (old bare ``ERR BUSY`` lines still
    parse, with both None)."""
    payload = line[4:] if line.startswith("ERR ") else line
    stripped, trace_id = _split_trace_echo(payload)
    stripped, retry_after_ms = _split_retry_after(stripped)
    code, _, rest = stripped.partition(" ")
    if code in KNOWN_WIRE_CODES and rest:
        cls = ServerBusyError if code == ERR_BUSY else QueryFailedError
        return cls(code, rest, payload, trace_id, retry_after_ms)
    return QueryFailedError(ERR_FAILED, stripped, payload, trace_id,
                            retry_after_ms)


def _classify_error(exc: BaseException) -> Tuple[str, str]:
    """(wire code, message) for an exception crossing the wire boundary."""
    from hyperspace_tpu.exceptions import DeadlineExceededError

    if isinstance(exc, WireError):
        return exc.code, exc.message
    if isinstance(exc, QueryFailedError):
        # A proxied upstream error keeps its code across this hop —
        # BUSY stays retryable (and keeps its retry-after hint) through
        # the front door instead of degrading to permanent FAILED.
        return exc.code, exc.message
    if isinstance(exc, DeadlineExceededError):
        return ERR_DEADLINE, str(exc)
    if isinstance(exc, ValueError):
        # The spec decoders (interop/query.py, the SQL front end) raise
        # ValueError for malformed requests — the client's fault.
        return ERR_BADREQ, str(exc)
    return ERR_FAILED, f"{type(exc).__name__}: {exc}"


def _current_rss_mb() -> float:
    """CURRENT resident set in MB (Linux /proc; falls back to the POSIX
    peak, which can only over-shed — the conservative failure mode for an
    overload watermark)."""
    try:
        with open("/proc/self/statm") as f:
            pages = int(f.read().split()[1])
        return pages * os.sysconf("SC_PAGE_SIZE") / float(1 << 20)
    except Exception:  # noqa: BLE001 — non-Linux
        try:
            import resource

            return resource.getrusage(
                resource.RUSAGE_SELF).ru_maxrss / 1024.0
        except Exception:  # noqa: BLE001
            return 0.0


# -- the bounded worker pool --------------------------------------------------
class _Job:
    """One admitted request: the execute closure plus its rendezvous.
    Workers compute; the connection's handler thread does ALL socket IO —
    that single-writer discipline is what makes torn frames impossible."""

    __slots__ = ("fn", "kind", "deadline_at", "enqueued_t", "done",
                 "result", "error", "report", "abandoned",
                 "trace_id", "request_id", "root_span", "queue_wait_ms",
                 "tenant")

    def __init__(self, fn: Callable[[], pa.Table], kind: str,
                 deadline_at: Optional[float], trace_id: str = "",
                 request_id: str = "", tenant: str = "") -> None:
        self.fn = fn
        self.kind = kind
        self.tenant = tenant  # wire tenant id ("" = untagged)
        self.deadline_at = deadline_at  # absolute time.monotonic(), or None
        self.enqueued_t = time.monotonic()
        self.done = threading.Event()
        self.result: Optional[pa.Table] = None
        self.error: Optional[BaseException] = None
        self.report = None  # the query's run report, for the verb surface
        self.abandoned = False  # handler gave up waiting; discard result
        self.trace_id = trace_id      # wire trace context (adopted or
        self.request_id = request_id  # minted by the handler)
        self.root_span = None    # the serve.request Span when tracing on
        self.queue_wait_ms: Optional[float] = None


class _WorkerPool:
    """Fixed worker threads over a bounded admission queue — the hard cap
    on concurrent query execution, and the seam every shed decision goes
    through."""

    _EWMA_ALPHA = 0.2

    def __init__(self, session, workers: int, queue_depth: int) -> None:
        self._session = session
        self._queue: "queue.Queue" = queue.Queue(maxsize=max(1, queue_depth))
        self._threads: list = []
        self._stop_sentinel = object()
        self._lock = threading.Lock()
        self._idle = threading.Condition(self._lock)
        self._active = 0  # jobs executing right now
        self._queued_or_active = 0  # admitted and not yet finished
        # Requests whose RESPONSE is not yet fully written.  Workers only
        # compute; the connection handler streams the result afterwards —
        # drain() must wait for that write too, or a SIGTERM between
        # "worker done" and "stream flushed" tears the frame mid-send.
        self._open_requests = 0
        self._queue_wait_ewma_ms = 0.0
        self._rss_at = 0.0
        self._rss_mb = 0.0
        # tenant id -> queued-or-active count, for the per-tenant quota
        # (``hyperspace.serving.tenant.maxQueued``): a hot tenant sheds
        # against ITS count while everyone else keeps being admitted.
        self._tenant_queued: Dict[str, int] = {}
        self.draining = False
        self.workers = max(1, int(workers))

    def start(self) -> None:
        for i in range(self.workers):
            t = threading.Thread(target=self._run,
                                 name=f"hs-serve-worker-{i}", daemon=True)
            t.start()
            self._threads.append(t)

    # -- admission ---------------------------------------------------------
    def retry_after_hint_ms(self) -> int:
        """The backoff a shed client should take before retrying: about
        one recent queue wait (the EWMA the latency watermark also
        reads), floored at 100 ms so an idle-queue shed (drain,
        connection cap) still suggests a real pause, capped at 30 s."""
        with self._lock:
            ewma = self._queue_wait_ewma_ms
        return int(max(100.0, min(30_000.0, ewma * 2.0)))

    def _shed(self, reason: str, message: str) -> None:
        from hyperspace_tpu.telemetry import metrics

        metrics.inc("serve.shed")
        metrics.inc(f"serve.shed.{reason}")
        raise WireError(ERR_BUSY, message,
                        retry_after_ms=self.retry_after_hint_ms())

    def submit(self, job: _Job, conf) -> None:
        """Admit ``job`` or shed it with a retryable ``ERR BUSY``."""
        from hyperspace_tpu.telemetry import metrics

        if self.draining:
            self._shed("draining", "server is draining; retry elsewhere")
        rss_mark = float(getattr(conf, "serving_shed_rss_watermark_mb", 0.0))
        if rss_mark > 0:
            now = time.monotonic()
            if now - self._rss_at > 0.2:  # memoize: a stat per ~5 admits
                self._rss_mb = _current_rss_mb()
                self._rss_at = now
            if self._rss_mb > rss_mark:
                self._shed("memory",
                           f"memory watermark: rss {self._rss_mb:.0f} MB > "
                           f"{rss_mark:.0f} MB; retry later")
        wait_mark = float(getattr(conf,
                                  "serving_shed_queue_wait_watermark_ms",
                                  0.0))
        if wait_mark > 0 and self._queue_wait_ewma_ms > wait_mark \
                and self._queue.qsize() > 0:
            self._shed("latency",
                       f"queue-wait watermark: recent wait "
                       f"{self._queue_wait_ewma_ms:.0f} ms > "
                       f"{wait_mark:.0f} ms; retry later")
        # Count BEFORE enqueueing: a worker can finish the job before this
        # thread resumes, and wait_idle must never observe a transient
        # zero while work is genuinely in flight.  The per-tenant quota
        # rides the same critical section so a tenant's count and the
        # global count can never disagree.
        quota = int(getattr(conf, "serving_tenant_max_queued", 0))
        tenant_over = False
        with self._lock:
            if quota > 0 and job.tenant and \
                    self._tenant_queued.get(job.tenant, 0) >= quota:
                tenant_over = True
            else:
                self._queued_or_active += 1
                if job.tenant:
                    self._tenant_queued[job.tenant] = \
                        self._tenant_queued.get(job.tenant, 0) + 1
        if tenant_over:
            metrics.inc(f"serve.tenant.{job.tenant}.shed")
            self._shed("tenant",
                       f"tenant {job.tenant!r} is at its queued quota "
                       f"({quota}); retry later")
        if job.tenant:
            metrics.set_gauge(f"serve.tenant.{job.tenant}.queued",
                              self._tenant_queued.get(job.tenant, 0))
        try:
            self._queue.put_nowait(job)
        except queue.Full:
            with self._idle:
                self._queued_or_active -= 1
                self._release_tenant(job)
                self._idle.notify_all()
            self._shed("queue_full",
                       f"admission queue full "
                       f"(depth {self._queue.maxsize}); retry later")
        metrics.inc("serve.admitted")
        metrics.set_gauge("serve.queue_depth", self._queue.qsize())

    def _release_tenant(self, job: _Job) -> None:
        """Drop one from the job's tenant count (caller holds the lock)."""
        if not job.tenant:
            return
        n = self._tenant_queued.get(job.tenant, 1) - 1
        if n <= 0:
            # hslint: allow[lock-discipline] caller holds self._idle/_lock
            self._tenant_queued.pop(job.tenant, None)
        else:
            # hslint: allow[lock-discipline] caller holds self._idle/_lock
            self._tenant_queued[job.tenant] = n

    def tenant_snapshot(self) -> Dict[str, int]:
        """tenant id -> queued-or-active right now (the ``tenants``
        verb's live column)."""
        with self._lock:
            return dict(self._tenant_queued)

    # -- workers -----------------------------------------------------------
    def _run(self) -> None:
        from hyperspace_tpu.exceptions import DeadlineExceededError
        from hyperspace_tpu.telemetry import metrics
        from hyperspace_tpu.telemetry import trace
        from hyperspace_tpu.utils import deadline as _deadline

        while True:
            item = self._queue.get()
            if item is self._stop_sentinel:
                return
            job: _Job = item
            now = time.monotonic()
            wait_ms = (now - job.enqueued_t) * 1000.0
            job.queue_wait_ms = wait_ms
            metrics.observe("serve.queue_wait_ms", wait_ms)
            metrics.set_gauge("serve.queue_depth", self._queue.qsize())
            with self._lock:
                # The EWMA is a read-modify-write shared across workers;
                # unlocked, two workers interleaving lose updates and the
                # latency watermark sheds on stale numbers.
                self._queue_wait_ewma_ms += self._EWMA_ALPHA * (
                    wait_ms - self._queue_wait_ewma_ms)
                self._active += 1
                metrics.set_gauge("serve.inflight", self._active)
            try:
                if job.abandoned:
                    pass  # handler already answered; don't spend compute
                elif job.deadline_at is not None and now > job.deadline_at:
                    # Expired while QUEUED: zero execution spent on it.
                    job.error = DeadlineExceededError(
                        f"deadline expired after {wait_ms:.0f} ms in the "
                        f"admission queue")
                else:
                    budget = None if job.deadline_at is None \
                        else job.deadline_at - time.monotonic()
                    # This worker's report thread-local could still hold
                    # a PREVIOUS request's report; clear it so a query
                    # that dies before collect() cannot be flight-
                    # recorded against a stale report.
                    self._session.last_run_report_value = None
                    try:
                        # The wire trace context rides the worker's
                        # context: collect() sees a served request, and
                        # the root span carries the ids to the sinks.
                        with trace.request_scope(job.trace_id,
                                                 job.request_id):
                            with trace.span(
                                    "serve.request", kind=job.kind,
                                    trace_id=job.trace_id,
                                    request_id=job.request_id) as sp:
                                if isinstance(sp, trace.Span):
                                    job.root_span = sp
                                with _deadline.scope(budget):
                                    job.result = job.fn()
                                sp.set(queue_wait_ms=round(wait_ms, 1))
                    finally:
                        # The run report lands in this WORKER's
                        # thread-local (success OR failure — the flight
                        # recorder wants the failed query's report too);
                        # hand it to the connection so the
                        # last_run_report verb keeps its
                        # query-then-ask-same-connection contract.
                        job.report = self._session.last_run_report_value
            except BaseException as e:  # noqa: BLE001 — a worker must
                # survive anything a query can throw; the error crosses
                # the wire instead (the handler classifies it).
                job.error = e
            finally:
                # Flight-record BEFORE done.set(): the job's span tree /
                # report are final here, and recording first means a
                # record exists by the time the handler can answer — no
                # live-Span serialization race, no torn record.  The
                # worker owns every ADMITTED job's record (including
                # abandoned ones, whose handler answered DEADLINE long
                # before this abort landed); the handler records only
                # requests that never reached a worker (sheds, BADREQ).
                self._record_flight(job)
                job.done.set()
                with self._idle:
                    self._active -= 1
                    self._queued_or_active -= 1
                    self._release_tenant(job)
                    tenant_left = self._tenant_queued.get(job.tenant, 0) \
                        if job.tenant else 0
                    metrics.set_gauge("serve.inflight", self._active)
                    self._idle.notify_all()
                if job.tenant:
                    metrics.set_gauge(f"serve.tenant.{job.tenant}.queued",
                                      tenant_left)

    def _record_flight(self, job: _Job) -> None:
        """One completed job → one flight-recorder offer (+ the latency
        histogram's exemplar link when the record was retained)."""
        from hyperspace_tpu.telemetry import flight_recorder, metrics

        if job.abandoned:
            # The CLIENT saw ERR DEADLINE regardless of what the aborted
            # execution eventually produced — record what was answered.
            outcome = ERR_DEADLINE
            error = ("abandoned: deadline passed before the result was "
                     "ready")
        elif job.error is not None:
            outcome, raw = _classify_error(job.error)
            error = str(raw).replace("\n", " ")[:500]
        else:
            outcome, error = "OK", ""
        latency_ms = (time.monotonic() - job.enqueued_t) * 1000.0
        retained = flight_recorder.record(
            self._session.conf, kind=job.kind, outcome=outcome,
            latency_ms=latency_ms, trace_id=job.trace_id,
            request_id=job.request_id, queue_wait_ms=job.queue_wait_ms,
            error=error, span=job.root_span, report=job.report)
        if not job.abandoned and job.error is None:
            metrics.observe("serve.latency_ms", latency_ms,
                            exemplar=job.trace_id if retained else None)

    # -- request accounting (handler threads) -------------------------------
    def request_started(self) -> None:
        with self._idle:
            self._open_requests += 1

    def request_finished(self) -> None:
        with self._idle:
            self._open_requests -= 1
            self._idle.notify_all()

    # -- lifecycle ---------------------------------------------------------
    def wait_idle(self, grace_s: float) -> bool:
        """Block until every admitted job finished AND every in-flight
        response is fully written, or ``grace_s`` passed.  Returns True
        when the pool drained clean."""
        deadline_at = time.monotonic() + max(0.0, grace_s)
        with self._idle:
            while self._queued_or_active > 0 or self._open_requests > 0:
                left = deadline_at - time.monotonic()
                if left <= 0:
                    return False
                self._idle.wait(left)
        return True

    def stop(self, timeout_s: float = 5.0) -> None:
        for _ in self._threads:
            self._queue.put(self._stop_sentinel)
        for t in self._threads:
            t.join(timeout=timeout_s)
        self._threads.clear()


# -- the connection handler ---------------------------------------------------
class _Responder:
    """The request→response engine shared by BOTH accept paths — the
    threaded per-connection handler and the async event loop's
    dispatchers: parse, verb-or-admit, stream the answer, classify
    errors.  Subclasses provide ``server`` (the inner server state:
    session / pool / plan_cache / proxy_client), ``connection`` (the
    socket) and ``wfile`` (a buffered binary writer); everything else —
    including the per-connection ``last_run_report`` contract — lives
    here, which is what keeps the two io modes bit-equal on the
    wire."""

    server: Any = None
    connection: Any = None
    wfile: Any = None

    def _init_responder(self) -> None:
        # The most recent run report of a query served on THIS connection
        # (queries execute on pool workers, so the session's thread-local
        # cannot answer the last_run_report verb anymore).
        self._last_report = None
        # The currently admitted job (None between requests / before
        # admission): the error path uses it to tell "a worker owns this
        # request's flight record" from "record it here".
        self._cur_job = None

    def _respond_one(self, line: bytes, conf) -> bool:
        from hyperspace_tpu.interop.query import (
            mint_trace_id,
            pop_trace_context,
        )
        from hyperspace_tpu.telemetry import flight_recorder, metrics

        t0 = time.monotonic()
        trace_id: Optional[str] = None
        request_id: Optional[str] = None
        kind = "unknown"
        is_verb = False
        self._cur_job = None  # the admitted job, for the error path
        try:
            spec = self._parse(line)
            # Adopt the client's trace context — or mint one for a
            # missing/malformed id (a bad trace id must never reject the
            # request).  Every response echoes the id, so the client can
            # quote it to slow_queries()/the trace verb afterwards.
            trace_id, request_id, adopted = pop_trace_context(spec)
            if adopted:
                metrics.inc("serve.trace.adopted")
            else:
                metrics.inc("serve.trace.minted")
            # The session-scoped tenant id rides every request as a spec
            # key; popped here so neither verbs nor the query decoders
            # ever see it.  Quota enforcement happens at admission.
            tenant = spec.pop("tenant", "")
            if tenant is None:
                tenant = ""
            if not isinstance(tenant, str):
                raise WireError(ERR_BADREQ, '"tenant" must be a string')
            is_verb = "verb" in spec
            if is_verb:
                # Observability verbs answer INLINE on the connection
                # thread: they read process state, never the executor, and
                # must keep working while the admission queue is slammed —
                # an operator debugging an overload needs `metrics` most
                # exactly then.
                table = _serve_verb(self.server.session, spec,
                                    self._last_report,
                                    pool=self.server.pool)
            else:
                kind = "sql" if "sql" in spec else "spec"
                table = self._execute_admitted(spec, conf, trace_id,
                                               request_id, tenant)
        except Exception as exc:  # -> coded wire error, connection closes
            if trace_id is None:
                trace_id, request_id = mint_trace_id(), mint_trace_id()
                metrics.inc("serve.trace.minted")
            code, raw = _classify_error(exc)
            msg = str(raw).replace("\n", " ")[:500]
            metrics.inc("serve.errors")
            metrics.inc(f"serve.err.{code.lower()}")
            if code == ERR_DEADLINE:
                metrics.inc("serve.deadline.expired")
            if not is_verb and self._cur_job is None:
                # Sheds and malformed requests never reach a worker, so
                # the handler is the only place that can record them.
                # Admitted jobs (incl. abandoned deadline expiries) are
                # recorded by their worker, with the span tree/report.
                flight_recorder.record(
                    conf, kind=kind, outcome=code,
                    latency_ms=(time.monotonic() - t0) * 1000.0,
                    trace_id=trace_id, request_id=request_id, error=msg)
            retry_ms = getattr(exc, "retry_after_ms", None)
            hint = f" retry-after-ms={int(retry_ms)}" \
                if retry_ms is not None else ""
            try:
                self.connection.settimeout(
                    float(conf.serving_send_timeout_s))
                self.wfile.write(
                    f"ERR {code} {msg}{hint} trace={trace_id}\n"
                    .encode("utf-8"))
            except OSError:
                pass
            return False
        # The send side gets its OWN timeout: REQUEST_TIMEOUT_S historically
        # guarded only the read, so a dead client that stopped READING
        # mid-Arrow-stream pinned its thread on a full send buffer forever.
        try:
            self.connection.settimeout(float(conf.serving_send_timeout_s))
            if netfaults.armed():
                # Wire-fault detour: materialize the whole frame so the
                # net.send seam can tear it at an exact byte boundary.
                # Gated on an armed net plan — the zero-fault hot path
                # never pays the extra copy.
                import io as _io

                buf = _io.BytesIO()
                buf.write(f"OK trace={trace_id}\n".encode("utf-8"))
                with pa.ipc.new_stream(buf, table.schema) as writer:
                    writer.write_table(table)
                netfaults.send_all(self.connection, buf.getvalue())
            else:
                self.wfile.write(f"OK trace={trace_id}\n".encode("utf-8"))
                with pa.ipc.new_stream(self.wfile, table.schema) as writer:
                    writer.write_table(table)
                self.wfile.flush()
            metrics.inc("serve.ok")
            return True
        except TimeoutError:
            metrics.inc("serve.send_timeouts")
            return False  # dead reader: free the thread, drop the socket
        except OSError:
            return False  # client hung up mid-response

    def _parse(self, line: bytes) -> Dict[str, Any]:
        if len(line) > MAX_REQUEST_BYTES or not line.endswith(b"\n"):
            raise WireError(
                ERR_BADREQ,
                f"request exceeds {MAX_REQUEST_BYTES} bytes or is not "
                f"newline-terminated")
        try:
            spec = json.loads(line.decode("utf-8"))
        except (ValueError, UnicodeDecodeError) as e:
            raise WireError(ERR_BADREQ, f"request is not JSON: {e}")
        if not isinstance(spec, dict):
            # A bare JSON string/array is valid JSON — and `"sql" in
            # spec` on a string would substring-match.
            raise WireError(ERR_BADREQ, "request must be a JSON object")
        return spec

    def _execute_admitted(self, spec: Dict[str, Any], conf,
                          trace_id: str, request_id: str,
                          tenant: str = "") -> pa.Table:
        from hyperspace_tpu.exceptions import DeadlineExceededError

        deadline_ms = spec.pop("deadline_ms", None)
        if deadline_ms is None:
            default_ms = float(conf.serving_default_deadline_ms or 0.0)
            deadline_ms = default_ms if default_ms > 0 else None
        elif not isinstance(deadline_ms, (int, float)) or \
                isinstance(deadline_ms, bool) or deadline_ms <= 0:
            raise WireError(ERR_BADREQ,
                            f'"deadline_ms" must be a positive number, '
                            f'got {deadline_ms!r}')
        deadline_at = None if deadline_ms is None \
            else time.monotonic() + float(deadline_ms) / 1000.0
        fn, kind = self._make_query_fn(spec)
        job = _Job(fn, kind, deadline_at, trace_id=trace_id,
                   request_id=request_id, tenant=tenant)
        self.server.pool.submit(job, conf)  # raises WireError(BUSY) = shed
        self._cur_job = job  # admitted: its worker owns the flight record
        if deadline_at is None:
            job.done.wait()
        else:
            left = max(0.0, deadline_at - time.monotonic())
            if not job.done.wait(left):
                # The deadline is a RESPONSE contract, enforced here even
                # when the worker is mid-phase: answer DEADLINE the moment
                # it passes — the deadline contextvar aborts the work at
                # its next phase boundary, and the abandoned flag discards
                # the orphan result (and skips the job entirely if it was
                # still queued).
                job.abandoned = True
                raise DeadlineExceededError(
                    "deadline exceeded before the result was ready (the "
                    "query aborts at its next phase boundary)")
        if job.error is not None:
            raise job.error
        if job.report is not None:
            self._last_report = job.report
        return job.result

    def _make_query_fn(self, spec: Dict[str, Any]):
        """Validate the request SHAPE on the connection thread (BADREQ
        without consuming a queue slot), return the execute closure the
        worker runs."""
        session = self.server.session
        plan_cache = self.server.plan_cache
        proxy = getattr(self.server, "proxy_client", None)
        if proxy is not None:
            # Proxy mode: this server is a FRONT DOOR for non-Python
            # clients — queries forward through the fleet client (load
            # routing, failover, retry-after backoff) while verbs keep
            # answering locally.  Shape validation is the backend's job;
            # its coded errors come back as-is (_classify_error keeps
            # the upstream code, so BUSY stays retryable end-to-end).
            forward = dict(spec)

            def run_proxy() -> pa.Table:
                return proxy.query(forward)

            return run_proxy, ("sql" if "sql" in spec else "spec")
        if "sql" in spec:
            # {"sql": "SELECT ...", "tables": {name: parquet_dir}} —
            # SQL text over the wire, the reference corpus's native
            # form (goldstandard/PlanStabilitySuite.scala:81-283).
            if not isinstance(spec["sql"], str):
                raise WireError(ERR_BADREQ, '"sql" must be a string')
            tables = spec.get("tables", {})
            if not isinstance(tables, dict) or not all(
                    isinstance(v, str) for v in tables.values()):
                raise WireError(
                    ERR_BADREQ,
                    '"tables" must map names to parquet directory paths '
                    'over the wire')

            def run() -> pa.Table:
                from hyperspace_tpu.sql import sql as run_sql

                ds = run_sql(session, spec["sql"], tables=tables)
                return ds.collect(plan_cache=plan_cache)

            return run, "sql"

        def run_spec() -> pa.Table:
            from hyperspace_tpu.interop.query import dataset_from_spec

            return dataset_from_spec(session, spec).collect(
                plan_cache=plan_cache)

        return run_spec, "spec"


class _Handler(_Responder, socketserver.StreamRequestHandler):
    """The THREADED accept path's per-connection shell: blocking reads
    with the idle timeout, one handler thread per connection."""

    timeout = REQUEST_TIMEOUT_S  # initial value; per-phase settimeout below

    def setup(self) -> None:
        super().setup()
        self._init_responder()

    def handle(self) -> None:
        # Pipelined: serve requests until EOF, idle timeout, or an error
        # response (errors close the connection so framing stays
        # unambiguous for simple clients).
        while self._serve_one():
            pass

    def _serve_one(self) -> bool:
        from hyperspace_tpu.telemetry import metrics

        conf = self.server.session.conf
        try:
            self.connection.settimeout(
                float(conf.serving_request_timeout_s))
            line = self.rfile.readline(MAX_REQUEST_BYTES + 1)
        except (TimeoutError, OSError):
            return False
        if not line:
            return False  # clean EOF between requests
        metrics.inc("serve.requests")
        # The request is in flight from here until its response is fully
        # written: drain()'s wait_idle blocks on this accounting, so a
        # SIGTERM mid-stream cannot exit the process between the worker
        # finishing a result and this thread flushing it (torn frame).
        pool = self.server.pool
        pool.request_started()
        try:
            return self._respond_one(line, conf)
        finally:
            pool.request_finished()


class _AsyncResponder(_Responder):
    """One async connection's responder: same engine, socket-backed
    writer, reused across the connection's pipelined requests (the
    ``last_run_report`` contract is per connection)."""

    def __init__(self, server, sock: socket.socket) -> None:
        self.server = server
        self.connection = sock
        self.wfile = sock.makefile("wb")
        self._init_responder()


class _AsyncConn:
    """Selector-side state of one async connection."""

    __slots__ = ("sock", "buf", "responder")

    def __init__(self, server, sock: socket.socket) -> None:
        self.sock = sock
        self.buf = b""
        self.responder = _AsyncResponder(server, sock)


def _reject_connection(server, request: socket.socket) -> None:
    """Answer ``ERR BUSY`` to a connection past the cap and close it —
    shared by both accept paths, always bounded (1 s send timeout)."""
    from hyperspace_tpu.interop.query import mint_trace_id
    from hyperspace_tpu.telemetry import flight_recorder, metrics

    metrics.inc("serve.shed")
    metrics.inc("serve.shed.connections")
    # No request line was read, so there is no client trace context to
    # adopt — record the shed under minted ids so the tail still shows
    # it happened.
    flight_recorder.record(
        server.session.conf, kind="unknown", outcome=ERR_BUSY,
        latency_ms=0.0, trace_id=mint_trace_id(),
        request_id=mint_trace_id(), error="connection capacity reached")
    hint = server.pool.retry_after_hint_ms()
    try:
        request.settimeout(1.0)
        request.sendall(
            f"ERR {ERR_BUSY} connection capacity reached; "
            f"retry later retry-after-ms={hint}\n".encode("utf-8"))
    except OSError:
        pass


class _AsyncIOLoop:
    """The selector accept path (``hyperspace.serving.ioMode=async``):
    ONE event-loop thread owns accept plus request reads for EVERY
    connection, so thousands of mostly-idle sockets cost one thread
    instead of one each.  Complete request lines hand off to a small
    dispatcher pool that runs the SAME :class:`_Responder` engine as the
    threaded path — admission, verbs, deadlines, and error taxonomy are
    shared code, which is what makes the two io modes bit-equal on the
    wire.

    Single-writer discipline, async flavor: while a response is in
    flight its socket is UNREGISTERED from the selector — the
    dispatcher is the connection's only writer, and the loop never
    reads ahead of an unfinished response, so pipelining stays ordered
    and frames cannot tear.  Finished connections return through the
    requeue + wakeup pipe (the loop thread owns all selector state).

    The event loop itself must never block: hslint's
    blocking-discipline rule covers ``_event_loop`` / ``_on_accept`` /
    ``_on_readable`` / ``_on_wakeup`` exactly like the threaded accept
    loop, so a store read or a sleep slipping in fails the lint, not
    production."""

    def __init__(self, outer: "QueryServer", server) -> None:
        import selectors

        self._outer = outer
        self._server = server
        self._sel = selectors.DefaultSelector()
        self._listener: socket.socket = server.socket
        self._ready: "queue.Queue" = queue.Queue()
        self._requeue: "queue.Queue" = queue.Queue()
        self._wake_r, self._wake_w = socket.socketpair()
        self._stop = threading.Event()
        self._loop_thread: Optional[threading.Thread] = None
        self._dispatchers: list = []
        self._conns: set = set()  # loop-thread-owned

    def start(self) -> None:
        self._listener.setblocking(False)
        self._wake_r.setblocking(False)
        self._sel.register(self._listener, _read_event(), "accept")
        self._sel.register(self._wake_r, _read_event(), "wakeup")
        self._loop_thread = threading.Thread(
            target=self._event_loop, name="hs-serve-io", daemon=True)
        self._loop_thread.start()
        # Concurrent responses are bounded by the dispatcher count: the
        # pool's workers plus headroom so inline verbs keep answering
        # while every worker slot is executing.
        n = self._server.pool.workers + 4
        for i in range(n):
            t = threading.Thread(target=self._dispatch,
                                 name=f"hs-serve-dispatch-{i}",
                                 daemon=True)
            t.start()
            self._dispatchers.append(t)

    # -- the event loop (block-free; see hslint blocking-discipline) --------
    def _event_loop(self) -> None:
        while not self._stop.is_set():
            try:
                events = self._sel.select(timeout=0.2)
            except OSError:
                continue
            for key, _mask in events:
                tag = key.data
                if tag == "accept":
                    self._on_accept()
                elif tag == "wakeup":
                    self._on_wakeup()
                else:
                    self._on_readable(tag)

    def _on_accept(self) -> None:
        try:
            sock, _addr = self._listener.accept()
        except OSError:
            return
        if not netfaults.on_accept(sock):
            return  # consumed by an armed net.accept fault (block-free)
        if not self._outer._acquire_conn():
            # Reject IN the loop, bounded send — same contract as the
            # threaded accept loop's early ERR BUSY.
            _reject_connection(self._server, sock)
            try:
                sock.close()
            except OSError:
                pass
            return
        sock.setblocking(False)
        conn = _AsyncConn(self._server, sock)
        self._conns.add(conn)
        self._sel.register(sock, _read_event(), conn)

    def _on_readable(self, conn: _AsyncConn) -> None:
        try:
            data = conn.sock.recv(65536)
        except (BlockingIOError, InterruptedError):
            return
        except OSError:
            self._drop(conn, registered=True)
            return
        if not data:
            self._drop(conn, registered=True)  # clean EOF
            return
        conn.buf += data
        if b"\n" in conn.buf or len(conn.buf) > MAX_REQUEST_BYTES:
            self._sel.unregister(conn.sock)
            self._hand_off(conn)

    def _on_wakeup(self) -> None:
        try:
            while self._wake_r.recv(4096):
                pass
        except (BlockingIOError, InterruptedError):
            pass
        except OSError:
            return
        while True:
            try:
                conn, keep = self._requeue.get_nowait()
            except queue.Empty:
                break
            if not keep or self._stop.is_set():
                self._drop(conn, registered=False)
            elif b"\n" in conn.buf:
                # The client pipelined ahead: the next request is already
                # buffered, so no readiness event will ever fire for it.
                self._hand_off(conn)
            else:
                try:
                    conn.sock.setblocking(False)
                    self._sel.register(conn.sock, _read_event(), conn)
                except (OSError, ValueError):
                    self._drop(conn, registered=False)

    def _hand_off(self, conn: _AsyncConn) -> None:
        line, sep, rest = conn.buf.partition(b"\n")
        conn.buf = rest
        self._ready.put_nowait((conn, line + sep))

    def _drop(self, conn: _AsyncConn, registered: bool) -> None:
        if conn not in self._conns:
            return
        self._conns.discard(conn)
        if registered:
            try:
                self._sel.unregister(conn.sock)
            except (KeyError, ValueError):
                pass
        try:
            conn.responder.wfile.close()
        except OSError:
            pass
        try:
            conn.sock.close()
        except OSError:
            pass
        self._outer._release_conn()

    # -- dispatchers (one response at a time per connection) -----------------
    def _dispatch(self) -> None:
        from hyperspace_tpu.telemetry import metrics

        while True:
            item = self._ready.get()
            if item is None:
                return
            conn, line = item
            pool = self._server.pool
            metrics.inc("serve.requests")
            pool.request_started()
            keep = False
            try:
                keep = conn.responder._respond_one(
                    line, self._server.session.conf)
            except Exception:  # noqa: BLE001 — a dispatcher must survive
                keep = False   # anything a response path can throw
            finally:
                pool.request_finished()
            self._requeue.put((conn, keep))
            try:
                self._wake_w.send(b"x")
            except OSError:
                pass

    # -- lifecycle -----------------------------------------------------------
    def stop_accepting(self) -> None:
        """Phase one of drain/stop: end the event loop (no new accepts,
        no new request reads).  In-flight dispatcher responses keep
        running — wait_idle covers them."""
        self._stop.set()
        try:
            self._wake_w.send(b"x")
        except OSError:
            pass
        if self._loop_thread is not None:
            self._loop_thread.join(timeout=5)
            self._loop_thread = None

    def close(self) -> None:
        """Phase two: stop dispatchers and close every connection."""
        self.stop_accepting()
        for _ in self._dispatchers:
            self._ready.put(None)
        for t in self._dispatchers:
            t.join(timeout=5)
        self._dispatchers.clear()
        for conn in list(self._conns):
            self._drop(conn, registered=True)
        try:
            self._sel.close()
        except OSError:
            pass
        for s in (self._wake_r, self._wake_w):
            try:
                s.close()
            except OSError:
                pass


def _read_event() -> int:
    import selectors

    return selectors.EVENT_READ


def _serve_verb(session, spec: Dict[str, Any],
                last_report=None, pool=None) -> pa.Table:
    """Non-query verbs of the wire protocol:

      {"verb": "metrics"}          -> (name, value) rows: counters/gauges
                                      flat, histograms flattened to
                                      name.count/name.sum/name.mean
      {"verb": "last_run_report"}  -> one row, column ``report_json`` —
                                      the most recent query report of
                                      THIS CONNECTION (query then ask on
                                      one connection; queries execute on
                                      pool workers, so the handler keeps
                                      the report per connection)
      {"verb": "workload"}         -> the captured advisor workload table
                                      (advisor/workload.py)
      {"verb": "perf_history",
       "index"?, "limit"?}         -> the persistent perf ledger
                                      (telemetry/perf_ledger.py): one row
                                      per recorded action run under the
                                      serving session's systemPath;
                                      optional filters — ``index`` keeps
                                      action records for that index,
                                      ``limit`` the most recent N
      {"verb": "build_report"}     -> one row, column ``report_json`` —
                                      the session's most recent action
                                      BuildReport (session-wide: builds
                                      are serialized by the log protocol)
      {"verb": "slow_queries"}     -> the flight recorder's retained ring
                                      (telemetry/flight_recorder.py):
                                      slow/error/deadline/shed requests
                                      plus sampled healthy ones, oldest
                                      first
      {"verb": "trace",
       "id": "<trace_id>"}         -> one row, column ``record_json`` —
                                      the full retained record (span
                                      tree, run report, outcome) of that
                                      trace id; the id every response
                                      echoes (``trace=``) and every
                                      client error carries
      {"verb": "doctor",
       "fleet"?: true}             -> the aggregated health report
                                      (telemetry/doctor.py): one row per
                                      check (columns check, status,
                                      summary, dataJson) plus the
                                      ``overall`` row — ok/warn/crit,
                                      worst check wins; ``fleet`` adds
                                      the cluster checks over the
                                      published heartbeats
      {"verb": "fleet_status"}     -> every published fleet heartbeat
                                      (telemetry/fleet.py): process
                                      identity, role, health grade,
                                      heartbeat age, freshness — the
                                      "which of my servers is sick"
                                      surface, answering inline so it
                                      works during overload
      {"verb": "alerts",
       "fleet"?: true}             -> current SLO alert states
                                      (telemetry/alerts.py): one row per
                                      objective — availability, latency,
                                      staleness, build-claim liveness —
                                      with state/severity/since and the
                                      incident-bundle key captured at
                                      firing; ``fleet`` merges every
                                      fresh heartbeat's active alerts
                                      with process attribution.
                                      Answers inline, so "am I paging"
                                      works during overload
      {"verb": "lifecycle"}        -> the lifecycle decision journal
                                      (lifecycle/journal.py): every
                                      maintenance-daemon decision —
                                      refresh mode chosen, advisor
                                      build/drop, backoff skip, or "did
                                      nothing, here's why" — oldest
                                      first (docs/19-lifecycle.md)
      {"verb": "tenants"}          -> per-tenant admission state: one
                                      row per tenant id seen (columns
                                      tenant, queued, shed) — ``queued``
                                      is the live queued-or-active
                                      count the quota
                                      (``hyperspace.serving.tenant
                                      .maxQueued``) grades, ``shed`` the
                                      tenant's lifetime quota sheds;
                                      answers inline, so a hot tenant's
                                      operator can see themselves
                                      shedding while it happens

    ``slow_queries`` and ``trace`` answer inline like ``metrics`` — an
    operator debugging an overloaded server needs exactly them while the
    admission queue is shedding.
    """
    verb = spec["verb"]
    if not isinstance(verb, str):
        raise ValueError('"verb" must be a string')
    if verb == "metrics":
        from hyperspace_tpu.telemetry import metrics as m

        names: list = []
        values: list = []

        def emit(name: str, value) -> None:
            if isinstance(value, (int, float)) and value is not None:
                names.append(name)
                values.append(float(value))

        for name, value in sorted(m.snapshot().items()):
            if isinstance(value, dict):  # histogram snapshot
                for part in ("count", "sum", "mean", "min", "max"):
                    if value.get(part) is not None:
                        emit(f"{name}.{part}", value[part])
            else:
                emit(name, value)
        return pa.table({"name": pa.array(names, type=pa.string()),
                         "value": pa.array(values, type=pa.float64())})
    if verb == "last_run_report":
        report = last_report if last_report is not None \
            else session.last_run_report_value
        payload = json.dumps(report.to_dict() if report is not None
                             else None)
        return pa.table({"report_json": pa.array([payload],
                                                 type=pa.string())})
    if verb == "workload":
        from hyperspace_tpu.advisor.workload import workload_table

        return workload_table(session.conf)
    if verb == "perf_history":
        from hyperspace_tpu.telemetry.perf_ledger import history_table

        index = spec.get("index")
        limit = spec.get("limit")
        if index is not None and not isinstance(index, str):
            raise ValueError('"index" must be a string')
        if limit is not None and (not isinstance(limit, int)
                                  or isinstance(limit, bool) or limit < 0):
            raise ValueError('"limit" must be a non-negative integer')
        return history_table(session.conf, index=index, limit=limit)
    if verb == "build_report":
        report = session.last_build_report_value
        payload = json.dumps(report.to_dict() if report is not None
                             else None)
        return pa.table({"report_json": pa.array([payload],
                                                 type=pa.string())})
    if verb == "slow_queries":
        from hyperspace_tpu.telemetry.flight_recorder import (
            slow_queries_table,
        )

        return slow_queries_table(session.conf)
    if verb == "trace":
        from hyperspace_tpu.telemetry import flight_recorder

        trace_id = spec.get("id")
        if not isinstance(trace_id, str) or not trace_id:
            raise ValueError(
                'the trace verb needs {"id": "<trace_id>"} — the id a '
                'response echoed as trace=... or an error carried')
        rec = flight_recorder.recorder().find(trace_id.lower())
        if rec is None:
            raise ValueError(
                f"no retained flight record for trace id {trace_id!r} "
                f"(healthy requests are sampled; slow/error/shed ones "
                f"are always kept while they fit the ring)")
        return pa.table({"record_json": pa.array(
            [json.dumps(rec, default=str)], type=pa.string())})
    if verb == "doctor":
        from hyperspace_tpu.telemetry.doctor import doctor

        fleet = spec.get("fleet", False)
        if not isinstance(fleet, bool):
            raise ValueError('"fleet" must be a boolean')
        return doctor(session, fleet=fleet).table()
    if verb == "fleet_status":
        from hyperspace_tpu.telemetry.fleet import fleet_status_table

        return fleet_status_table(session.conf)
    if verb == "alerts":
        from hyperspace_tpu.telemetry.alerts import alerts_table

        fleet = spec.get("fleet", False)
        if not isinstance(fleet, bool):
            raise ValueError('"fleet" must be a boolean')
        return alerts_table(session, fleet=fleet)
    if verb == "lifecycle":
        from hyperspace_tpu.lifecycle.journal import history_table

        return history_table(session.conf)
    if verb == "tenants":
        from hyperspace_tpu.telemetry import metrics as m

        queued = pool.tenant_snapshot() if pool is not None else {}
        shed: Dict[str, float] = {}
        prefix, suffix = "serve.tenant.", ".shed"
        for name, value in m.snapshot().items():
            if name.startswith(prefix) and name.endswith(suffix) \
                    and not isinstance(value, dict):
                shed[name[len(prefix):-len(suffix)]] = float(value)
        tenants = sorted(set(queued) | set(shed))
        return pa.table({
            "tenant": pa.array(tenants, type=pa.string()),
            "queued": pa.array([int(queued.get(t, 0)) for t in tenants],
                               type=pa.int64()),
            "shed": pa.array([int(shed.get(t, 0)) for t in tenants],
                             type=pa.int64()),
        })
    raise ValueError(f"Unknown verb {verb!r}; expected metrics, "
                     f"last_run_report, workload, perf_history, "
                     f"build_report, slow_queries, trace, doctor, "
                     f"fleet_status, alerts, lifecycle, or tenants")


def _is_loopback(host: str) -> bool:
    if host == "localhost":
        return True
    if host == "":
        return False  # "" binds INADDR_ANY — every interface, most exposed
    import ipaddress

    try:
        return ipaddress.ip_address(host).is_loopback
    except ValueError:
        return False  # a hostname we can't classify: treat as remote


class QueryServer:
    """Admission-controlled threaded TCP server bound to ``session``.
    ``port=0`` picks an ephemeral port (read it back from ``.address``).

    Sizing comes from the session conf at construction
    (``hyperspace.serving.workers`` / ``.queueDepth`` /
    ``.maxConnections`` — see docs/07-interop.md); timeouts, deadlines,
    and shed watermarks are read live per request, so ``conf.set`` on a
    running server takes effect immediately.

    ``handle_sigterm=True`` installs a SIGTERM handler (main thread only)
    that runs :meth:`drain` in the background: stop accepting, let
    in-flight requests finish within ``hyperspace.serving.drainGraceS``,
    then close — ``drained`` is set when the shutdown completes, so a
    serving script can simply ``server.drained.wait()``.

    ``hyperspace.serving.ioMode=async`` swaps the threaded accept path
    for the selector event loop (:class:`_AsyncIOLoop`) — same wire
    behavior, one io thread for every connection.

    ``proxy_endpoints=[...]`` turns this server into a thin FRONT DOOR:
    queries forward through a :class:`FleetQueryClient` over those
    backends (least-loaded routing, failover, retry-after backoff), so
    a non-Python client pointed at the proxy gets fleet fault tolerance
    without reimplementing it; observability verbs still answer from
    THIS process."""

    def __init__(self, session, host: str = "127.0.0.1",
                 port: int = 0, allow_remote: bool = False,
                 handle_sigterm: bool = False,
                 proxy_endpoints: Optional[list] = None) -> None:
        # The server is UNAUTHENTICATED and reads any path the process can
        # access; binding a non-loopback interface exposes that to the
        # network.  Require the caller to say so explicitly.
        if not _is_loopback(host) and not allow_remote:
            raise ValueError(
                f"QueryServer binds {host!r}, a non-loopback interface, but "
                f"the protocol has no authentication: any peer that can "
                f"reach the port can read any file this process can.  Pass "
                f"allow_remote=True only behind a trusted network boundary.")

        outer = self

        class _Server(socketserver.ThreadingTCPServer):
            allow_reuse_address = True
            daemon_threads = True

            def process_request(self, request, client_address):
                if not netfaults.on_accept(request):
                    return  # consumed by an armed net.accept fault
                if not outer._acquire_conn():
                    # Reject IN the accept loop — no handler thread is
                    # spawned, so a connection storm cannot grow the
                    # thread count past maxConnections + workers.
                    _reject_connection(self, request)
                    self.shutdown_request(request)
                    return
                super().process_request(request, client_address)

            def process_request_thread(self, request, client_address):
                try:
                    super().process_request_thread(request, client_address)
                finally:
                    outer._release_conn()

        self._server = _Server((host, port), _Handler)
        self._server.session = session
        conf = session.conf
        # Telemetry conf set between session construction and server
        # start must win before the FIRST request's serve.request span
        # opens (collect re-applies per query, but that is too late for
        # the worker's root span).
        from hyperspace_tpu.telemetry import trace as _trace

        _trace.configure_from_conf(conf)
        self._server.pool = _WorkerPool(
            session,
            workers=int(getattr(conf, "serving_workers", 4)),
            queue_depth=int(getattr(conf, "serving_queue_depth", 16)))
        if getattr(conf, "serving_plan_cache_enabled", True):
            from hyperspace_tpu.execution.plan_cache import PlanCache

            self._server.plan_cache = PlanCache(
                budget_bytes=int(getattr(conf, "serving_plan_cache_bytes",
                                         64 << 20)),
                ttl_s=float(conf.cache_expiry_seconds))
        else:
            self._server.plan_cache = None
        self._server.proxy_client = (
            FleetQueryClient(proxy_endpoints, conf=conf)
            if proxy_endpoints else None)
        self._io_mode = str(getattr(conf, "serving_io_mode",
                                    "threaded")).strip().lower()
        if self._io_mode not in ("threaded", "async"):
            raise ValueError(
                f"hyperspace.serving.ioMode must be 'threaded' or "
                f"'async', got {self._io_mode!r}")
        self._async: Optional[_AsyncIOLoop] = None
        self._max_connections = int(getattr(conf,
                                            "serving_max_connections", 64))
        self._conn_lock = threading.Lock()
        self._conn_count = 0
        self._thread: Optional[threading.Thread] = None
        self._draining = False
        self.drained = threading.Event()
        if handle_sigterm:
            self._install_sigterm()

    # -- connection accounting ---------------------------------------------
    def _acquire_conn(self) -> bool:
        if self._draining:
            return False
        with self._conn_lock:
            if self._max_connections > 0 and \
                    self._conn_count >= self._max_connections:
                return False
            self._conn_count += 1
        from hyperspace_tpu.telemetry import metrics

        metrics.set_gauge("serve.connections", self._conn_count)
        return True

    def _release_conn(self) -> None:
        with self._conn_lock:
            self._conn_count = max(0, self._conn_count - 1)
        from hyperspace_tpu.telemetry import metrics

        metrics.set_gauge("serve.connections", self._conn_count)

    # -- surface -------------------------------------------------------------
    @property
    def session(self):
        return self._server.session

    @property
    def pool(self) -> _WorkerPool:
        return self._server.pool

    @property
    def plan_cache(self):
        return self._server.plan_cache

    @property
    def address(self) -> Tuple[str, int]:
        return self._server.server_address

    def start(self) -> "QueryServer":
        # A serving process publishes role "server" in its fleet
        # heartbeat (telemetry/fleet.py; conf-gated — maybe_start is a
        # no-op with fleet telemetry off, and never raises).  The
        # heartbeat carries this server's address so the front door can
        # match fleet rows to endpoints, and a fresh start clears any
        # draining flag a previous in-process server left behind.
        from hyperspace_tpu.telemetry import alerts, fleet

        fleet.set_process_role("server")
        host, port = self.address[0], self.address[1]
        fleet.set_serving_address(f"{host}:{port}")
        fleet.set_serving_draining(False)
        fleet.maybe_start(self.session)
        # The SLO alert engine watches this server's counters; same
        # conf-gated never-raises start (hyperspace.alerts.enabled).
        alerts.maybe_start(self.session)
        self._server.pool.start()
        if self._io_mode == "async":
            self._async = _AsyncIOLoop(self, self._server)
            self._async.start()
        else:
            self._thread = threading.Thread(
                target=self._server.serve_forever,
                name="hs-query-server", daemon=True)
            self._thread.start()
        return self

    def drain(self, grace_s: Optional[float] = None) -> bool:
        """Graceful shutdown: stop accepting new connections AND new
        requests (both shed ``ERR BUSY``), let in-flight requests finish
        within ``grace_s`` (default conf
        ``hyperspace.serving.drainGraceS``), then stop the workers and
        close the listener.  Returns True when everything in flight
        completed inside the grace window.  Idempotent."""
        from hyperspace_tpu.telemetry import metrics

        if self.drained.is_set():
            return True
        if grace_s is None:
            grace_s = float(getattr(self.session.conf,
                                    "serving_drain_grace_s", 10.0))
        self._draining = True
        self._server.pool.draining = True
        metrics.inc("serve.drains")
        # Park the maintenance daemon too: a refresh racing this drain
        # would keep the process alive past its grace window
        # (lifecycle/daemon.py; the latch is process-global).
        from hyperspace_tpu.lifecycle import daemon as _lifecycle_daemon

        _lifecycle_daemon.notify_drain()
        # Flag the fleet heartbeat as draining and publish immediately:
        # the front door skips draining rows, so new requests stop
        # routing here DURING the grace window instead of shedding BUSY
        # at the door (publish_once is fault-quiet / conf-gated).
        from hyperspace_tpu.telemetry import fleet as _fleet

        _fleet.set_serving_draining(True)
        _fleet.publish_once(self.session.conf)
        if self._async is not None:
            self._async.stop_accepting()
        elif self._thread is not None:
            self._server.shutdown()  # stop the accept loop
        clean = self._server.pool.wait_idle(grace_s)
        # Persist the flight recorder's ring (+ metrics snapshot +
        # perf-ledger tail) as a diagnostics bundle AFTER in-flight
        # requests finished — so a SIGTERM'd server leaves "what
        # happened" readable after restart.  dump_diagnostics never
        # raises and runs fault-quiet.
        from hyperspace_tpu.telemetry import flight_recorder

        flight_recorder.dump_diagnostics(self.session.conf)
        # Deregister the fleet heartbeat: a drained server is a PLANNED
        # exit, not a dead process — without this the fleet doctor would
        # page crit on every rolling restart.  The diagnostics bundle
        # above keeps the tail readable; SIGKILL skips this path, which
        # is exactly how a genuinely dead process IS flagged.
        _fleet.publisher_for(self.session).stop()
        self._server.pool.stop()
        if self._async is not None:
            self._async.close()
        if self._server.proxy_client is not None:
            self._server.proxy_client.close()
        self._server.server_close()
        if self._thread is not None:
            self._thread.join(timeout=5)
        self.drained.set()
        return clean

    def _install_sigterm(self) -> None:
        import signal

        def _on_term(signum, frame) -> None:
            threading.Thread(target=self.drain, name="hs-serve-drain",
                             daemon=True).start()

        try:
            signal.signal(signal.SIGTERM, _on_term)
        except ValueError:
            raise ValueError(
                "handle_sigterm=True requires constructing the "
                "QueryServer on the main thread (signal handlers are "
                "main-thread-only); call drain() from your own handler "
                "instead")

    def stop(self) -> None:
        # shutdown() blocks on serve_forever's exit handshake — calling it
        # on a never-started server would hang forever, so only do the
        # handshake when start() actually ran; server_close() alone
        # releases the socket either way.
        if self.drained.is_set():
            return
        if self._thread is not None:
            self._server.shutdown()
        self._server.pool.stop()
        if self._async is not None:
            self._async.close()
        if self._server.proxy_client is not None:
            self._server.proxy_client.close()
        self._server.server_close()
        if self._thread is not None:
            self._thread.join(timeout=5)

    def __enter__(self) -> "QueryServer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()


class MetricsScrapeServer:
    """Long-lived Prometheus scrape endpoint: ``GET /metrics`` serves the
    process metrics registry's text exposition
    (``telemetry/metrics.render_prometheus`` — the ``build.phase.*``,
    ``exec.*``, ``io.*``, ``serve.*`` catalog of docs/16-observability.md).

    This is the pull-based counterpart of the ``metrics`` verb: the verb
    answers an Arrow client once; this endpoint stays up for a scraper to
    poll on its own schedule — the ops surface ROADMAP item 2's serving
    layer reports through.  Same security posture as :class:`QueryServer`:
    loopback by default, ``allow_remote=True`` required to expose it
    (metrics leak workload shape, file counts, index names via series
    values).

    ``fleet=True`` (requires ``session``) serves the FLEET-merged
    exposition instead (telemetry/fleet.py): every fresh published
    heartbeat's series plus this process's live registry, each labeled
    ``process="<id>"`` — one scrape target answers for the whole fleet,
    and the label answers "which server is slow".

    >>> with MetricsScrapeServer(port=9109) as ms:
    ...     ...  # curl http://127.0.0.1:9109/metrics
    """

    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 allow_remote: bool = False, session=None,
                 fleet: bool = False) -> None:
        if not _is_loopback(host) and not allow_remote:
            raise ValueError(
                f"MetricsScrapeServer binds {host!r}, a non-loopback "
                f"interface, without authentication.  Pass "
                f"allow_remote=True only behind a trusted boundary.")
        if fleet and session is None:
            raise ValueError(
                "MetricsScrapeServer(fleet=True) needs session=... — the "
                "merged exposition reads the fleet heartbeats under that "
                "session's systemPath")
        scrape_conf = session.conf if session is not None else None
        import http.server

        class _MetricsHandler(http.server.BaseHTTPRequestHandler):
            def do_GET(self) -> None:  # noqa: N802 — stdlib contract
                if self.path.split("?")[0] not in ("/metrics", "/"):
                    self.send_error(404)
                    return
                from hyperspace_tpu.telemetry import metrics as m

                if fleet:
                    from hyperspace_tpu.telemetry.fleet import (
                        render_fleet_prometheus,
                    )

                    body = render_fleet_prometheus(
                        scrape_conf).encode("utf-8")
                else:
                    body = m.registry().render_prometheus() \
                        .encode("utf-8")
                self.send_response(200)
                self.send_header("Content-Type",
                                 "text/plain; version=0.0.4; "
                                 "charset=utf-8")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *args: object) -> None:
                pass  # a scrape per second must not spam stderr

        class _Server(socketserver.ThreadingTCPServer):
            allow_reuse_address = True
            daemon_threads = True

        self._server = _Server((host, port), _MetricsHandler)
        self._thread: Optional[threading.Thread] = None

    @property
    def address(self) -> Tuple[str, int]:
        return self._server.server_address

    def start(self) -> "MetricsScrapeServer":
        self._thread = threading.Thread(
            target=self._server.serve_forever,
            name="hs-metrics-server", daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        if self._thread is not None:
            self._server.shutdown()
        self._server.server_close()
        if self._thread is not None:
            self._thread.join(timeout=5)

    def __enter__(self) -> "MetricsScrapeServer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()


def request_query(address: Tuple[str, int],
                  spec: Dict[str, Any]) -> pa.Table:
    """Reference client (tests / Python callers): send ``spec``, return the
    result table.  Non-Python clients reimplement these ~10 lines with
    their socket + Arrow APIs."""
    with QueryClient(address) as client:
        return client.query(spec)


class QueryClient:
    """Persistent pipelined connection: successful ``query()`` calls ride
    one socket (the server answers each in order).  After an error
    response, a transport failure, or the server's idle timeout
    (``hyperspace.serving.requestTimeoutS`` between requests) the server
    closes the connection — the client marks itself broken and subsequent
    calls raise ``ConnectionError`` asking for a fresh client, rather
    than failing with a confusing empty-status error on the dead socket.

    Wire errors raise :class:`QueryFailedError` (a ``RuntimeError``)
    carrying ``.code`` and ``.retryable`` — ``BUSY``/``DEADLINE`` mean
    "back off and retry on a new connection", the overload contract of
    docs/07-interop.md.

    Every request carries a client-minted TRACE CONTEXT (``trace_id`` /
    ``request_id`` spec keys, 16 hex chars each) that the server adopts
    and echoes on the status line — so a failure is correlatable from
    either side: ``.last_trace_id`` after a call (and
    ``QueryFailedError.trace_id`` on errors) is the handle
    ``slow_queries()`` / the ``trace`` verb answer for.

    ``tenant`` stamps every spec sent on this connection with a tenant
    id (the per-tenant admission key of
    ``hyperspace.serving.tenant.maxQueued``); an explicit ``"tenant"``
    key in a spec wins."""

    def __init__(self, address: Tuple[str, int],
                 tenant: Optional[str] = None,
                 timeout_s: Optional[float] = None) -> None:
        self._sock = netfaults.connect(address, timeout=timeout_s)
        self._f = self._sock.makefile("rb")
        self._broken = False
        self.tenant = tenant
        #: trace id of the most recent query() — server-echoed when the
        #: server speaks the trace protocol, else the client-minted one.
        self.last_trace_id: Optional[str] = None

    def is_stale(self) -> bool:
        """True when the pooled socket is no longer usable: the server
        hung up (half-open TCP after a bounce — a nonblocking peek sees
        EOF or an error), or bytes are pending between requests (a
        protocol violation on a pipelined connection — e.g. a hedged
        loser's late response; reading a fresh request's answer from it
        would cross-wire responses)."""
        if self._broken:
            return True
        try:
            self._sock.setblocking(False)
            try:
                chunk = self._sock.recv(1, socket.MSG_PEEK)
            finally:
                self._sock.setblocking(True)
        except (BlockingIOError, InterruptedError):
            return False  # no pending data: the healthy idle state
        except OSError:
            return True  # reset/refused already latched on the socket
        # EOF (b"") or unexpected pending bytes: either way, not safe.
        return True

    def query(self, spec: Dict[str, Any],
              deadline_ms: Optional[float] = None,
              timeout_s: Optional[float] = None) -> pa.Table:
        from hyperspace_tpu.interop.query import mint_trace_id

        if self._broken:
            raise ConnectionError(
                "connection closed by an earlier error or timeout; open a "
                "new QueryClient")
        if deadline_ms is not None:
            spec = {**spec, "deadline_ms": deadline_ms}
        if isinstance(spec, dict):
            if self.tenant is not None and "tenant" not in spec:
                spec = {**spec, "tenant": self.tenant}
            if "trace_id" not in spec:
                spec = {**spec, "trace_id": mint_trace_id()}
            if "request_id" not in spec:
                spec = {**spec, "request_id": mint_trace_id()}
            self.last_trace_id = spec["trace_id"]
        else:
            # A malformed (non-object) spec still goes to the server —
            # whose BADREQ answer, not a client-side crash, is the
            # contract under test for such requests.
            self.last_trace_id = None
        try:
            if timeout_s is not None:
                # The whole exchange — send, status line, Arrow stream —
                # rides one socket timeout: a SIGSTOPped or partitioned
                # server surfaces as ConnectionError within the budget
                # instead of pinning the caller forever.
                self._sock.settimeout(timeout_s)
            netfaults.send_all(
                self._sock, json.dumps(spec).encode("utf-8") + b"\n")
            netfaults.before_recv()
            status = self._f.readline().decode("utf-8").rstrip("\n")
        except OSError as exc:
            self._broken = True
            raise ConnectionError(f"connection lost: {exc}") from exc
        if not status.startswith("OK"):
            # ERR (server closes) or EOF (idle timeout / server gone).
            self._broken = True
            if not status:
                raise ConnectionError(
                    "server closed the connection (idle timeout or "
                    "shutdown); open a new QueryClient")
            err = parse_wire_error(status)
            if err.trace_id is None:
                # Pre-trace server: the minted id still names the
                # request on THIS side of the wire.
                err.trace_id = self.last_trace_id
            else:
                self.last_trace_id = err.trace_id
            raise err
        _, echoed = _split_trace_echo(status[2:].strip())
        if echoed is not None:
            self.last_trace_id = echoed
        try:
            with pa.ipc.open_stream(self._f) as reader:
                return reader.read_all()
        except OSError as exc:
            self._broken = True
            raise ConnectionError(f"connection lost: {exc}") from exc
        except pa.ArrowInvalid as exc:
            # A truncated/garbled IPC stream after a clean OK line: the
            # connection died mid-frame (torn frame, reset, server
            # crash).  That is a TRANSPORT fault, not a query failure —
            # surface it retryable so the front door fails over instead
            # of raising a decoder error at the caller.
            self._broken = True
            raise ConnectionError(
                f"response stream torn mid-frame: {exc}") from exc

    def close(self) -> None:
        self._f.close()
        self._sock.close()

    def __enter__(self) -> "QueryClient":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def _as_address(endpoint) -> Tuple[str, int]:
    """``"host:port"`` or ``(host, port)`` → ``(host, port)``."""
    if isinstance(endpoint, str):
        host, _, port = endpoint.rpartition(":")
        if not host or not port.isdigit():
            raise ValueError(
                f"endpoint {endpoint!r} is not 'host:port'")
        return host, int(port)
    host, port = endpoint
    return str(host), int(port)


class _Endpoint:
    """One server behind the front door: its address, a small pool of
    idle pipelined connections, the router's view of it (in-flight
    count, fleet-reported load, draining flag, penalty clock), and its
    circuit-breaker state (closed → open on consecutive failures →
    half-open probe after the cooldown)."""

    __slots__ = ("address", "label", "idle", "inflight", "penalized_until",
                 "load", "draining", "fresh", "lock",
                 "breaker_state", "breaker_fails", "breaker_until")

    MAX_IDLE = 4  # idle pipelined connections kept per endpoint

    def __init__(self, endpoint) -> None:
        self.address = _as_address(endpoint)
        self.label = f"{self.address[0]}:{self.address[1]}"
        self.idle: List[QueryClient] = []
        self.inflight = 0
        self.penalized_until = 0.0   # monotonic; routing skips until then
        self.load: Optional[float] = None  # fleet-reported queue+inflight
        self.draining = False
        self.fresh = True  # no fleet row ⇒ assume routable (fleet is opt-in)
        self.lock = threading.Lock()
        self.breaker_state = "closed"   # closed | open | half-open
        self.breaker_fails = 0          # consecutive failures while closed
        self.breaker_until = 0.0        # monotonic; open until then

    def acquire(self, tenant: Optional[str],
                timeout_s: Optional[float] = None) -> QueryClient:
        """Pop a VALIDATED idle connection or dial a new one.  Pooled
        sockets are peeked on checkout: a restarted server leaves
        half-open TCP behind, and handing that to a caller turns a
        routine bounce into a spurious reset charged to retry
        accounting — evict it silently instead
        (``client.pool.evicted``).  The connect happens OUTSIDE the
        lock (it blocks); in-flight is rolled back when the dial fails
        so a dead endpoint doesn't look busy forever."""
        from hyperspace_tpu.telemetry import metrics

        with self.lock:
            self.inflight += 1
        while True:
            with self.lock:
                client = self.idle.pop() if self.idle else None
            if client is None:
                break
            if client.is_stale():
                metrics.inc("client.pool.evicted")
                try:
                    client.close()
                except OSError:
                    pass
                continue
            client.tenant = tenant
            return client
        try:
            return QueryClient(self.address, tenant=tenant,
                               timeout_s=timeout_s)
        except OSError:
            with self.lock:
                self.inflight -= 1
            raise

    # -- circuit breaker -----------------------------------------------------
    def breaker_blocked(self, now: float) -> bool:
        """True when routing should avoid this endpoint: breaker open
        inside its cooldown, or a half-open probe already in flight."""
        with self.lock:
            if self.breaker_state == "open":
                return now < self.breaker_until
            return self.breaker_state == "half-open"

    def breaker_on_pick(self, now: float) -> bool:
        """Transition open → half-open when the cooldown has expired and
        this endpoint was actually PICKED (the probe request).  Returns
        True on the transition so the caller can count it."""
        with self.lock:
            if self.breaker_state == "open" and now >= self.breaker_until:
                self.breaker_state = "half-open"
                return True
        return False

    def breaker_failure(self, threshold: int, cooldown_s: float) -> bool:
        """Record a retryable/transport failure.  Returns True when this
        failure OPENED the breaker (threshold reached, or the half-open
        probe failed)."""
        now = time.monotonic()
        with self.lock:
            if self.breaker_state == "half-open":
                self.breaker_state = "open"
                self.breaker_until = now + cooldown_s
                return True
            self.breaker_fails += 1
            if self.breaker_state == "closed" \
                    and self.breaker_fails >= max(1, threshold):
                self.breaker_state = "open"
                self.breaker_until = now + cooldown_s
                return True
        return False

    def breaker_success(self) -> bool:
        """Record a served request.  Returns True when this success
        CLOSED a non-closed breaker (the half-open probe came back)."""
        with self.lock:
            was = self.breaker_state
            self.breaker_state = "closed"
            self.breaker_fails = 0
            return was != "closed"

    def release(self, client: QueryClient) -> None:
        with self.lock:
            self.inflight -= 1
            if len(self.idle) < self.MAX_IDLE:
                self.idle.append(client)
                return
        client.close()

    def discard(self, client: QueryClient) -> None:
        with self.lock:
            self.inflight -= 1
        try:
            client.close()
        except OSError:
            pass

    def close_idle(self) -> None:
        with self.lock:
            idle, self.idle = self.idle, []
        for client in idle:
            try:
                client.close()
            except OSError:
                pass


class FleetQueryClient:
    """Fault-tolerant front door over N :class:`QueryServer` endpoints.

    Routing is LEAST-LOADED: when fleet telemetry is on
    (``hyperspace.telemetry.fleet.enabled``), each server's heartbeat
    carries its address plus ``serve.inflight``/``serve.queue_depth``
    gauges and a ``draining`` flag; the router matches rows to endpoints
    by address, skips draining/stale rows, and sends each request to the
    least-loaded survivor (in-flight count breaks ties, round-robin
    breaks the rest).  Without fleet rows every endpoint is assumed
    routable and local in-flight counts carry the policy.

    Failure policy (the docs/07-interop.md retry contract):

      - RETRYABLE failures — ``BUSY``/``DEADLINE`` wire errors, plus
        transport faults (connection refused / reset / EOF) — retry on a
        DIFFERENT endpoint when one is available, with bounded jittered
        exponential backoff; a ``retry-after-ms`` hint from the server
        overrides the backoff step AND penalizes that endpoint for the
        hinted window so the next pick avoids it.
      - PERMANENT failures — ``BADREQ``/``FAILED`` — raise immediately;
        re-running a malformed or failing request elsewhere just fails
        N times.

    Retries increment ``client.retry`` (+ ``client.retry.<kind>``);
    a retry that lands on a different endpoint than the failed attempt
    increments ``client.failover``.  ``tenant`` stamps every spec for
    per-tenant admission on the servers.

    DEADLINE BUDGET: ``deadline_ms`` is ONE overall per-call budget —
    connect timeouts, socket read timeouts, backoff sleeps, the hedge
    delay, and the server-side deadline all spend from it, so the total
    elapsed across every failover attempt respects the caller's bound
    (per-attempt spending could overshoot it N-fold).

    CIRCUIT BREAKERS (``hyperspace.client.breaker.*``, default off):
    ``failures`` consecutive retryable/transport errors open an
    endpoint's breaker — routing avoids it for ``cooldownMs``, then ONE
    half-open probe request decides (success closes it, failure
    re-opens).  Transitions land on ``client.breaker.open`` /
    ``.half_open`` / ``.close`` counters and the
    ``client.breaker.open_now`` gauge the doctor's ``client`` check
    grades.

    HEDGED REQUESTS (``hyperspace.client.hedge.enabled``, default off):
    when the first attempt is slower than the hedge delay
    (``hedge.delayMs``, or 2× the client's latency EWMA when 0), a
    second attempt fires on a different survivor; the first response
    wins and the loser's late response is discarded by request_id
    (each attempt reads its own pipelined connection, so a late frame
    can never cross-wire onto a winner).  ``client.hedge.sent`` /
    ``client.hedge.wins`` count them.  Queries through this front door
    are reads — verbs and specs alike — which is what makes firing the
    same request twice safe.

    >>> with FleetQueryClient(["127.0.0.1:9001", "127.0.0.1:9002"],
    ...                       conf=session.conf) as fleet:
    ...     fleet.query({"index": "idx", "point": {"id": 7}})
    """

    def __init__(self, endpoints: Sequence[Union[str, Tuple[str, int]]],
                 conf=None, tenant: Optional[str] = None,
                 max_attempts: Optional[int] = None,
                 backoff_cap_ms: float = 2000.0,
                 status_refresh_s: float = 1.0,
                 hedge_enabled: Optional[bool] = None,
                 hedge_delay_ms: Optional[float] = None,
                 breaker_enabled: Optional[bool] = None,
                 breaker_failures: Optional[int] = None,
                 breaker_cooldown_ms: Optional[float] = None) -> None:
        if not endpoints:
            raise ValueError("FleetQueryClient needs at least one endpoint")
        self._endpoints = [_Endpoint(e) for e in endpoints]
        self._conf = conf
        self._tenant = tenant
        self._max_attempts = int(max_attempts if max_attempts is not None
                                 else max(3, len(self._endpoints)))
        self._backoff_cap_ms = float(backoff_cap_ms)
        self._status_refresh_s = float(status_refresh_s)
        self._status_stamp = 0.0  # monotonic; 0 forces a first refresh
        self._rr = 0
        self._lock = threading.Lock()  # guards _rr/_status_stamp/_lat_ewma
        # ONLY — never held across connect/send/sleep (lint:
        # lock-held-blocking)

        def _opt(value, key, default):
            return value if value is not None \
                else getattr(conf, key, default) if conf is not None \
                else default

        self._hedge_enabled = bool(
            _opt(hedge_enabled, "client_hedge_enabled", False))
        self._hedge_delay_ms = float(
            _opt(hedge_delay_ms, "client_hedge_delay_ms", 0.0))
        self._breaker_enabled = bool(
            _opt(breaker_enabled, "client_breaker_enabled", False))
        self._breaker_failures = int(
            _opt(breaker_failures, "client_breaker_failures", 5))
        self._breaker_cooldown_ms = float(
            _opt(breaker_cooldown_ms, "client_breaker_cooldown_ms", 2000.0))
        self._lat_ewma_ms = 0.0  # successful-request latency EWMA
        #: trace id of the most recent query() — same contract as
        #: :class:`QueryClient`.
        self.last_trace_id: Optional[str] = None

    # -- routing --------------------------------------------------------------
    def _refresh_status(self) -> None:
        """Fold fresh fleet heartbeats into the endpoint table (by the
        ``address`` snapshot field).  Cheap-throttled; fault-quiet —
        routing falls back to local in-flight counts on any failure."""
        if self._conf is None:
            return
        now = time.monotonic()
        with self._lock:
            if now - self._status_stamp < self._status_refresh_s:
                return
            self._status_stamp = now
        try:
            from hyperspace_tpu.telemetry import fleet

            rows = {}
            for snap in fleet.fresh_snapshots(self._conf):
                addr = str(snap.get("address", "") or "")
                if addr:
                    rows[addr] = snap
        except Exception:  # noqa: BLE001 — telemetry must not break routing
            return
        for ep in self._endpoints:
            snap = rows.get(ep.label)
            if snap is None:
                # No fresh row: leave it routable on local signals only
                # (fleet telemetry may simply be off on that server).
                ep.load = None
                ep.draining = False
                ep.fresh = True
                continue
            gauges = snap.get("metrics", {}).get("gauges", {})
            ep.load = (float(gauges.get("serve.inflight", 0.0)) +
                       float(gauges.get("serve.queue_depth", 0.0)))
            ep.draining = bool(snap.get("draining", False))
            ep.fresh = True

    def _pick(self, tried: set,
              exclude: Optional[set] = None) -> _Endpoint:
        """Least-loaded routable endpoint not yet tried this request;
        progressively relax (allow breaker-open/penalized, then tried)
        rather than fail a pick while any endpoint exists.  ``exclude``
        labels (the hedge's other attempt) are avoided at every tier
        but the last-resort one."""
        from hyperspace_tpu.telemetry import metrics

        self._refresh_status()
        now = time.monotonic()
        exclude = exclude or set()

        def _tier(skip_tried: bool = True, skip_draining: bool = True,
                  skip_penalized: bool = False,
                  skip_broken: bool = False) -> List[_Endpoint]:
            return [ep for ep in self._endpoints
                    if ep.label not in exclude
                    and (not skip_tried or ep.label not in tried)
                    and (not skip_draining or not ep.draining)
                    and (not skip_penalized or now >= ep.penalized_until)
                    and (not skip_broken
                         or not ep.breaker_blocked(now))]

        pool = (_tier(skip_penalized=True,
                      skip_broken=self._breaker_enabled)
                or _tier()
                or _tier(skip_draining=False)
                or [ep for ep in self._endpoints if ep.label not in exclude]
                or self._endpoints)

        def _load(ep: _Endpoint) -> float:
            base = ep.load if ep.load is not None else 0.0
            return base + ep.inflight

        low = min(_load(ep) for ep in pool)
        ties = [ep for ep in pool if _load(ep) <= low]
        with self._lock:
            self._rr += 1
            ep = ties[self._rr % len(ties)]
        if self._breaker_enabled and ep.breaker_on_pick(now):
            metrics.inc("client.breaker.half_open")
            self._breaker_gauge()
        return ep

    def _breaker_gauge(self) -> None:
        from hyperspace_tpu.telemetry import metrics

        metrics.set_gauge(
            "client.breaker.open_now",
            sum(1 for ep in self._endpoints
                if ep.breaker_state != "closed"))

    # -- request path ---------------------------------------------------------
    def query(self, spec: Dict[str, Any],
              deadline_ms: Optional[float] = None) -> pa.Table:
        deadline_at = (time.monotonic() + float(deadline_ms) / 1000.0
                       if deadline_ms is not None else None)
        if self._hedge_enabled and isinstance(spec, dict):
            return self._query_hedged(spec, deadline_ms, deadline_at)
        return self._query_attempts(spec, deadline_ms, deadline_at)

    @staticmethod
    def _remaining_ms(deadline_at: Optional[float]) -> Optional[float]:
        if deadline_at is None:
            return None
        return (deadline_at - time.monotonic()) * 1000.0

    def _observe_latency(self, elapsed_ms: float) -> None:
        with self._lock:
            self._lat_ewma_ms = elapsed_ms if self._lat_ewma_ms <= 0.0 \
                else 0.8 * self._lat_ewma_ms + 0.2 * elapsed_ms

    def _query_attempts(self, spec: Dict[str, Any],
                        deadline_ms: Optional[float],
                        deadline_at: Optional[float],
                        exclude: Optional[set] = None,
                        note: Optional[Dict[str, Any]] = None,
                        max_attempts: Optional[int] = None) -> pa.Table:
        """The retry/failover loop, spending from ONE deadline budget:
        every attempt's socket timeout, server-side deadline, and
        backoff sleep is bounded by what remains of ``deadline_ms`` —
        the budget is the caller's, not per-attempt."""
        from hyperspace_tpu.telemetry import metrics

        attempts_cap = int(max_attempts) if max_attempts is not None \
            else self._max_attempts
        last_exc: Optional[Exception] = None
        last_label: Optional[str] = None
        tried: set = set()
        for attempt in range(1, attempts_cap + 1):
            remaining = self._remaining_ms(deadline_at)
            if remaining is not None and remaining <= 1.0:
                break  # budget exhausted: surface the last failure
            if len(tried) >= len(self._endpoints):
                tried.clear()  # every endpoint failed once: start over
            ep = self._pick(tried, exclude=exclude)
            tried.add(ep.label)
            if note is not None:
                note.setdefault("labels", set()).add(ep.label)
            if last_label is not None and last_label != ep.label:
                # A retry routed AWAY from the endpoint that failed —
                # the failover event the drill test counts.
                metrics.inc("client.failover")
            # Spread the remaining budget over the attempts still
            # available (bounded by distinct endpoints): a GRAY failure
            # — server alive but serving nothing — otherwise eats the
            # whole budget in one socket timeout, leaving nothing to
            # fail over with.
            if remaining is not None:
                spread = max(1, min(attempts_cap - attempt + 1,
                                    len(self._endpoints)))
                timeout_s = remaining / 1000.0 / spread + 0.05
            else:
                timeout_s = None
            retry_after_ms: Optional[float] = None
            kind = "connection"
            t0 = time.monotonic()
            try:
                client = ep.acquire(self._tenant, timeout_s=timeout_s)
            except OSError as exc:
                last_exc = ConnectionError(
                    f"connect to {ep.label} failed: {exc}")
            else:
                try:
                    table = client.query(
                        spec,
                        deadline_ms=self._remaining_ms(deadline_at)
                        if deadline_at is not None else deadline_ms,
                        timeout_s=timeout_s)
                except QueryFailedError as exc:
                    # The server closes the connection after an ERR.
                    ep.discard(client)
                    self.last_trace_id = exc.trace_id
                    if not exc.retryable:
                        raise  # BADREQ/FAILED: same answer everywhere
                    kind = exc.code.lower()
                    retry_after_ms = exc.retry_after_ms
                    last_exc = exc
                except (ConnectionError, OSError) as exc:
                    ep.discard(client)
                    last_exc = exc
                else:
                    ep.release(client)
                    self.last_trace_id = client.last_trace_id
                    self._observe_latency(
                        (time.monotonic() - t0) * 1000.0)
                    if self._breaker_enabled and ep.breaker_success():
                        metrics.inc("client.breaker.close")
                        self._breaker_gauge()
                    return table
            metrics.inc("client.retry")
            metrics.inc(f"client.retry.{kind}")
            last_label = ep.label
            if self._breaker_enabled and ep.breaker_failure(
                    self._breaker_failures,
                    self._breaker_cooldown_ms / 1000.0):
                metrics.inc("client.breaker.open")
                self._breaker_gauge()
            # Penalize the failed endpoint for the server's hinted
            # window (or a nominal beat) so the next pick avoids it.
            ep.penalized_until = time.monotonic() + \
                (retry_after_ms or 100.0) / 1000.0
            if attempt < attempts_cap:
                if not self._backoff(attempt, retry_after_ms, deadline_at):
                    break  # no budget left to sleep AND attempt again
        if last_exc is None:
            last_exc = TimeoutError(
                f"deadline budget ({deadline_ms} ms) exhausted before "
                f"any attempt completed")
        raise last_exc

    def _query_hedged(self, spec: Dict[str, Any],
                      deadline_ms: Optional[float],
                      deadline_at: Optional[float]) -> pa.Table:
        """Run the attempts loop in a worker thread; when it is slower
        than the hedge delay, fire ONE extra single-attempt on a
        different survivor.  First response wins; the loser finishes
        reading its own connection in the background and its response
        is discarded by request_id."""
        from hyperspace_tpu.interop.query import mint_trace_id
        from hyperspace_tpu.telemetry import metrics

        lock = threading.Lock()
        done = threading.Event()
        state: Dict[str, Any] = {"winner": None, "table": None,
                                 "trace": None, "outstanding": 1}
        errs: Dict[str, Exception] = {}
        primary_note: Dict[str, Any] = {}

        def _runner(tag: str, req_spec: Dict[str, Any],
                    exclude: Optional[set], note: Optional[dict],
                    max_attempts: Optional[int] = None) -> None:
            try:
                # The hedge branch runs a SINGLE attempt: its job is
                # beating a slow primary, not re-running the whole retry
                # ladder in parallel.
                table = self._query_attempts(
                    req_spec, deadline_ms, deadline_at,
                    exclude=exclude, note=note, max_attempts=max_attempts)
            except Exception as exc:  # noqa: BLE001 — reported to caller
                with lock:
                    errs[tag] = exc
                    state["outstanding"] -= 1
                    if state["outstanding"] <= 0 \
                            and state["winner"] is None:
                        done.set()
            else:
                with lock:
                    state["outstanding"] -= 1
                    if state["winner"] is None:
                        state["winner"] = tag
                        state["table"] = table
                        state["trace"] = self.last_trace_id
                        done.set()
                    # else: the loser — its request_id lost the race and
                    # its fully-read response is dropped here.

        primary_spec = {**spec, "request_id": mint_trace_id()}
        threading.Thread(
            target=_runner, args=("primary", primary_spec, None,
                                  primary_note),
            name="hs-client-primary", daemon=True).start()

        delay_s = self._hedge_delay_s()
        remaining = self._remaining_ms(deadline_at)
        if remaining is not None:
            delay_s = min(delay_s, max(0.0, remaining / 1000.0))
        fired = False
        if not done.wait(delay_s) and len(self._endpoints) > 1:
            with lock:
                slow_primary = state["winner"] is None \
                    and state["outstanding"] > 0
                if slow_primary:
                    state["outstanding"] += 1
            if slow_primary:
                remaining = self._remaining_ms(deadline_at)
                if remaining is None or remaining > 5.0:
                    metrics.inc("client.hedge.sent")
                    fired = True
                    hedge_spec = {**spec, "request_id": mint_trace_id()}
                    threading.Thread(
                        target=_runner,
                        args=("hedge", hedge_spec,
                              set(primary_note.get("labels", set())),
                              None, 1),
                        name="hs-client-hedge", daemon=True).start()
                else:
                    with lock:
                        state["outstanding"] -= 1
        remaining = self._remaining_ms(deadline_at)
        # The attempts' socket timeouts are budget-bounded, so a small
        # grace past the deadline is enough for the threads to settle.
        done.wait(remaining / 1000.0 + 0.5 if remaining is not None
                  else None)
        with lock:
            if state["winner"] is not None:
                if fired and state["winner"] == "hedge":
                    metrics.inc("client.hedge.wins")
                self.last_trace_id = state["trace"]
                return state["table"]
            exc = errs.get("primary") or errs.get("hedge")
        if exc is not None:
            raise exc
        raise TimeoutError(
            f"deadline budget ({deadline_ms} ms) exhausted before any "
            f"attempt completed")

    def _hedge_delay_s(self) -> float:
        """The wait before hedging: the configured delay, or — when 0 —
        2× the latency EWMA clamped to [10 ms, 500 ms] (50 ms with no
        history yet)."""
        if self._hedge_delay_ms > 0.0:
            return self._hedge_delay_ms / 1000.0
        with self._lock:
            ewma = self._lat_ewma_ms
        if ewma <= 0.0:
            return 0.050
        return min(0.500, max(0.010, 2.0 * ewma / 1000.0))

    def _backoff(self, attempt: int, retry_after_ms: Optional[float],
                 deadline_at: Optional[float] = None) -> bool:
        """Jittered exponential backoff, honoring the server's
        ``retry-after-ms`` hint as the step when present — capped by
        what remains of the deadline budget.  Returns False when the
        budget cannot fund the sleep (the caller stops retrying)."""
        step = retry_after_ms if retry_after_ms is not None \
            else 50.0 * (2.0 ** (attempt - 1))
        delay_ms = min(self._backoff_cap_ms, step) * (0.5 + random.random())
        remaining = self._remaining_ms(deadline_at)
        if remaining is not None:
            if remaining <= 2.0:
                return False
            delay_ms = min(delay_ms, remaining - 1.0)
        time.sleep(delay_ms / 1000.0)
        return True

    def close(self) -> None:
        for ep in self._endpoints:
            ep.close_idle()
        if self._breaker_enabled:
            # The open-now gauge describes THIS client's live routing
            # table; with the client gone nothing is "open now" — a
            # stale nonzero would keep the doctor's client check
            # warning forever.
            from hyperspace_tpu.telemetry import metrics

            metrics.set_gauge("client.breaker.open_now", 0.0)

    def __enter__(self) -> "FleetQueryClient":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
