"""Pluggable operation-log storage backends with conditional-put semantics.

The reference rides HDFS ``create-if-absent`` + atomic rename for its log
protocol (IndexLogManager.scala:149-165).  A production lake lives on
GCS/S3, where **rename does not exist** and the primitives are different:

  - a flat key namespace (no directories; "listing" is a prefix scan)
  - per-key **generation numbers** that bump on every successful put
  - conditional puts: ``put_if_absent`` (generation 0) and
    ``put_if_generation_match`` (the GCS ``ifGenerationMatch`` / S3
    conditional-write model)
  - **listing may lag writes** (eventual visibility), while point reads
    (GET by key) are strongly consistent

This module is the seam: :class:`LogStore` defines exactly those
primitives, and ``index/object_log_manager.py`` builds the Delta-style
numbered-commit + CAS-pointer protocol on top of them.  Two real
implementations ship:

  - :class:`PosixLogStore` — the current POSIX semantics extracted behind
    the interface (``O_EXCL`` create-if-absent; generations via a sidecar
    file under an ``flock``-serialized critical section, so conditional
    puts are atomic across real OS processes).
  - :class:`EmulatedObjectStore` — honest object-store semantics over a
    local directory: flat percent-encoded keys, per-key generations, a
    configurable **stale-list visibility window** (keys committed within
    the window are hidden from ``list_keys`` but visible to point reads),
    and no rename anywhere in its API.  ``os.replace`` appears only
    *inside* the emulation, playing the role of the store server's
    internal atomic commit.

Both stores are fault-injectable (io/faults.py) at the ``store.put`` /
``store.read`` / ``store.list`` / ``store.delete`` sites.  A ``torn`` put
COMMITS half the payload with a real generation before dying — modeling
an upload the store accepted but the writer never finished — so readers
must treat the key as burned-but-unparseable, the same envelope the POSIX
log already survives.
"""

from __future__ import annotations

import json
import os
import threading
import time
import urllib.parse
from typing import List, Optional, Tuple

from hyperspace_tpu.io import faults

try:  # flock is the cross-process arbiter; absent (non-POSIX) we degrade
    import fcntl as _fcntl  # to in-process locking only.
except ImportError:  # pragma: no cover - linux container always has it
    _fcntl = None

_LOCK_NAME = ".lock"
_GEN_SUFFIX = ".g"


class LogStore:
    """Flat key→bytes store with per-key generations and conditional puts.

    Contract (mirrors GCS object semantics):
      - ``generation(key)`` is 0 for an absent key and strictly increases
        with every successful put to that key;
      - ``put_if_absent`` / ``put_if_generation_match`` are ATOMIC with
        respect to every other mutation of the same key, across processes;
      - point reads (``read`` / ``read_with_generation`` / ``exists``)
        are strongly consistent;
      - ``list_keys`` MAY lag recent writes (stale-visibility window).
    """

    def list_keys(self, prefix: str = "") -> List[str]:
        raise NotImplementedError

    def read(self, key: str) -> bytes:
        """Bytes at ``key``; FileNotFoundError when absent."""
        raise NotImplementedError

    def read_with_generation(self, key: str) -> Tuple[Optional[bytes], int]:
        """(bytes or None, generation) — generation 0 means absent."""
        raise NotImplementedError

    def generation(self, key: str) -> int:
        raise NotImplementedError

    def exists(self, key: str) -> bool:
        return self.generation(key) > 0

    def put_if_absent(self, key: str, data: bytes) -> bool:
        """Commit ``data`` iff ``key`` does not exist.  False on conflict."""
        return self.put_if_generation_match(key, data, 0)

    def put_if_generation_match(self, key: str, data: bytes,
                                expected_generation: int) -> bool:
        """Commit ``data`` iff the key's current generation equals
        ``expected_generation`` (0 = must be absent).  False on mismatch —
        the compare-and-swap every pointer update rides."""
        raise NotImplementedError

    def delete(self, key: str) -> None:
        """Remove ``key``; absent keys are a no-op."""
        raise NotImplementedError


class PosixLogStore(LogStore):
    """The POSIX backend: keys are files in ``root``; conditional puts are
    serialized by ``flock`` on a root-level lock file (plus an in-process
    mutex), generations live in a ``<key>.g`` sidecar."""

    def __init__(self, root: str, stale_list_s: float = 0.0) -> None:
        self.root = root
        # POSIX directory listings are strongly consistent; the parameter
        # exists so either store class satisfies the same constructor.
        self.stale_list_s = 0.0
        self._mutex = threading.Lock()

    # -- key <-> filename ---------------------------------------------------
    def _encode(self, key: str) -> str:
        return key

    def _decode(self, name: str) -> str:
        return name

    def _data_path(self, key: str) -> str:
        return os.path.join(self.root, self._encode(key))

    def _gen_path(self, key: str) -> str:
        return self._data_path(key) + _GEN_SUFFIX

    # -- locking ------------------------------------------------------------
    def _locked(self):
        """Cross-process critical section: flock on ``root/.lock`` (the
        emulated store server's single-threaded commit point)."""
        store = self

        class _Section:
            def __enter__(self):
                store._mutex.acquire()
                os.makedirs(store.root, exist_ok=True)
                self._fd = os.open(os.path.join(store.root, _LOCK_NAME),
                                   os.O_CREAT | os.O_RDWR)
                if _fcntl is not None:
                    _fcntl.flock(self._fd, _fcntl.LOCK_EX)
                return self

            def __exit__(self, *exc):
                try:
                    if _fcntl is not None:
                        _fcntl.flock(self._fd, _fcntl.LOCK_UN)
                    os.close(self._fd)
                finally:
                    store._mutex.release()
                return False

        return _Section()

    # -- reads (strongly consistent) ----------------------------------------
    def _meta(self, key: str) -> Tuple[int, float]:
        """(generation, commit wall-time) from the sidecar; (0, 0) absent."""
        try:
            with open(self._gen_path(key), "r", encoding="utf-8") as f:
                meta = json.load(f)
            return int(meta["g"]), float(meta.get("t", 0.0))
        except (FileNotFoundError, ValueError, KeyError):
            # No sidecar but a data file = a pre-LogStore layout (or a
            # crash inside the emulation): report generation 1 so the data
            # stays visible and CAS still has something to compare.
            return (1, 0.0) if os.path.isfile(self._data_path(key)) else (0, 0.0)

    def generation(self, key: str) -> int:
        faults.check("store.read")
        return self._meta(key)[0]

    def read(self, key: str) -> bytes:
        faults.check("store.read")
        with open(self._data_path(key), "rb") as f:
            return f.read()

    def read_with_generation(self, key: str) -> Tuple[Optional[bytes], int]:
        faults.check("store.read")
        gen = self._meta(key)[0]
        if gen == 0:
            return None, 0
        try:
            with open(self._data_path(key), "rb") as f:
                return f.read(), gen
        except FileNotFoundError:
            return None, gen

    def list_keys(self, prefix: str = "") -> List[str]:
        faults.check("store.list")
        if not os.path.isdir(self.root):
            return []
        now = time.time()
        out: List[str] = []
        for name in os.listdir(self.root):
            if name == _LOCK_NAME or name.endswith(_GEN_SUFFIX) \
                    or ".tmp-" in name:
                continue
            key = self._decode(name)
            if prefix and not key.startswith(prefix):
                continue
            if self.stale_list_s > 0.0:
                # The visibility window: recently committed keys are
                # hidden from LISTING (point reads still see them) —
                # the eventual-consistency shape the CAS protocol must
                # survive.
                _g, t = self._meta(key)
                if t and now - t < self.stale_list_s:
                    continue
            out.append(key)
        return sorted(out)

    # -- mutations (atomic under the lock) ----------------------------------
    def _commit(self, key: str, data: bytes, gen: int) -> None:
        """Install data+generation.  The replace pair is the emulated
        server's internal atomic commit — nothing above this layer ever
        sees or needs a rename."""
        data_path = self._data_path(key)
        tmp = f"{data_path}.tmp-{os.getpid()}"
        with open(tmp, "wb") as f:
            f.write(data)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, data_path)
        gen_tmp = f"{self._gen_path(key)}.tmp-{os.getpid()}"
        with open(gen_tmp, "w", encoding="utf-8") as f:
            json.dump({"g": gen, "t": time.time()}, f)
        os.replace(gen_tmp, self._gen_path(key))

    def put_if_generation_match(self, key: str, data: bytes,
                                expected_generation: int) -> bool:
        from hyperspace_tpu.telemetry import metrics
        from hyperspace_tpu.telemetry.trace import span

        kind = faults.fire("store.put")  # enospc/eio/crash raise here
        with span("store.put", key=key) as sp, self._locked():
            metrics.inc("log.store.puts")
            cur = self._meta(key)[0]
            if cur != int(expected_generation):
                # The optimistic-concurrency signal: some other writer
                # moved the key's generation between read and CAS.
                metrics.inc("log.cas.conflicts")
                sp.set(outcome="conflict")
                return False
            sp.set(outcome="committed", bytes=len(data))
            if kind == "torn":
                # The store ACCEPTED a partial upload: commit half the
                # payload with a real generation, then the writer dies.
                # The key is burned; readers must skip the garbage.
                self._commit(key, data[:max(1, len(data) // 2)], cur + 1)
                raise faults.InjectedCrash(
                    f"injected torn put of {key!r}")
            self._commit(key, data, cur + 1)
            return True

    def delete(self, key: str) -> None:
        faults.check("store.delete")
        with self._locked():
            for path in (self._data_path(key), self._gen_path(key)):
                try:
                    os.unlink(path)
                except FileNotFoundError:
                    pass


class EmulatedObjectStore(PosixLogStore):
    """Object-store semantics over a local directory: flat percent-encoded
    keys (``/`` is data, not structure), per-key generations, conditional
    puts, and a configurable stale-list visibility window.

    The window defaults to 0 (strong listing); tests and the conf key
    ``hyperspace.system.objectStore.staleListMs`` widen it to prove the
    log protocol never *depends* on listing freshness: conditional puts
    arbitrate id claims, and readers probe forward with point reads
    (``ObjectStoreLogManager.get_latest_id``)."""

    def __init__(self, root: str, stale_list_s: float = 0.0) -> None:
        super().__init__(root)
        self.stale_list_s = float(stale_list_s)

    def _encode(self, key: str) -> str:
        return urllib.parse.quote(key, safe="")

    def _decode(self, name: str) -> str:
        return urllib.parse.unquote(name)


def store_for(conf, root: str) -> LogStore:
    """The store every subsystem opens under ``root``: backend class
    from ``hyperspace.index.logStoreClass``, stale-list window from
    ``hyperspace.system.objectStore.staleListMs``."""
    from hyperspace_tpu.exceptions import HyperspaceError
    from hyperspace_tpu.utils.reflection import load_class

    cls = load_class(conf.log_store_class, LogStore, HyperspaceError)
    return cls(root, stale_list_s=float(getattr(
        conf, "object_store_stale_list_ms", 0.0)) / 1000.0)
