"""ctypes binding to the native host runtime (native/hs_native.cc).

The native library accelerates the metadata-side hot loops — the per-query
file walk + stat + md5 fingerprint fold behind index-validity signatures
(FileBasedSignatureProvider.scala:38-61; SURVEY §3.2's driver bottleneck).
Loading is best-effort: the library is compiled once with g++ into the
in-checkout cache ``.hs_cache/native``, keyed by the sha256 of the
committed ``hs_native.cc`` (so a stale build of older source is never
loaded); on any failure every entry point returns None and callers fall
back to the pure-Python implementations, which are byte-identical.

Set ``HS_NATIVE=0`` to disable the native path entirely.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import List, Optional, Sequence, Tuple

# The C++ source ships INSIDE the package so pip installs keep the native
# fast path (it compiles on demand wherever g++ exists).
_SOURCE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "hs_native.cc")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_lib_failed = False
_compile_thread: Optional[threading.Thread] = None
_waited_for_compile = False
# How long the FIRST caller waits for an in-flight compile before falling
# back to pure Python (the compile keeps running; a later call picks up the
# result).  Keeps a cold cache from stalling a user query on g++ -O2.
_FIRST_CALL_WAIT_S = 5.0

_SCAN_CB = ctypes.CFUNCTYPE(None, ctypes.c_void_p, ctypes.c_char_p,
                            ctypes.c_longlong, ctypes.c_longlong)


def _cache_so_path() -> str:
    from hyperspace_tpu.utils.xla_cache import checkout_cache_dir

    with open(_SOURCE, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(checkout_cache_dir("native"),
                        f"libhs_native-{digest}.so")


def _compile(out_path: str) -> bool:
    tmp = out_path + f".tmp{os.getpid()}"
    try:
        subprocess.run(
            ["g++", "-O2", "-std=c++17", "-shared", "-fPIC",
             "-o", tmp, _SOURCE],
            check=True, capture_output=True, timeout=120)
        os.replace(tmp, out_path)
        return True
    except (OSError, subprocess.SubprocessError):
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return False


def _declare(lib: ctypes.CDLL) -> ctypes.CDLL:
    lib.hs_scan_files.restype = ctypes.c_int
    lib.hs_scan_files.argtypes = [ctypes.POINTER(ctypes.c_char_p),
                                  ctypes.c_int, _SCAN_CB, ctypes.c_void_p]
    lib.hs_scan_fingerprint.restype = ctypes.c_longlong
    lib.hs_scan_fingerprint.argtypes = [
        ctypes.POINTER(ctypes.c_char_p), ctypes.c_int, ctypes.c_char_p,
        ctypes.c_char_p, ctypes.POINTER(ctypes.c_longlong)]
    lib.hs_fold_md5.restype = None
    lib.hs_fold_md5.argtypes = [
        ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_longlong),
        ctypes.POINTER(ctypes.c_longlong), ctypes.c_longlong,
        ctypes.c_char_p, ctypes.c_char_p]
    lib.hs_md5.restype = None
    lib.hs_md5.argtypes = [ctypes.c_char_p, ctypes.c_longlong,
                           ctypes.c_char_p]
    return lib


def get_lib() -> Optional[ctypes.CDLL]:
    """The loaded native library, or None when unavailable/disabled.

    A missing cache triggers ONE background compile; callers get the Python
    fallback (None) after a short bounded wait instead of blocking a user
    query on g++.
    """
    global _lib, _lib_failed, _compile_thread
    if os.environ.get("HS_NATIVE", "1") == "0":
        return None
    if _lib is not None or _lib_failed:
        return _lib
    with _lock:
        if _lib is not None or _lib_failed:
            return _lib
        if not os.path.isfile(_SOURCE):
            _lib_failed = True
            return None
        cached = _cache_so_path()
        thread = None
        if not os.path.isfile(cached):
            if _compile_thread is None:
                _compile_thread = threading.Thread(
                    target=_compile, args=(cached,), daemon=True)
                _compile_thread.start()
            # Only ONE caller pays the bounded wait; while the compile is
            # still running everyone else gets the Python fallback at once.
            global _waited_for_compile
            if not _waited_for_compile:
                _waited_for_compile = True
                thread = _compile_thread
    if thread is not None:
        thread.join(_FIRST_CALL_WAIT_S)
    with _lock:
        if _lib is not None or _lib_failed:
            return _lib
        cached = _cache_so_path()
        if not os.path.isfile(cached):
            # Observe the thread dead FIRST, then re-check the file —
            # os.replace may land between the two looks otherwise.
            thread_dead = (_compile_thread is not None
                           and not _compile_thread.is_alive())
            if not os.path.isfile(cached):
                if thread_dead:
                    _lib_failed = True  # finished and produced nothing
                return None  # failed, or still compiling: Python fallback
        try:
            _lib = _declare(ctypes.CDLL(cached))
        except OSError:
            _lib_failed = True
    return _lib


def available() -> bool:
    return get_lib() is not None


def scan_files(root_paths: Sequence[str]
               ) -> Optional[List[Tuple[str, int, int]]]:
    """(path, size, mtime_ns) for every data file under the roots, or None
    when the native library is unavailable.  Order is unspecified; callers
    sort (as io/files.list_data_files always has)."""
    lib = get_lib()
    if lib is None:
        return None
    out: List[Tuple[str, int, int]] = []

    @_SCAN_CB
    def cb(_ctx, path, size, mtime_ns):
        out.append((path.decode("utf-8", "surrogateescape"), size, mtime_ns))

    roots = (ctypes.c_char_p * len(root_paths))(
        *[p.encode("utf-8", "surrogateescape") for p in root_paths])
    lib.hs_scan_files(roots, len(root_paths), cb, None)
    return out


def scan_fingerprint(root_paths: Sequence[str], init: str = ""
                     ) -> Optional[Tuple[str, int, int]]:
    """(md5 hex, file count, total bytes) over the sorted data files of the
    roots — walk + stat + fold in one native pass.  None when unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    roots = (ctypes.c_char_p * len(root_paths))(
        *[p.encode("utf-8", "surrogateescape") for p in root_paths])
    out_hex = ctypes.create_string_buffer(33)
    total = ctypes.c_longlong(0)
    count = lib.hs_scan_fingerprint(roots, len(root_paths),
                                    init.encode("utf-8"), out_hex,
                                    ctypes.byref(total))
    return out_hex.value.decode("ascii"), int(count), int(total.value)


def fold_md5_files(files: Sequence[Tuple[str, int, int]], init: str = ""
                   ) -> Optional[str]:
    """Native fold over (path, size, mtime) triples in the given order;
    byte-identical to utils.hashing.fold_md5 over f"{size}{mtime}{path}"."""
    lib = get_lib()
    if lib is None:
        return None
    n = len(files)
    paths = (ctypes.c_char_p * n)(
        *[f[0].encode("utf-8", "surrogateescape") for f in files])
    sizes = (ctypes.c_longlong * n)(*[f[1] for f in files])
    mtimes = (ctypes.c_longlong * n)(*[f[2] for f in files])
    out_hex = ctypes.create_string_buffer(33)
    lib.hs_fold_md5(paths, sizes, mtimes, n, init.encode("utf-8"), out_hex)
    return out_hex.value.decode("ascii")
