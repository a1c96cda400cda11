"""Strict-mode runtime device→host sync guard
(``hyperspace.system.deviceGuard.enabled``, default off).

The device-discipline lint rule proves the CHECKED-IN hot path pulls
from device only through attributed seams; this module enforces the same
contract at RUNTIME, where the static pass cannot see — a monkeypatched
kernel, a REPL experiment, a dependency upgrade that starts calling
``.item()`` on our arrays.  Armed, it turns PR 11's ``exec.transfer.*``
metrics from an observation into a contract tests can assert.

Two halves:

  - **attributed seams** — :func:`pull` (array) and :func:`scalar`
    (0-d/dynamic-shape sync point) are the sanctioned device→host
    conversions.  Each runs inside an allowance window, counts
    ``guard.sync.attributed``, and feeds ``exec.transfer.d2h.bytes``
    through the timeline seam.  The blocking conversion itself is
    a ``device.sync`` span (tag ``site``): with the guard's seams the
    only place the host waits on the device, it is where tracing sees
    the wait.  They are cheap pass-throughs for host inputs and when the
    guard is off.
  - **the guard** — when a collect runs with the conf key on,
    :func:`arm` patches the concrete jax array type's scalar-conversion
    surface (``item``/``tolist``/``__float__``/``__int__``/``__bool__``/
    ``__index__``/``__array__``) to RAISE :class:`DeviceSyncError` (and
    count ``guard.sync.violations``) on any conversion outside an
    allowance window.  The patch is process-global, installed lazily on
    first arming — with the conf off (the default) nothing is patched
    and jax is untouched.

CPU-backend caveat (documented in docs/18): on the CPU backend numpy can
reach a jax array's buffer zero-copy, so a raw ``np.asarray`` is not
interceptable there — but ``.item()``/``float()``/``bool()``/``int()``
(the scalar syncs that dominate the 196-site audit) always route through
the patched surface, and the static rule covers ``np.asarray`` at review
time.  On TPU every pull is a device→host copy through ``__array__`` and
is caught.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any, Iterator

from hyperspace_tpu.exceptions import DeviceSyncError
from hyperspace_tpu.telemetry.trace import span

_armed = False
_patched = False
_install_lock = threading.Lock()
_local = threading.local()


def _depth() -> int:
    return getattr(_local, "depth", 0)


@contextlib.contextmanager
def allowed() -> Iterator[None]:
    """Allowance window: device→host conversions inside the block are
    attributed (used by the seams below and timeline.kernel_end)."""
    _local.depth = _depth() + 1
    try:
        yield
    finally:
        _local.depth = _depth() - 1


def armed() -> bool:
    return _armed


def arm(conf) -> None:
    """Apply the session conf to the process-global guard (called per
    collect, like the fault injector / tracing conf re-application).
    First arming installs the patch; disarming leaves it installed but
    inert (one module-global read per conversion)."""
    global _armed
    enabled = bool(getattr(conf, "device_guard_enabled", False))
    if enabled and not _patched:
        _install()
    _armed = enabled


def _is_device(x: Any) -> bool:
    cls = type(x)
    return cls.__module__.split(".")[0] in ("jaxlib", "jax")


def pull(x: Any, site: str = "") -> Any:
    """THE sanctioned device→host array pull: ``np.asarray`` inside an
    allowance window, ``exec.transfer.d2h.bytes``-counted and
    ``guard.sync.attributed``-counted.  Host inputs pass through."""
    import numpy as np

    if not _is_device(x):
        return np.asarray(x)
    with allowed(), span("device.sync", site=site):
        out = np.asarray(x)
    _count_attributed(site)
    from hyperspace_tpu.telemetry import timeline

    timeline.record_transfer("d2h", int(out.nbytes))
    return out


def scalar(x: Any, site: str = "") -> Any:
    """The sanctioned dynamic-shape sync point: one scalar (a match
    count, a group count) crossing to host, attributed.  Returns a
    Python number; host numbers pass through."""
    if not _is_device(x):
        return x
    import numpy as np

    with allowed(), span("device.sync", site=site):
        out = np.asarray(x).item()
    _count_attributed(site)
    return out


def _count_attributed(site: str) -> None:
    if not _armed:
        return
    from hyperspace_tpu.telemetry import metrics

    metrics.inc("guard.sync.attributed")


def _violation(kind: str):
    from hyperspace_tpu.telemetry import metrics

    metrics.inc("guard.sync.violations")
    return DeviceSyncError(
        f"unattributed device→host sync via {kind} while "
        f"hyperspace.system.deviceGuard.enabled is on — route the pull "
        f"through execution/sync_guard.pull()/scalar() (or the "
        f"timeline kernel seams) so exec.transfer.*/exec.kernel.* can "
        f"attribute it (docs/18-static-analysis.md)")


def _install() -> None:
    """Patch the concrete jax array type's host-conversion surface.
    Idempotent.  Raises RuntimeError when the surface cannot be patched:
    an armed guard that patches nothing would pass every test while
    catching no sync."""
    global _patched
    with _install_lock:
        if _patched:
            return
        from jaxlib._jax import ArrayImpl as cls

        missing = []

        def _wrap(name: str):
            orig = getattr(cls, name, None)
            if orig is None:
                missing.append(name)
                return

            def guarded(self, *args, **kwargs):
                if _armed and _depth() == 0:
                    raise _violation(f"{name}()")
                return orig(self, *args, **kwargs)

            guarded.__name__ = name
            try:
                setattr(cls, name, guarded)
            except (AttributeError, TypeError):
                missing.append(name)

        for name in ("item", "tolist", "__float__", "__int__",
                     "__bool__", "__index__", "__array__"):
            _wrap(name)
        if missing:
            raise RuntimeError(
                f"deviceGuard cannot patch {cls.__module__}."
                f"{cls.__qualname__}: {', '.join(missing)}")
        _patched = True
