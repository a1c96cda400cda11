"""Persistent XLA compilation cache, applied lazily at first kernel dispatch.

First compile of the build/query kernels costs tens of seconds on a real
chip; the on-disk cache makes that a once-per-checkout cost instead of
once-per-process.  Applied from the engine's own kernel entry points — NOT
at package import — so embedding applications that merely import
hyperspace_tpu never have their own JAX programs redirected into our cache
directory.

Where the cache lives: ``JAX_COMPILATION_CACHE_DIR`` (read by JAX itself)
or an application-configured ``jax_compilation_cache_dir`` wins and no
other directory is set.  Otherwise the fixed in-checkout directory
``.hs_cache/xla`` (gitignored): a fixed path, because the path is part of
the cache key, and inside the checkout, because a fresh machine that gets
a copy of the checkout keeps nothing under ``~``.  ``HS_XLA_CACHE=0``
disables.
"""

from __future__ import annotations

import os

_applied = False


def checkout_cache_dir(name: str) -> str:
    """``<checkout>/.hs_cache/<name>``, created on demand."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    path = os.path.join(root, ".hs_cache", name)
    os.makedirs(path, exist_ok=True)
    return path


def ensure_persistent_xla_cache() -> None:
    global _applied
    if _applied:
        return
    _applied = True
    if os.environ.get("HS_XLA_CACHE", "1") == "0":
        return
    import jax

    if jax.config.jax_compilation_cache_dir:
        return  # JAX_COMPILATION_CACHE_DIR or the application chose one
    jax.config.update("jax_compilation_cache_dir", checkout_cache_dir("xla"))
    # Cache every program: on a v5e chip a cold smoke run compiles ~370
    # programs, nearly all under the default 1 s threshold, and together
    # they are most of its compile time (PERF.md).
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
