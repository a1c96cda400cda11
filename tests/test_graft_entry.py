"""Driver-contract tests for __graft_entry__.

The ``dryrun_multichip`` smoke now lives with the rest of the mesh
coverage in tests/test_parallel_mesh.py (rule-table units, shard/gather
round-trips, ownership bit-equality, subprocess fallback); this module
keeps the ``entry()`` contract — the flagship single-chip compute step
must stay jittable from a fresh process.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    # Simulate the driver: no pytest conftest, no pre-set virtual mesh;
    # the child is pinned to the CPU so it never contends for a chip.
    env.pop("XLA_FLAGS", None)
    env.pop("HS_DEVICE_BATCH_ROWS", None)
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=180)


def test_entry_is_jittable():
    r = _run(
        "import jax\n"
        "import __graft_entry__ as g\n"
        "fn, args = g.entry()\n"
        "out = jax.jit(fn)(*args)\n"
        "jax.block_until_ready(out)\n")
    assert r.returncode == 0, r.stderr[-2000:]
