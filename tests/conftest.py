"""Test harness configuration.

Runs JAX on a virtual 8-device CPU mesh so distribution tests exercise real
shardings without TPU hardware (the analog of the reference's local[4] Spark
with 5 shuffle partitions, build.sbt:94-101 / SparkInvolvedSuite.scala:31-36).

Environment must be set before jax is imported anywhere.
"""

import os

# Tests run on the CPU: the suite needs a deterministic 8-device mesh, and
# the chip is reached through benchmark/run.py and chip_smoke.py instead.
# The env var covers child processes; the jax.config pin below covers this
# one.
os.environ["JAX_PLATFORMS"] = "cpu"
# Tiny kernel capacity: tests build 10-row indexes; padding them to the
# production 1M-row batch would lexsort a million rows per create.
os.environ.setdefault("HS_DEVICE_BATCH_ROWS", "4096")
# Keep the persistent XLA cache out of the developer cache dir during tests.
os.environ.setdefault("HS_XLA_CACHE", "0")
# Deterministic routing thresholds: auto-calibration would derive them from
# this machine's measured physics, flipping host/device routing run to run.
# Calibration itself is tested explicitly in test_calibrate.py.
os.environ.setdefault("HS_CALIBRATE", "0")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax

jax.config.update("jax_platforms", "cpu")

import pytest

# Heavy suites excluded from the `pytest -m quick` tier (round-5 verdict:
# cap suite growth — the TPC corpora + fuzz nets grow wall-clock
# superlinearly): everything NOT listed here is auto-marked `quick` below,
# so the quick tier stays under ~3 minutes while `run-tests.py` (and CI's
# full job) keeps running the whole suite.
_HEAVY_MODULES = frozenset({
    "test_tpcds",               # 20-query TPC-DS corpus, rules on+off
    "test_sql_tpch",            # TPC-H corpus
    "test_plan_stability_tpch",  # golden-plan diffs over the corpus
    "test_fuzz_equivalence",    # hypothesis nets
    "test_fuzz_queries",
    "test_concurrency",         # cross-process races (spawn pools)
    "test_multiprocess",        # multi-host jax.distributed smoke
    "test_multihost_build",     # subprocess host fleets + SIGKILL drill
    "test_interop",             # Arrow-IPC server + C++ client build
    "test_external_build",      # streaming spill builds
    "test_chaos",               # seeded fleet chaos drill (3-server fleet)
    "test_netfaults",           # wire-fault drills + SIGSTOP subprocesses
})


def pytest_collection_modifyitems(config, items):
    for item in items:
        module = getattr(item, "module", None)
        name = getattr(module, "__name__", "").rpartition(".")[2]
        if name not in _HEAVY_MODULES:
            item.add_marker(pytest.mark.quick)


@pytest.fixture(autouse=True)
def _disarm_fault_injection():
    """The fault injector (io/faults.py) is process-global; a test that
    arms it and then fails must never leak faults into the next test."""
    yield
    from hyperspace_tpu.io import faults

    faults.clear()


@pytest.fixture(autouse=True)
def _reset_telemetry():
    """Tracing state and sinks (telemetry/trace.py) are process-global
    like the fault injector: a test that enables tracing (or a session
    conf that installs a JSONL sink) must not leak into the next test.
    Metrics are NOT reset here — the registry is additive by design and
    tests assert deltas or reset explicitly."""
    yield
    from hyperspace_tpu.lifecycle import daemon as lifecycle_daemon
    from hyperspace_tpu.telemetry import flight_recorder, trace

    trace.disable_tracing()
    trace.clear_sinks()
    flight_recorder.reset()  # the request ring is process-global too
    lifecycle_daemon.clear_drain()  # so is the drain latch a server sets


@pytest.fixture()
def tmp_index_root(tmp_path):
    """Per-test index system path (HyperspaceSuite.scala:28-121 analog)."""
    root = tmp_path / "indexes"
    root.mkdir()
    return str(root)
