"""The main-path kernels compile for a v5e chip at production widths.

No chip is needed: the TPU compiler is installed, and it compiles for a
``v5e:2x2`` topology that is described, not attached
(docs/13-benchmarking.md; the on-chip-measurement guide §2).  Interpret
mode and the CPU backend accept programs the chip's compiler refuses —
an unaligned slice, too much VMEM, a program that never finishes
compiling — so these compiles guard every PR at no chip time.

The production batch is ``deviceBatchRows`` = 1<<20 rows with 200
buckets.  The topology is described inside a module-scoped fixture,
never at import time: only one process may load the TPU library, and the
suite runs under several xdist workers.
"""

from __future__ import annotations

import re
import time

import jax
import jax.numpy as jnp
import pytest

ROWS = 1 << 20
BUCKETS = 200
GROUPS = 1 << 18


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """ShapeDtypeStruct factory on one described v5e chip.  The
    persistent compile cache is off around these compiles: an entry
    written for a described device cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    sharding = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=sharding)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def compiled_pallas(monkeypatch):
    """Kernels traced here take their TPU branch: ``_interpret`` asks
    ``jax.default_backend()``, which is the CPU in this process."""
    from hyperspace_tpu.ops import pallas_kernels

    monkeypatch.setattr(pallas_kernels, "_interpret", lambda: False)


def _compile(fn, *args, x64: bool = False):
    with jax.enable_x64(x64):
        return jax.jit(fn).lower(*args).compile()


def test_hash_buckets(chip, compiled_pallas):
    from hyperspace_tpu.ops.pallas_kernels import hash_buckets

    words = chip((ROWS, 2), jnp.uint32)
    out = _compile(lambda w: hash_buckets((w,), BUCKETS), words)
    assert "tpu_custom_call" in out.as_text()


def test_bucket_histogram(chip, compiled_pallas):
    from hyperspace_tpu.ops.pallas_kernels import bucket_histogram

    ids = chip((ROWS,), jnp.int32)
    out = _compile(lambda b: bucket_histogram(b, BUCKETS), ids)
    assert "tpu_custom_call" in out.as_text()


def test_route_sort_is_the_bucket_sort_and_compiles(chip, compiled_pallas):
    """The build's fused hash → lexsort program, with the pallas hash;
    the monolithic build (ops/sort) traces the very same function."""
    from hyperspace_tpu.ops import sort
    from hyperspace_tpu.ops.hash import _route_sort_impl

    assert sort._bucket_sort_impl is _route_sort_impl
    words = chip((ROWS, 2), jnp.uint32)
    out = _compile(
        lambda w, o, n: _route_sort_impl((w,), (o,), n, BUCKETS, True),
        words, words, chip((), jnp.int32))
    assert "tpu_custom_call" in out.as_text()


def test_group_sort(chip):
    from hyperspace_tpu.ops.aggregate import _group_sort

    _compile(lambda w, n: _group_sort((w,), n),
             chip((ROWS, 2), jnp.uint32), chip((), jnp.int64), x64=True)


def test_segment_reduce_float64_and_int64(chip):
    from hyperspace_tpu.ops.aggregate import _segment_reduce

    _compile(
        lambda p, b, n, f, i: _segment_reduce(
            p, b, n, (f, i, f), ops=("sum", "max", "mean", "count"),
            capacity=GROUPS),
        chip((ROWS,), jnp.int32), chip((ROWS,), jnp.bool_),
        chip((), jnp.int64), chip((ROWS,), jnp.float64),
        chip((ROWS,), jnp.int64), x64=True)


def _loops(compiled) -> int:
    """``while`` ops in a compiled program: a binary search over the
    keys compiles to one, a sort to none."""
    return len(re.findall(r"\swhile\(", compiled.as_text()))


def test_match_ranges_int64(chip):
    from hyperspace_tpu.ops.join import _match_ranges

    keys = chip((ROWS,), jnp.int64)
    assert _loops(_compile(_match_ranges, keys, keys, x64=True)) == 0


def test_expand(chip):
    from hyperspace_tpu.ops.join import _expand

    ranges = chip((ROWS,), jnp.int32)
    out = _compile(lambda lo, hi: _expand(lo, hi, 4 * ROWS), ranges, ranges,
                   x64=True)
    assert _loops(out) == 0


def test_join_ranges_int32_keys(chip):
    """The join's sort + match program on the keys it runs with: integer
    keys narrowed to int32 whenever they fit (ops/join.py)."""
    from hyperspace_tpu.ops.join import _join_ranges

    keys = chip((ROWS,), jnp.int32)
    count = chip((), jnp.int32)
    assert _loops(_compile(_join_ranges, keys, keys, count, count,
                           x64=True)) == 0


def test_join_ranges_int64_keys_has_no_search_loop(chip):
    """Q3's join at its padded shapes: resident int64 keys, 1<<20 orders
    against 1<<22 lineitems.  Ranked by sorts, the program holds no
    loop of dependent gathers; the cold compile seconds are printed."""
    from hyperspace_tpu.ops.join import _join_ranges

    count = chip((), jnp.int64)
    t0 = time.perf_counter()
    out = _compile(_join_ranges, chip((ROWS,), jnp.int64),
                   chip((4 * ROWS,), jnp.int64), count, count, x64=True)
    print(f"_join_ranges int64 {ROWS}x{4 * ROWS}: compiled in "
          f"{time.perf_counter() - t0:.1f} s")
    assert _loops(out) == 0


def test_topk_groups_float64(chip):
    from hyperspace_tpu.ops.join_agg import _topk_groups

    _compile(lambda c, n: _topk_groups(c, n, k=10, ascending=False,
                                       capacity=GROUPS),
             chip((GROUPS,), jnp.float64), chip((), jnp.int64), x64=True)
