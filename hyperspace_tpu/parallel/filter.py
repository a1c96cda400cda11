"""Mesh-sharded predicate evaluation: the distributed scan/filter path.

Reference analog: Spark evaluates predicates inside each executor's task
over its file split (SURVEY.md §2.4 "predicate-pushdown kernel").  Here
the predicate is one elementwise XLA program (ops/filter.compile_predicate)
whose inputs are sharded row-wise over the device mesh; GSPMD partitions
the program with ZERO collectives — each device scans 1/N of the rows in
its own HBM and only the boolean mask returns to host.

Scope: the mesh spans THIS process's addressable devices
(``jax.local_devices()``) — the filter input is a host-resident arrow
batch, which a single process owns; sharding it across other hosts'
devices is not addressable.  Multi-host scans parallelize one level up,
by giving each host its own file split.
"""

from __future__ import annotations

from typing import Callable, List, Sequence

import numpy as np

from hyperspace_tpu.execution import sync_guard
from hyperspace_tpu.parallel.mesh import SHARD_AXIS, build_mesh
from jax import enable_x64 as _enable_x64


def eval_predicate_on_mesh(fn: Callable, columns: Sequence[np.ndarray],
                           literals: List[float], mesh=None) -> np.ndarray:
    """Boolean mask for ``fn(columns, literals)`` with ``columns`` sharded
    row-wise over ``mesh`` (this process's devices by default).  Rows are
    padded up to a device multiple — only the LAST shard is copied for the
    pad; every other shard transfers zero-copy views — and the pad is
    sliced off the mask.  x64 is scoped here so int64 columns keep full
    width regardless of the caller."""
    import jax

    with _enable_x64():
        from jax.sharding import NamedSharding, PartitionSpec

        if mesh is None:
            mesh = build_mesh(devices=jax.local_devices())
        devices = list(mesh.devices.flat)
        n_dev = len(devices)
        n = int(columns[0].shape[0])
        shard_rows = -(-n // n_dev)
        sharding = NamedSharding(mesh, PartitionSpec(SHARD_AXIS))
        sharded = []
        for c in columns:
            c = np.asarray(c)
            parts = []
            for i, dev in enumerate(devices):
                piece = c[i * shard_rows:min(n, (i + 1) * shard_rows)]
                if piece.shape[0] < shard_rows:
                    piece = np.concatenate(
                        [piece, np.zeros(shard_rows - piece.shape[0],
                                         dtype=c.dtype)])
                parts.append(jax.device_put(piece, dev))
            sharded.append(jax.make_array_from_single_device_arrays(
                (shard_rows * n_dev,), sharding, parts))
        mask = fn(sharded, literals)
        return sync_guard.pull(mask, "mesh_filter.mask")[:n]
