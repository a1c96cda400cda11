"""Worker for the multi-process DCN smoke test (test_multiprocess.py).

Run as: python multiprocess_worker.py <coordinator> <num_procs> <pid>

Each process owns 2 virtual CPU devices; `initialize_distributed` wires
the processes into one 4-device runtime; `build_mesh_2d(2)` lays the
(dcn, ici) mesh so the DCN axis crosses the PROCESS boundary.  The body
then runs the hierarchical shuffle's exact two-stage traffic pattern
(all_to_all over dcn, then over ici) on deterministic data and each
process verifies its addressable output shards against a numpy
simulation — the same answer a single-process run produces.
"""

import functools
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=2").strip()

import jax

jax.config.update("jax_platforms", "cpu")
# Cross-process collectives on the CPU backend need the gloo transport on
# jax versions where the default CPU client ships none ("Multiprocess
# computations aren't implemented on the CPU backend" otherwise).
try:
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
except Exception:  # older jax: option absent, default transport works
    pass

import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

from hyperspace_tpu.parallel.multihost import (  # noqa: E402
    DCN_AXIS,
    ICI_AXIS,
    build_mesh_2d,
    initialize_distributed,
)

from jax import shard_map as _shard_map


def main() -> None:
    coordinator, num_procs, pid = (sys.argv[1], int(sys.argv[2]),
                                   int(sys.argv[3]))
    initialize_distributed(coordinator_address=coordinator,
                           num_processes=num_procs, process_id=pid)
    assert jax.process_count() == num_procs, jax.process_count()
    assert len(jax.local_devices()) == 2
    n_devices = len(jax.devices())
    assert n_devices == 2 * num_procs, n_devices

    S, Pn = num_procs, 2
    mesh = build_mesh_2d(S)
    assert mesh.devices.shape == (S, Pn)
    # The DCN axis must cross the process boundary: each mesh ROW is one
    # process's devices.
    for s in range(S):
        owners = {d.process_index for d in mesh.devices[s]}
        assert owners == {s}, (s, owners)

    rows_per_dev = 8
    n = n_devices * rows_per_dev
    data = np.arange(n * 2, dtype=np.int32).reshape(n, 2)

    def body(x):
        # The hierarchical shuffle's traffic pattern: stage 1 crosses
        # slices on the slow axis, stage 2 fans out within the slice.
        x = jax.lax.all_to_all(x, DCN_AXIS, split_axis=0, concat_axis=0,
                               tiled=True)
        x = jax.lax.all_to_all(x, ICI_AXIS, split_axis=0, concat_axis=0,
                               tiled=True)
        return x + 1

    @functools.partial(jax.jit, static_argnames=())
    def program(x):
        return _shard_map(body, mesh=mesh, in_specs=P((DCN_AXIS, ICI_AXIS)),
                          out_specs=P((DCN_AXIS, ICI_AXIS)))(x)

    sharding = NamedSharding(mesh, P((DCN_AXIS, ICI_AXIS)))
    local = data[pid * Pn * rows_per_dev:(pid + 1) * Pn * rows_per_dev]
    garr = jax.make_array_from_process_local_data(sharding, local)
    out = program(garr)

    # Numpy simulation of the same two tiled all_to_alls — the parity
    # oracle (identical to what a single-process run computes).
    shards = data.reshape(S, Pn, rows_per_dev, 2)
    chunk = rows_per_dev // S
    stage1 = np.empty_like(shards)
    for s in range(S):
        for p in range(Pn):
            stage1[s, p] = np.concatenate(
                [shards[src, p, s * chunk:(s + 1) * chunk] for src in
                 range(S)])
    chunk2 = rows_per_dev // Pn
    stage2 = np.empty_like(stage1)
    for s in range(S):
        for p in range(Pn):
            stage2[s, p] = np.concatenate(
                [stage1[s, src, p * chunk2:(p + 1) * chunk2] for src in
                 range(Pn)])
    want = stage2 + 1

    for shard in out.addressable_shards:
        dev_id = shard.index[0].start // rows_per_dev
        s, p = dev_id // Pn, dev_id % Pn
        np.testing.assert_array_equal(np.asarray(shard.data), want[s, p])
    print(f"proc{pid}: DCN smoke OK over {n_devices} devices "
          f"({S} processes x {Pn})")


if __name__ == "__main__":
    main()
