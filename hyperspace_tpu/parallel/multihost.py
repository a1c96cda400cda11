"""Multi-slice / multi-host distribution: DCN-aware mesh + hierarchical shuffle.

The reference's cluster story is Spark's shuffle service over TCP — flat:
every executor pair exchanges directly (SURVEY.md §2.4).  TPU pods are NOT
flat: chips within a slice talk over ICI (high bandwidth, low latency);
slices talk over DCN (data-center network — an order of magnitude slower).
A flat all_to_all over S slices x P chips issues S*P-1 messages per chip,
most of them over DCN.

``hierarchical_bucket_shuffle`` runs the bucket shuffle in TWO stages over
a 2-axis mesh ``("dcn", "ici")``:

  1. all_to_all over the DCN axis only: each chip sends every row straight
     to the row's DESTINATION SLICE (at its own intra-slice position) —
     S-1 large messages per chip on the slow link, each row crossing DCN
     exactly once;
  2. all_to_all over the ICI axis inside the destination slice: rows fan
     out to their final owner chip — P-1 messages on the fast link;
  3. the same per-device lexsort as the flat shuffle.

Bucket ownership is identical to the flat shuffle's (range partition over
the flattened (slice, chip) order), so the result is BIT-IDENTICAL to
``parallel.shuffle.bucket_shuffle`` on the same devices — only the traffic
pattern changes.  Capacity is padded per stage (the MoE-dispatch pattern)
with overflow counted and retried, like the flat path.

This collective path assumes every participant stays alive: a SIGKILLed
host poisons the all_to_all and wedges the survivors.  The
crash-TOLERANT cross-host build is ``parallel/multihost_build.py`` —
the same bucket-ownership contract (``sharded_build.bucket_group_bounds``)
executed through crash-recoverable work claims over the LogStore seam,
where losing a host costs one claim TTL, not the build.  Use this
module's collectives for healthy-pod throughput; use the claim build
when partial failure is in scope.

On real multi-host pods, call ``initialize_distributed()`` first (one
process per host; jax.distributed wires the DCN coordinator), then
``build_mesh_2d(n_slices, chips_per_slice)``.  Single-host validation uses
the same code over virtual CPU devices (tests/test_parallel.py runs 2x4
and 4x2 meshes); tests/test_multiprocess.py additionally wires TWO OS
processes through ``initialize_distributed`` over CPU and runs the
two-stage all_to_all pattern across the process boundary.  Remaining
pod-only gap: ``hierarchical_bucket_shuffle`` takes process-local numpy
inputs, so multi-process runs must feed each host its own shard (the
natural pod usage); the single entry point has not been driven end-to-end
across processes in this environment.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P
from jax import shard_map as _shard_map

from hyperspace_tpu.execution import sync_guard
from hyperspace_tpu.ops.hash import _bucket_ids_impl, use_pallas
from hyperspace_tpu.parallel.shuffle import (
    ShuffleResult,
    empty_shuffle_result,
    make_row_records,
    marshal_shuffle_inputs,
    scatter_to_buffer,
    sort_received,
    unpack_shuffle_output,
)

DCN_AXIS = "dcn"
ICI_AXIS = "ici"


def initialize_distributed(coordinator_address: Optional[str] = None,
                           num_processes: Optional[int] = None,
                           process_id: Optional[int] = None) -> None:
    """Wire the multi-host runtime (one call per host process, before any
    other jax use).  With no arguments jax auto-detects the TPU pod
    environment; explicit arguments serve CPU/GPU clusters."""
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)


def build_mesh_2d(n_slices: int, chips_per_slice: Optional[int] = None,
                  devices: Optional[Sequence] = None) -> Mesh:
    """A 2-axis ``(dcn, ici)`` mesh: axis 0 crosses slices, axis 1 stays
    within one.  ``jax.devices()`` enumerates the full pod; flattened
    (slice-major) order matches the 1-axis mesh's device order, so bucket
    ownership agrees with the flat shuffle."""
    if devices is None:
        devices = jax.devices()
    if chips_per_slice is None:
        if len(devices) % n_slices:
            raise ValueError(
                f"{len(devices)} devices do not split into {n_slices} slices")
        chips_per_slice = len(devices) // n_slices
    devices = np.asarray(devices[:n_slices * chips_per_slice]).reshape(
        n_slices, chips_per_slice)
    return Mesh(devices, (DCN_AXIS, ICI_AXIS))


def _hier_kernel(num_buckets: int, S: int, Pn: int, cap_dcn: int,
                 cap_ici: int, n_key_cols: int, pallas: bool,
                 hash_words, order_words, row_words, payload, valid):
    """Per-device body under shard_map over the (dcn, ici) mesh.  Inputs
    are the LOCAL shard (L rows).  Record layout, scatter packing, and
    the final sort are SHARED with the flat kernel (parallel/shuffle.py)
    — that sharing is what makes the two shuffles bit-identical."""
    word_cols = tuple(hash_words[:, 2 * k:2 * k + 2]
                      for k in range(n_key_cols))
    bucket = _bucket_ids_impl(word_cols, num_buckets, pallas)
    n_devices = S * Pn
    buckets_per_device = -(-num_buckets // n_devices)
    owner = bucket // buckets_per_device           # global device id
    dest_slice = owner // Pn
    record = make_row_records(hash_words, order_words, row_words, payload,
                              bucket)

    # Stage 1 — DCN: rows go to their destination SLICE (at this chip's
    # own intra-slice position).  One row crosses DCN exactly once.
    d1 = jnp.where(valid.astype(bool), dest_slice, S)
    send1, over1 = scatter_to_buffer(record, d1, S, cap_dcn)
    recv1 = jax.lax.all_to_all(send1, DCN_AXIS, split_axis=0, concat_axis=0,
                               tiled=True)

    # Stage 2 — ICI: within the destination slice, rows fan out to their
    # final chip (recomputed from the bucket carried in the record).
    flag1 = recv1[:, 0]
    owner1 = recv1[:, 1].astype(jnp.int32) // buckets_per_device
    d2 = jnp.where(flag1.astype(bool), owner1 % Pn, Pn)
    send2, over2 = scatter_to_buffer(recv1, d2, Pn, cap_ici)
    recv2 = jax.lax.all_to_all(send2, ICI_AXIS, split_axis=0, concat_axis=0,
                               tiled=True)

    out, count = sort_received(recv2, n_key_cols)
    return out, count[None], jnp.stack([over1, over2])[None]


@functools.partial(
    jax.jit,
    static_argnames=("num_buckets", "n_slices", "per_slice", "cap_dcn",
                     "cap_ici", "n_key_cols", "mesh", "pallas"))
def _hier_program(hash_words, order_words, row_words, payload, valid, *,
                  num_buckets, n_slices, per_slice, cap_dcn, cap_ici,
                  n_key_cols, mesh, pallas):
    body = functools.partial(_hier_kernel, num_buckets, n_slices, per_slice,
                             cap_dcn, cap_ici, n_key_cols, pallas)
    spec = P((DCN_AXIS, ICI_AXIS))
    return _shard_map(
        body, mesh=mesh,
        in_specs=(spec, spec, spec, spec, spec),
        out_specs=(spec, spec, spec),
        # The pallas hash kernel's pallas_call cannot type its output's
        # varying mesh axes; every output here is sharded on the one axis.
        check_vma=False,
    )(hash_words, order_words, row_words, payload, valid)


def hierarchical_bucket_shuffle(
    hash_words: Sequence[np.ndarray],
    order_words: Sequence[np.ndarray],
    num_buckets: int,
    mesh: Mesh,
    payload_words: Optional[np.ndarray] = None,
    slack: float = 1.5,
    pad_local_to: int = 0,
) -> Tuple[ShuffleResult, Optional[np.ndarray]]:
    """Two-stage bucket shuffle over a ``build_mesh_2d`` mesh.  Same
    arguments and same ``ShuffleResult`` contract as
    ``parallel.shuffle.bucket_shuffle`` — and the same OUTPUT: bucket
    ownership uses the flattened device order, so flat and hierarchical
    runs on the same devices produce identical perms/buckets/counts."""
    from hyperspace_tpu.utils.xla_cache import ensure_persistent_xla_cache

    ensure_persistent_xla_cache()
    if tuple(mesh.axis_names) != (DCN_AXIS, ICI_AXIS):
        raise ValueError(
            f"hierarchical shuffle needs a (dcn, ici) mesh, got "
            f"{mesh.axis_names}")
    S, Pn = mesh.devices.shape
    n_devices = S * Pn
    n = hash_words[0].shape[0]
    if n == 0:
        return empty_shuffle_result(n_devices, payload_words)
    n_key_cols = len(hash_words)
    hw, ow, rw, pl, valid, local = marshal_shuffle_inputs(
        hash_words, order_words, payload_words, n_devices, pad_local_to)

    # Stage capacities: DCN buffers hold one device's rows for one SLICE
    # (balanced ~local/S); ICI buffers hold one device's staged rows for
    # one final chip (staged total is up to S*cap_dcn, split P ways).
    cap_dcn = max(16, int(-(-local * slack // S)))
    cap_dcn = min(local, -(-cap_dcn // 8) * 8)
    cap_ici = max(16, int(-(-S * cap_dcn * slack // Pn)))
    cap_ici = min(S * cap_dcn, -(-cap_ici // 8) * 8)

    while True:
        out, counts, overflows = _hier_program(
            hw, ow, rw, pl, valid,
            num_buckets=num_buckets, n_slices=S, per_slice=Pn,
            cap_dcn=cap_dcn, cap_ici=cap_ici, n_key_cols=n_key_cols,
            mesh=mesh, pallas=use_pallas())
        over = sync_guard.pull(
            overflows, "shuffle.overflows").reshape(n_devices, 2).sum(axis=0)
        if over[0] == 0 and over[1] == 0:
            break
        grew = False
        if over[0] and cap_dcn < local:
            cap_dcn = min(local, cap_dcn * 2)
            grew = True
        if (over[1] or over[0]) and cap_ici < S * cap_dcn:
            # A DCN overflow changes the staged volume too.
            cap_ici = min(S * cap_dcn, cap_ici * 2)
            grew = True
        if not grew:
            raise RuntimeError(
                "hierarchical_bucket_shuffle: capacity overflow at maximum")

    counts = sync_guard.pull(counts, "shuffle.counts").reshape(-1)
    perm, buckets_sorted, routed_payload = unpack_shuffle_output(
        sync_guard.pull(out, "shuffle.routed"), counts, n_devices,
        Pn * cap_ici, n_key_cols, payload_words is not None)
    return ShuffleResult(perm=perm, buckets_sorted=buckets_sorted,
                         device_row_counts=counts,
                         capacity=cap_ici), routed_payload
