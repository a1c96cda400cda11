"""Fault-tolerant multi-host index build: cross-host bucket ownership,
crash-recoverable work claims, exactly-once commit.

PR 14's mesh made the build data-parallel inside ONE process; this module
takes the same bucket-ownership contract cross-host over the only seam
every host already shares — the index tree's filesystem/object store.  N
subprocess hosts cooperate on one build with no live collective between
them, because a collective is exactly what a SIGKILLed participant
poisons: rows move between hosts as spill files, and coordination moves
through :class:`~hyperspace_tpu.lifecycle.lease.WorkClaims` — the
maintenance lease's TTL + epoch-fencing CAS protocol, one claim per work
item.  (``jax.distributed`` over gloo/DCN remains the collective path
for healthy pods — ``parallel/multihost.py``; this module is the one
that survives losing a host.)

The work items mirror the single-process pipeline's two phases
(``actions/create.py`` ``_BucketSpill``), so the bytes cannot diverge:

  - ``chunk-<n>``: route one DETERMINISTIC slice of the global row
    stream (the same ``device_batch_rows`` boundaries ``_stream_build``
    cuts) through the same fused route kernel, landing one Arrow IPC
    run file per (chunk, bucket group) in the shared spill dir — writes
    are temp + atomic rename, so a half-written run is never visible.
  - ``group-<g>``: once every chunk claim is done, merge one bucket
    group's runs in chunk order (ties = global row order, exactly like
    ``_finish_group``), sort each bucket, and parquet-encode into the
    holder's OWN staging directory; the claim's done record carries the
    staged manifest (file names + per-file sha256).

Failure story:

  - a SIGKILLed/SIGSTOPped host's claims expire after ``claimTtlS``; a
    survivor reclaims (epoch bump) and redoes exactly those items.
    Re-finalizing a group is idempotent — byte-identical files — so it
    does not matter which attempt wins, only that exactly one does.
  - a fenced zombie (SIGCONT after takeover) loses the done-record CAS,
    journals ``claim.fence``, and deletes its own staged files.
  - the coordinator (the CreateAction itself) validates the union —
    every group covered by a done claim whose staged files exist and
    hash to their manifest — promotes the winning files into the next
    ``v__=N`` dir, and then the ordinary action commit at
    ``base_id + 2`` (``io/log_store.put_if_absent``) is the ONE
    transaction that publishes all of it or nothing.
  - build scratch lives under ``<systemPath>/_hyperspace_build/
    build-<pid>-<token>/``; a dead coordinator's whole dir is reaped at
    the next build start (the ``reap_orphan_spill_dirs`` idiom).

``telemetry/doctor.py`` grades leftover claims against the PR 15 fleet
heartbeats (hosts here publish them when fleet telemetry is on):
expired claim with no live heartbeat → the next build will reclaim it
(warn); FRESH claim whose holder is dead → the build stalls a TTL
(crit).
"""

from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys
import time
import uuid
from typing import Any, Dict, List, Optional, Tuple

BUILD_DIR = "_hyperspace_build"
PLAN_KEY = "plan"
_BUILD_DIR_PREFIX = "build-"
_MAX_GROUPS = 8  # must match _BucketSpill._MAX_GROUPS (same group cuts)


def armed(conf) -> bool:
    """True when createIndex should run through the claim pipeline.

    0 disables (the ordinary in-process build); 1 runs a single
    subprocess host through the SAME claim/stage/commit protocol —
    degenerate but useful as the baseline for a 1-vs-2-host
    scaling ratio (identical per-chunk overheads on both sides); >= 2
    is the real multi-host build."""
    return int(getattr(conf, "multihost_build_hosts", 0)) >= 1


def build_root(conf) -> str:
    from hyperspace_tpu.index.path_resolver import PathResolver

    return os.path.join(PathResolver(conf).system_path, BUILD_DIR)


def _store(conf, build_id: str):
    from hyperspace_tpu.io.log_store import store_for

    return store_for(conf, os.path.join(build_root(conf), build_id))


def reap_orphan_build_dirs(conf) -> int:
    """Remove build scratch dirs whose coordinating pid is provably dead
    (same contract as ``actions/create.reap_orphan_spill_dirs``: a
    SIGKILLed coordinator runs no cleanup, and its dir holds a routed
    copy of the source).  Returns the number reaped."""
    from hyperspace_tpu.actions.create import _pid_alive
    from hyperspace_tpu.io.files import remove_tree

    root = build_root(conf)
    try:
        names = os.listdir(root)
    except OSError:
        return 0
    reaped = 0
    for name in names:
        if not name.startswith(_BUILD_DIR_PREFIX):
            continue
        pid_part = name[len(_BUILD_DIR_PREFIX):].split("-", 1)[0]
        if not pid_part.isdigit():
            continue
        pid = int(pid_part)
        if pid == os.getpid() or _pid_alive(pid):
            continue
        try:
            remove_tree(os.path.join(root, name), ignore_errors=True)
            reaped += 1
        except OSError:
            pass  # best-effort, like the spill reap
    return reaped


# -- the plan (written once by the coordinator, read by every host) ----------

def _group_bounds(num_buckets: int, groups: int) -> List[int]:
    # The shared ownership contract — identical cuts to
    # _BucketSpill._bounds, from the one function both layers call.
    from hyperspace_tpu.parallel.sharded_build import bucket_group_bounds

    return bucket_group_bounds(num_buckets, groups)


def _chunk_ranges(total_rows: int, batch_rows: int) -> List[List[int]]:
    """Global row-stream slices at ``device_batch_rows`` — the same
    boundaries ``_stream_build`` cuts, so single-process and multi-host
    runs route identical chunks and the merged tie order matches."""
    ranges = []
    start = 0
    while start < total_rows:
        ranges.append([start, min(start + batch_rows, total_rows)])
        start += batch_rows
    return ranges


def _code_column_names(columns, indexed, rel_schema, lineage) -> List[str]:
    """The ride-along sort-code column plan, from the relation schema
    (mirrors ``_BucketSpill._plan_code_columns``: () when any key is
    rank-mapped — chunk-local ranks don't merge across chunks)."""
    from hyperspace_tpu.actions.create import DATA_FILE_ID_COLUMN
    from hyperspace_tpu.io import columnar
    from hyperspace_tpu.io.parquet import _dtype_from_string

    for c in indexed:
        if not columnar.is_numeric_type(
                _dtype_from_string(rel_schema.get(c, "string"))):
            return []
    taken = set(columns)
    if lineage:
        taken.add(DATA_FILE_ID_COLUMN)
    names = []
    for i in range(len(indexed)):
        name = f"__hs_sort{i}"
        while name in taken:
            name += "_"
        taken.add(name)
        names.append(name)
    return names


def make_plan(conf, build_id: str, index_name: str, relation, resolved,
              files, columns, lineage: bool, batch_rows: int) -> Dict:
    """The immutable build plan every host executes against.  Requires
    parquet sources (footer row counts define the chunk boundaries
    without a decode) and the lexicographic layout (the Z-order build
    is a global two-pass and does not hash-partition)."""
    import pyarrow.parquet as pq

    from hyperspace_tpu.exceptions import HyperspaceError

    if getattr(resolved, "layout", "lexicographic") == "zorder":
        raise HyperspaceError(
            "multihost build does not support the zorder layout (the "
            "global curve is a single two-pass build); unset "
            "hyperspace.index.build.multihost.hosts for this index")
    if relation.read_format != "parquet":
        raise HyperspaceError(
            f"multihost build requires parquet sources (footer row "
            f"counts plan the chunk claims); got "
            f"{relation.read_format!r}")
    file_rows = []
    for f in files:
        try:
            file_rows.append(pq.read_metadata(f.name).num_rows)
        except Exception as e:
            raise HyperspaceError(
                f"multihost build could not read the parquet footer of "
                f"{f.name}: {e}") from e
    total = sum(file_rows)
    num_buckets = int(conf.num_buckets)
    groups = min(_MAX_GROUPS, num_buckets)
    rel_schema = dict(relation.schema())
    return {
        "v": 1,
        "build_id": build_id,
        "index": index_name,
        "format": relation.read_format,
        "roots": list(relation.root_paths),
        "options": [list(kv) for kv in relation.options],
        "rel_schema": rel_schema,
        "files": [{"name": f.name, "id": f.id, "rows": r}
                  for f, r in zip(files, file_rows)],
        "columns": list(columns),
        "indexed": list(resolved.indexed_columns),
        "layout": getattr(resolved, "layout", "lexicographic"),
        "lineage": bool(lineage),
        "total_rows": total,
        "batch_rows": int(batch_rows),
        "num_buckets": num_buckets,
        "groups": groups,
        "bounds": _group_bounds(num_buckets, groups),
        "chunks": _chunk_ranges(total, int(batch_rows)),
        "code_cols": _code_column_names(
            columns, resolved.indexed_columns, rel_schema, lineage),
        "max_rows_per_file": int(conf.index_max_rows_per_file),
        "compression": conf.index_file_compression,
    }


def _chunk_items(plan: Dict) -> List[str]:
    return [f"chunk-{i:05d}" for i in range(len(plan["chunks"]))]


def _group_items(plan: Dict) -> List[str]:
    return [f"group-{g:03d}" for g in range(plan["groups"])]


def _scratch(conf, build_id: str) -> str:
    return os.path.join(build_root(conf), build_id)


# -- host side: route + finalize under claims --------------------------------

def _read_global_slice(plan: Dict, start: int, end: int,
                       cache: Dict) -> "pa.Table":
    """Rows [start, end) of the global stream (files in listing order,
    rows in file order) — the multihost mirror of ``_read_chunk`` +
    ``_stream_build``'s buffering, including schema-evolution nulls and
    the constant-per-file lineage column.  ``cache`` holds the last few
    decoded files (consecutive chunks usually share a file)."""
    import numpy as np
    import pyarrow as pa

    from hyperspace_tpu.actions.create import DATA_FILE_ID_COLUMN
    from hyperspace_tpu.io.parquet import _dtype_from_string, read_table

    columns = plan["columns"]
    options = {k: v for k, v in plan["options"]}
    parts = []
    offset = 0
    for frec in plan["files"]:
        rows = frec["rows"]
        lo, hi = max(start, offset), min(end, offset + rows)
        if lo < hi:
            t = cache.get(frec["name"])
            if t is None:
                t = read_table([frec["name"]], plan["format"], columns,
                               options, partition_roots=plan["roots"])
                missing = [c for c in columns if c not in t.column_names]
                for c in missing:
                    t = t.append_column(c, pa.nulls(
                        t.num_rows, type=_dtype_from_string(
                            plan["rel_schema"].get(c, "string"))))
                if plan["lineage"]:
                    fid = np.full(t.num_rows, frec["id"], dtype=np.int64)
                    t = t.append_column(DATA_FILE_ID_COLUMN, pa.array(fid))
                while len(cache) >= 2:
                    cache.pop(next(iter(cache)))
                cache[frec["name"]] = t
            parts.append(t.slice(lo - offset, hi - lo))
        offset += rows
        if offset >= end:
            break
    return pa.concat_tables(parts, promote_options="default")


def _route_table(conf, plan: Dict, table: "pa.Table"):
    """The fused route for one chunk — the same kernels and the same
    host-mirror threshold as ``_BucketSpill._route_chunk`` (mesh-less:
    each host is one device here; ownership crosses hosts via the
    bucket-group claims, not a collective), so bucket assignment and
    tie order are bit-identical to the single-process build."""
    import numpy as np
    import pyarrow as pa

    from hyperspace_tpu.io import columnar
    from hyperspace_tpu.ops.hash import route_partition, route_partition_np

    code_cols = plan["code_cols"]
    key_cols = plan["indexed"]
    word_cols = [np.asarray(columnar.to_hash_words(table.column(c)))
                 for c in key_cols]
    codes64 = [columnar.to_order_codes64(table.column(c))
               for c in key_cols] if code_cols else []
    num_buckets = plan["num_buckets"]
    if table.num_rows < conf.device_min_rows("build"):
        buckets, perm = route_partition_np(word_cols, codes64, num_buckets)
    else:
        buckets, perm = route_partition(
            word_cols, [columnar.split_words64(k) for k in codes64],
            num_buckets, pad_to=max(1, int(conf.device_batch_rows)))
    buckets = np.asarray(buckets)
    perm = np.asarray(perm)
    sorted_buckets = buckets[perm]
    routed = table.take(pa.array(perm))
    for i, name in enumerate(code_cols):
        routed = routed.append_column(name, pa.array(codes64[i][perm]))
    starts = np.searchsorted(sorted_buckets, np.arange(num_buckets), "left")
    ends = np.searchsorted(sorted_buckets, np.arange(num_buckets), "right")
    return routed, starts, ends


def _route_one_chunk(conf, plan: Dict, scratch: str, chunk_no: int,
                     cache: Dict) -> Dict:
    """Process one ``chunk-<n>`` claim: read the slice, route it, land
    one run file per touched bucket group (temp + atomic rename — a
    crash mid-write is never visible), and return the claim result:
    which buckets each group's run holds, in batch order."""
    from hyperspace_tpu.actions.create import _write_chunk_file
    from hyperspace_tpu.io import faults

    start, end = plan["chunks"][chunk_no]
    table = _read_global_slice(plan, start, end, cache)
    routed, starts, ends = _route_table(conf, plan, table)
    spill = os.path.join(scratch, "spill")
    groups: Dict[str, List[int]] = {}
    for gid in range(plan["groups"]):
        b0, b1 = plan["bounds"][gid], plan["bounds"][gid + 1]
        present = [b for b in range(b0, b1) if ends[b] > starts[b]]
        if not present:
            continue
        path = os.path.join(spill, f"chunk-{chunk_no:05d}-g{gid:03d}.arrow")
        tmp = f"{path}.tmp-{os.getpid()}-{uuid.uuid4().hex[:6]}"
        _write_chunk_file(
            routed, tmp,
            [(int(starts[b]), int(ends[b] - starts[b])) for b in present])
        faults.atomic_replace(tmp, path, "data.write")
        groups[str(gid)] = present
    schema = {} if chunk_no else {
        name: str(t) for name, t in
        zip(table.column_names, table.schema.types)}
    result = {"rows": table.num_rows, "groups": groups}
    if schema:
        result["schema"] = schema
    return result


def _finalize_group(conf, plan: Dict, scratch: str, gid: int,
                    chunk_results: List[Dict], staged_dir: str) -> Dict:
    """Process one ``group-<g>`` claim: merge the group's runs in chunk
    order, sort each bucket (ride-along codes or the host re-derive —
    the ``_finish_group`` logic), parquet-encode into ``staged_dir``
    (holder-private), and return the staged manifest with per-file
    sha256 — what the coordinator validates before promoting."""
    import pyarrow as pa

    from hyperspace_tpu.io.parquet import (
        sort_permutation_from_codes,
        sort_permutation_host,
        write_bucket_run,
    )

    spill = os.path.join(scratch, "spill")
    b0, b1 = plan["bounds"][gid], plan["bounds"][gid + 1]
    # bucket -> [(chunk_no, path, batch_idx)] in chunk order = tie order.
    runs: Dict[int, List[Tuple[int, str, int]]] = {}
    paths = []
    for chunk_no, res in enumerate(chunk_results):
        present = res["groups"].get(str(gid))
        if not present:
            continue
        path = os.path.join(spill, f"chunk-{chunk_no:05d}-g{gid:03d}.arrow")
        paths.append(path)
        for bi, b in enumerate(present):
            runs.setdefault(b, []).append((chunk_no, path, bi))
    os.makedirs(staged_dir, exist_ok=True)
    code_cols = plan["code_cols"]
    manifest: List[Dict[str, Any]] = []
    readers = {}
    handles = []
    rows_total = 0
    try:
        for p in paths:
            mm = pa.memory_map(p, "rb")
            handles.append(mm)
            readers[p] = pa.ipc.open_file(mm)
        for b in sorted(runs):
            batches = [readers[p].get_batch(bi)
                       for _no, p, bi in sorted(runs[b])]
            btable = pa.Table.from_batches(batches)
            if code_cols:
                perm = sort_permutation_from_codes(btable, code_cols)
                btable = btable.take(pa.array(perm)).drop_columns(
                    list(code_cols))
            else:
                perm = sort_permutation_host(btable, plan["indexed"],
                                             plan["layout"])
                btable = btable.take(pa.array(perm))
            written = write_bucket_run(
                btable, b, staged_dir, plan["max_rows_per_file"],
                compression=plan["compression"])
            rows_total += btable.num_rows
            for p in written:
                manifest.append({
                    "name": os.path.basename(p),
                    "bucket": b,
                    "sha256": _sha256_file(p),
                })
    finally:
        for mm in handles:
            try:
                mm.close()
            except OSError:
                pass
    return {"dir": os.path.relpath(staged_dir, scratch),
            "files": manifest, "rows": rows_total}


def _sha256_file(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for block in iter(lambda: f.read(1 << 20), b""):
            h.update(block)
    return h.hexdigest()


def _heartbeat(conf) -> None:
    from hyperspace_tpu.telemetry import fleet

    if fleet.enabled(conf):
        fleet.publish_once(conf)


def run_host(conf, build_id: str, owner: Optional[str] = None) -> int:
    """One build host's main loop: claim-route every chunk, then
    claim-finalize every bucket group, reclaiming expired items as they
    appear.  Every expensive output is committed through the claim CAS
    — a fenced attempt deletes its own staged files and moves on.
    Returns the number of items this host completed."""
    from hyperspace_tpu.io.files import remove_tree
    from hyperspace_tpu.lifecycle.lease import WorkClaims
    from hyperspace_tpu.telemetry import fleet

    owner = owner or fleet.process_identity()
    store = _store(conf, build_id)
    plan = json.loads(store.read(PLAN_KEY).decode("utf-8"))
    scratch = _scratch(conf, build_id)
    claims = WorkClaims(
        store, conf, owner=owner,
        ttl_s=float(getattr(conf, "multihost_build_claim_ttl_s", 10.0)),
        index=plan["index"])
    poll_s = max(0.005,
                 float(getattr(conf, "multihost_build_poll_s", 0.05)))
    completed = 0
    cache: Dict[str, Any] = {}
    _heartbeat(conf)

    def drive(items, process) -> int:
        """Claim/process items until every one is done; returns how
        many THIS host completed."""
        done_here = 0
        while True:
            progress = False
            remaining = False
            for item in items:
                rec, _gen = claims.get(item)
                if rec is not None and rec.get("done"):
                    continue
                claim = claims.try_claim(item)
                if claim is None:
                    remaining = True
                    continue
                outputs = process(item, claim)
                # The margin stand-down: if our TTL ran out (or nearly
                # — store-RTT margin) while processing, renew first; a
                # lost renew means the item was reclaimed and our
                # output is the zombie's.
                committed = False
                if claims.holds(claim) or claims.renew(claim):
                    committed = claims.complete(claim, outputs["result"])
                if committed:
                    done_here += 1
                else:
                    for orphan in outputs.get("discard", ()):
                        remove_tree(orphan, ignore_errors=True)
                    remaining = True
                progress = True
                _heartbeat(conf)
            if not remaining:
                return done_here
            if not progress:
                time.sleep(poll_s)
                _heartbeat(conf)

    def route(item, claim) -> Dict:
        chunk_no = int(item.split("-")[1])
        result = _route_one_chunk(conf, plan, scratch, chunk_no, cache)
        return {"result": result}  # runs are shared + deterministic:
        # a fenced duplicate wrote identical bytes, nothing to discard

    completed += drive(_chunk_items(plan), route)
    cache.clear()
    chunk_results = [claims.result(it) for it in _chunk_items(plan)]

    def finalize(item, claim) -> Dict:
        gid = int(item.split("-")[1])
        staged = os.path.join(
            scratch, "staged",
            _safe_name(owner), f"g{gid:03d}-e{claim['epoch']:03d}")
        result = _finalize_group(conf, plan, scratch, gid, chunk_results,
                                 staged)
        return {"result": result, "discard": [staged]}

    completed += drive(_group_items(plan), finalize)
    _heartbeat(conf)
    return completed


def _safe_name(owner: str) -> str:
    return "".join(c if c.isalnum() or c in "-_." else "_" for c in owner)


def host_main() -> None:
    """Subprocess entry: spec from ``HS_MULTIHOST_SPEC`` (system path,
    build id, conf field overrides)."""
    spec = json.loads(os.environ["HS_MULTIHOST_SPEC"])
    from hyperspace_tpu.config import HyperspaceConf

    conf = HyperspaceConf()
    conf.system_path = spec["system_path"]
    for field, value in spec.get("conf", {}).items():
        setattr(conf, field, value)
    run_host(conf, spec["build_id"], owner=spec.get("owner"))


_WORKER_CONF_FIELDS = (
    "num_buckets", "device_batch_rows", "index_max_rows_per_file",
    "index_file_compression", "log_store_class",
    "object_store_stale_list_ms", "multihost_build_claim_ttl_s",
    "multihost_build_poll_s", "fleet_telemetry_enabled",
    "fleet_publish_interval_s", "lineage_enabled",
)


def require_cpu_hosts(n: int) -> None:
    """The local build hosts are child processes of this one.  A chip
    belongs to one process, and this process may already hold it, so the
    children would fail or hang on it: they run only where the caller
    pinned ``JAX_PLATFORMS=cpu`` explicitly.  Anything else is refused
    here, before a plan is written, instead of defaulting to the CPU."""
    from hyperspace_tpu.exceptions import HyperspaceError

    if os.environ.get("JAX_PLATFORMS", "").lower() != "cpu":
        raise HyperspaceError(
            f"multihost build: {n} local host processes cannot each get a "
            f"chip of their own (a chip belongs to one process, and this "
            f"one may hold it); set JAX_PLATFORMS=cpu to run the hosts on "
            f"the CPU, or run host_main() once per machine")


def spawn_hosts(conf, build_id: str, n: int) -> List[subprocess.Popen]:
    """Spawn ``n`` build-host subprocesses against one plan.  Each
    inherits the environment (the explicit ``JAX_PLATFORMS=cpu`` pin
    that :func:`require_cpu_hosts` checked) plus the spec; the
    host-vs-device route threshold is resolved HERE so every host (and
    any host that later reclaims) routes through the same path."""
    import hyperspace_tpu

    overrides = {f: getattr(conf, f) for f in _WORKER_CONF_FIELDS
                 if hasattr(conf, f)}
    overrides["device_build_min_rows"] = conf.device_min_rows("build")
    pkg_parent = os.path.dirname(
        os.path.dirname(os.path.abspath(hyperspace_tpu.__file__)))
    procs = []
    for i in range(n):
        env = dict(os.environ)
        env["HS_MULTIHOST_SPEC"] = json.dumps({
            "system_path": conf.system_path,
            "build_id": build_id,
            "conf": overrides,
            "owner": None,  # fleet.process_identity() of the subprocess
        })
        # The parent may import the package from its cwd; the child has
        # no cwd entry on sys.path, so pin the package's location.
        env["PYTHONPATH"] = pkg_parent + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
        procs.append(subprocess.Popen(
            [sys.executable, "-c",
             "from hyperspace_tpu.parallel.multihost_build import "
             "host_main; host_main()"],
            env=env))
    return procs


# -- coordinator side (runs inside the CreateAction) -------------------------

def _poll_done(claims, items) -> int:
    done = 0
    for item in items:
        rec, _gen = claims.get(item)
        if rec is not None and rec.get("done"):
            done += 1
    return done


def _claim_span(claims, items) -> float:
    """Phase wall-clock from the claim records: first acquire to last
    complete across the items' done records.  Excludes subprocess
    interpreter spin-up, so a 1-vs-2-host ratio compares the work
    itself."""
    first, last = None, None
    for item in items:
        rec, _gen = claims.get(item)
        if rec is None or not rec.get("done"):
            continue
        acq = float(rec.get("acquired_at", 0.0))
        fin = float(rec.get("completed_at", 0.0))
        if acq and (first is None or acq < first):
            first = acq
        if fin and (last is None or fin > last):
            last = fin
    if first is None or last is None:
        return 0.0
    return max(0.0, last - first)


def run_multihost_build(action, files, columns, relation, resolved,
                        lineage: bool, batch_rows: int) -> None:
    """The coordinator: plan, spawn the hosts, wait on the claim table,
    validate + promote the union, and leave the normal action commit at
    ``base_id + 2`` as the single exactly-once transaction.  Called
    from ``CreateActionBase._build_index_data`` when
    ``hyperspace.index.build.multihost.hosts >= 2``."""
    import time as _time

    from hyperspace_tpu.exceptions import HyperspaceError
    from hyperspace_tpu.io.files import remove_tree
    from hyperspace_tpu.lifecycle import journal
    from hyperspace_tpu.lifecycle.lease import WorkClaims
    from hyperspace_tpu.telemetry import fleet, metrics

    conf = action.conf
    n_hosts = int(conf.multihost_build_hosts)
    require_cpu_hosts(n_hosts)
    reap_orphan_build_dirs(conf)
    build_id = f"{_BUILD_DIR_PREFIX}{os.getpid()}-{uuid.uuid4().hex[:8]}"
    plan = make_plan(conf, build_id, action.index_name, relation, resolved,
                     files, columns, lineage, batch_rows)
    scratch = _scratch(conf, build_id)
    os.makedirs(os.path.join(scratch, "spill"), exist_ok=True)
    store = _store(conf, build_id)
    store.put_if_absent(PLAN_KEY,
                        json.dumps(plan).encode("utf-8"))
    claims = WorkClaims(
        store, conf, owner=f"coordinator-{fleet.process_identity()}",
        ttl_s=float(conf.multihost_build_claim_ttl_s),
        index=action.index_name)
    poll_s = max(0.005, float(conf.multihost_build_poll_s))
    deadline = _time.monotonic() + \
        max(1.0, float(conf.multihost_build_deadline_s))
    chunk_items, group_items = _chunk_items(plan), _group_items(plan)
    procs = spawn_hosts(conf, build_id, n_hosts)
    t_spawn = _time.perf_counter()
    route_wall = finalize_wall = 0.0
    try:
        # Phase 1 barrier: every chunk routed.  The coordinator only
        # WATCHES — claims expire and survivors reclaim on their own;
        # it fails loudly when nobody is left to make progress.
        expired_logged = set()
        for items, phase in ((chunk_items, "route"),
                             (group_items, "finalize")):
            while _poll_done(claims, items) < len(items):
                if _time.monotonic() > deadline:
                    raise HyperspaceError(
                        f"multihost build {build_id}: {phase} phase "
                        f"missed the deadline "
                        f"({conf.multihost_build_deadline_s}s) with "
                        f"{len(items) - _poll_done(claims, items)} "
                        f"items pending")
                if all(p.poll() is not None for p in procs):
                    raise HyperspaceError(
                        f"multihost build {build_id}: every host exited "
                        f"(codes {[p.returncode for p in procs]}) with "
                        f"{phase} items pending")
                # Straggler visibility: an expired, un-reclaimed claim
                # means a host died or stalled — count it for the
                # doctor's fleet check rather than hanging silently,
                # and journal each sighting ONCE per claim epoch (the
                # doctor check itself stays read-only; this record is
                # what its non-ok grades point post-mortems at).
                now = time.time()
                for item in items:
                    rec, _g = claims.get(item)
                    if rec is not None and not rec.get("done") and \
                            float(rec.get("expires_at", 0)) < now:
                        metrics.inc("build.claims.expired_seen")
                        key = (item, int(rec.get("epoch", 0)))
                        if key not in expired_logged:
                            expired_logged.add(key)
                            journal.append(conf, {
                                "decision": "claim",
                                "index": action.index_name,
                                "mode": "expired", "outcome": "observed",
                                "reason": f"{phase} claim expired "
                                          f"un-reclaimed — straggler or "
                                          f"crash; a survivor reclaims "
                                          f"after the TTL",
                                "holder": str(rec.get("holder", "")),
                                "epoch": int(rec.get("epoch", 0)),
                                "item": item,
                            })
                time.sleep(poll_s)
        # Phase wall-clock from the claim records themselves (first
        # acquire -> last complete): what the work actually took,
        # independent of the ~seconds of subprocess interpreter spin-up
        # — the number a host-scaling ratio is honest against.
        route_wall = _claim_span(claims, chunk_items)
        finalize_wall = _claim_span(claims, group_items)
        total_wall = _time.perf_counter() - t_spawn
        for p in procs:
            try:
                p.wait(timeout=30.0)
            except subprocess.TimeoutExpired:
                p.kill()  # a SIGSTOPped zombie; its claims already lost
                p.wait()
        _commit_staged(action, plan, claims, scratch, resolved)
        journal.append(conf, {
            "decision": "claim", "index": action.index_name,
            "mode": "commit", "outcome": "done",
            "reason": f"{len(group_items)} groups / {len(chunk_items)} "
                      f"chunks over {n_hosts} hosts",
            "holder": claims.owner, "epoch": 0, "item": build_id,
        })
        report = action.build_report
        report.properties.update(
            multihost_hosts=n_hosts,
            multihost_chunks=len(chunk_items),
            multihost_groups=len(group_items),
            multihost_route_wall_s=round(route_wall, 4),
            multihost_finalize_wall_s=round(finalize_wall, 4),
            multihost_total_wall_s=round(total_wall, 4))
        report.add_seconds("mh_route", route_wall)
        report.add_seconds("mh_finalize", finalize_wall)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
        remove_tree(scratch, ignore_errors=True)


def _commit_staged(action, plan: Dict, claims, scratch: str,
                   resolved) -> None:
    """Validate the union of staged manifests — every group done, every
    staged file present and hashing to its manifest, row accounting
    exact — then promote the winners into the next ``v__=N`` dir.  Any
    gap aborts BEFORE the version dir exists: the union commits or
    nothing does."""
    from hyperspace_tpu.exceptions import HyperspaceError
    from hyperspace_tpu.io import faults

    manifests = {}
    rows = 0
    for item in _group_items(plan):
        res = claims.result(item)
        if res is None:
            raise HyperspaceError(
                f"multihost build: {item} has no completed claim")
        gid = int(item.split("-")[1])
        b0, b1 = plan["bounds"][gid], plan["bounds"][gid + 1]
        for frec in res["files"]:
            if not (b0 <= frec["bucket"] < b1):
                raise HyperspaceError(
                    f"multihost build: {item} staged bucket "
                    f"{frec['bucket']} outside its range "
                    f"[{b0}, {b1})")
            staged = os.path.join(scratch, res["dir"], frec["name"])
            if not os.path.exists(staged):
                raise HyperspaceError(
                    f"multihost build: staged file missing: {staged}")
            if _sha256_file(staged) != frec["sha256"]:
                raise HyperspaceError(
                    f"multihost build: staged file {staged} does not "
                    f"match its manifest sha256")
        rows += int(res.get("rows", 0))
        manifests[item] = res
    if rows != plan["total_rows"]:
        raise HyperspaceError(
            f"multihost build: staged {rows} rows for "
            f"{plan['total_rows']} source rows — refusing to commit a "
            f"torn index")
    schema = next((claims.result(it).get("schema")
                   for it in _chunk_items(plan)
                   if claims.result(it) and claims.result(it).get("schema")),
                  None)
    version = action.data_manager.get_next_version()
    out_dir = action.data_manager.version_path(version)
    os.makedirs(out_dir, exist_ok=True)
    for item, res in manifests.items():
        for frec in res["files"]:
            src = os.path.join(scratch, res["dir"], frec["name"])
            faults.atomic_replace(
                src, os.path.join(out_dir, frec["name"]), "data.write")
    action._write_index_file_sketch(out_dir, resolved)
    action._written_version = version
    if schema:
        action._index_schema = dict(schema)


# -- doctor seam -------------------------------------------------------------

def scan_build_claims(conf) -> List[Dict[str, Any]]:
    """Every pending (not done) claim record across every build scratch
    dir under this tree, each annotated with its build id — what
    ``telemetry/fleet._check_build_claims`` grades against the fleet
    heartbeats.  Never raises."""
    from hyperspace_tpu.lifecycle.lease import WorkClaims, _parse

    out: List[Dict[str, Any]] = []
    root = build_root(conf)
    try:
        builds = sorted(os.listdir(root))
    except OSError:
        return out
    for build_id in builds:
        if not build_id.startswith(_BUILD_DIR_PREFIX):
            continue
        try:
            store = _store(conf, build_id)
            for key in store.list_keys():
                if not key.startswith(WorkClaims.PREFIX):
                    continue
                payload, _gen = store.read_with_generation(key)
                rec = _parse(payload)
                if rec is None or rec.get("done"):
                    continue
                rec = dict(rec)
                rec["build_id"] = build_id
                out.append(rec)
        except Exception:  # noqa: BLE001 — a flaky store reads as empty
            continue
    return out
