"""CreateAction: validate config/plan, build the covering index on device,
commit the log entry.

Reference contract: actions/CreateAction.scala:30-90 (validate :45-66 —
supported relation, resolvable columns, free name) and
actions/CreateActionBase.scala:56-222 —
  - ``write``: select columns → repartition(numBuckets, indexedCols) →
    saveWithBuckets (:124-142).  Here that whole pipeline is the fused TPU
    kernel ``bucket_sort_permutation`` (hash + lexsort on device) plus a
    host-side bucketed Parquet writer — no cluster shuffle exists because
    the permutation materializes the shuffle's effect directly.
  - lineage (:177-222): the reference joins ``input_file_name()`` against a
    broadcast file→id map; we attach ``_data_file_id`` per file at read
    time — same result, no join needed, because the engine owns the reader.
  - ``getIndexLogEntry`` (:56-105): signature of the source plan, content
    tree of the written files, provider-enriched properties.
"""

from __future__ import annotations

import contextlib
import os
import threading
from typing import Dict, List, Optional

import numpy as np
import pyarrow as pa

from hyperspace_tpu.actions.base import Action
from hyperspace_tpu.config import HyperspaceConf
from hyperspace_tpu.exceptions import HyperspaceError
from hyperspace_tpu.index.data_manager import IndexDataManager
from hyperspace_tpu.index.index_config import IndexConfig
from hyperspace_tpu.index.log_entry import (
    Content,
    CoveringIndex,
    FileIdTracker,
    IndexLogEntry,
    LogicalPlanFingerprint,
    Signature,
    Source,
    States,
)
from hyperspace_tpu.index.log_manager import IndexLogManager
from hyperspace_tpu.index.signatures import get_provider
from hyperspace_tpu.io import columnar
from hyperspace_tpu.io.parquet import read_table, write_bucketed
from hyperspace_tpu.plan.nodes import LogicalPlan
from hyperspace_tpu.telemetry.events import CreateActionEvent
from hyperspace_tpu.telemetry.trace import span
from hyperspace_tpu.utils.resolver import resolve_or_raise

DATA_FILE_ID_COLUMN = "_data_file_id"  # IndexConstants.scala lineage column

# Spill temp-dir prefixes (hash spill / zorder two-pass).  Dirs are
# pid-stamped so a later build can prove an orphan's owner is dead before
# reaping it — a SIGKILLed build runs no cleanup handler, and these dirs
# hold a routed copy of the whole source.
_SPILL_DIR_KINDS = ("hs_build_spill_", "hs_zbuild_")


def _spill_dir_prefix(kind: str) -> str:
    return f"{kind}{os.getpid()}_"


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except OSError:
        pass  # EPERM etc.: the pid exists, someone else owns it
    return True


def reap_orphan_spill_dirs(tmp_root: Optional[str] = None) -> int:
    """Best-effort removal of spill dirs leaked by DEAD processes
    (SIGKILL or an injected crash mid-build), run at build start.  Only
    pid-stamped dirs whose owning pid provably no longer exists are
    touched; deletion goes through ``io/files.remove_tree`` so the
    ``io.delete`` fault site applies.  Returns the number reaped."""
    import tempfile

    from hyperspace_tpu.io.files import remove_tree

    root = tmp_root or tempfile.gettempdir()
    try:
        names = os.listdir(root)
    except OSError:
        return 0
    reaped = 0
    for name in names:
        kind = next((k for k in _SPILL_DIR_KINDS if name.startswith(k)),
                    None)
        if kind is None:
            continue
        pid_part = name[len(kind):].split("_", 1)[0]
        if not pid_part.isdigit():
            continue  # pre-pid-stamp dir: ownership unprovable, leave it
        pid = int(pid_part)
        if pid == os.getpid() or _pid_alive(pid):
            continue
        try:
            remove_tree(os.path.join(root, name), ignore_errors=True)
            reaped += 1
        except OSError:
            pass  # best-effort: a flaky mount must not fail THIS build
    return reaped


class _PrefetchReader:
    """Bounded decode-ahead over a source file list.

    ONE reader thread decodes file N+1 while the consumer routes file N
    (double-buffered at ``depth=2``, the conf default); ``depth`` bounds
    decoded-but-unconsumed chunks — the backpressure that keeps peak RSS
    at ~depth device batches instead of the dataset.  ``depth=0`` reads
    inline on the consumer thread: the forced-serial reference path
    (``hyperspace.index.build.pipeline.enabled=false``) and the
    no-thread degrade.  Deadline-aware (each handoff re-checks the
    request deadline) and drain-aware: ``close()`` cancels queued decode
    work and joins the reader, so a failed build never races its own
    prefetcher — the action's cleanup ``finally`` covers it."""

    def __init__(self, action: "CreateActionBase", files, columns,
                 relation, lineage, depth: int, spill=None) -> None:
        self.action = action
        self.files = list(files)
        self.columns = columns
        self.relation = relation
        self.lineage = lineage
        self.depth = max(0, int(depth))
        self.spill = spill
        self.peak_chunks = 0  # max decoded-unconsumed chunks observed
        self._stall_buffer_s = 0.0
        self._pool = None
        self._pending: List = []

    def _record_stall(self, seconds: float) -> None:
        """Count consumer stall in the ``prefetch`` phase — but only once
        the build is known to SPILL.  On a monolithic build the consumer
        has nothing to overlap, so its wait and the reader thread's
        ``read`` cover the same wall time; counting both would break the
        phase-sum-within-10%-of-wall audit.  Pre-spill stalls buffer and
        are counted with the first post-spill one (each wait has its own
        span and timeline interval either way)."""
        if self.spill is None or not self.spill.spilled:
            self._stall_buffer_s += seconds
            return
        self.action.build_report.add_seconds(
            "prefetch", self._stall_buffer_s + seconds)
        self._stall_buffer_s = 0.0

    def __iter__(self):
        from hyperspace_tpu.utils import deadline

        if self.depth == 0:
            for f in self.files:
                deadline.check()
                yield self.action._read_chunk(f, self.columns,
                                              self.relation, self.lineage)
            return
        from concurrent.futures import ThreadPoolExecutor

        self._pool = ThreadPoolExecutor(max_workers=1,
                                        thread_name_prefix="hs-prefetch")
        queue = list(self.files)
        try:
            while queue and len(self._pending) < self.depth:
                self._pending.append(self._pool.submit(
                    self.action._read_chunk, queue.pop(0), self.columns,
                    self.relation, self.lineage))
            while self._pending:
                deadline.check()
                ready = sum(1 for f in self._pending if f.done())
                if ready > self.peak_chunks:
                    self.peak_chunks = ready
                fut = self._pending.pop(0)
                # Stall attribution: time the CONSUMER spends waiting on
                # decode is the pipeline bubble prefetch exists to close
                # (the ``prefetch`` phase/lane; near zero when it wins).
                with self.action.build_report.phase(
                        "prefetch", count=False) as wait:
                    t = fut.result()
                self._record_stall(wait.seconds)
                if queue:
                    self._pending.append(self._pool.submit(
                        self.action._read_chunk, queue.pop(0),
                        self.columns, self.relation, self.lineage))
                yield t
            # A build that spilled only late in the stream still owns
            # its earlier (buffered) stalls.
            if self.spill is not None and self.spill.spilled \
                    and self._stall_buffer_s:
                self._record_stall(0.0)
        finally:
            self.close()

    def close(self) -> None:
        futures, self._pending = self._pending, []
        for fut in futures:
            fut.cancel()
        if self._pool is not None:
            self._pool.shutdown(wait=True)
            self._pool = None


class CreateActionBase(Action):
    """Shared by Create and the data-rebuilding Refresh actions."""

    def __init__(self, log_manager: IndexLogManager, data_manager: IndexDataManager,
                 session, plan: LogicalPlan, config: IndexConfig) -> None:
        super().__init__(log_manager)
        self.data_manager = data_manager
        self.session = session
        self.plan = plan
        self.config = config
        self._written_version: Optional[int] = None
        self._file_id_tracker = FileIdTracker()
        self._relation_cache = None

    @property
    def conf(self) -> HyperspaceConf:
        return self.session.conf

    @property
    def index_name(self) -> str:
        return self.config.index_name

    @property
    def num_buckets(self) -> int:
        # Z-order clusters via Morton-ordered file cuts, and hash
        # bucketing would scatter that clustering across buckets (a file
        # per bucket sees near-uniform value ranges on every dimension —
        # no pruning).  One bucket makes the whole index a single Z-curve
        # run; file granularity comes from index_max_rows_per_file.
        if getattr(self.config, "layout", None) == "zorder":
            return 1
        return self.conf.num_buckets

    @property
    def lineage_enabled(self) -> bool:
        # Refresh actions override both properties to pin the previous
        # entry's values (RefreshActionBase.scala:56-64).
        return self.conf.lineage_enabled

    def _relation(self):
        # Cached for the action's lifetime: the plan is fixed, and the
        # relation's file listing must not be re-walked per accessor call.
        if self._relation_cache is None:
            leaves = self.plan.leaf_relations()
            if len(leaves) != 1:
                # CreateAction.scala:52-58: exactly one supported relation.
                raise HyperspaceError(
                    f"Only plans over exactly one relation are supported for "
                    f"indexing; found {len(leaves)}")
            self._relation_cache = \
                self.session.source_provider_manager.get_relation(leaves[0])
        return self._relation_cache

    def _resolved_config(self) -> IndexConfig:
        """Resolve config columns against the relation schema
        (CreateActionBase.resolveConfig:155-175)."""
        schema = self._relation().schema()
        indexed = resolve_or_raise(self.config.indexed_columns, schema, "indexed column")
        included = resolve_or_raise(self.config.included_columns, schema, "included column")
        return IndexConfig(self.config.index_name, indexed, included,
                           layout=getattr(self.config, "layout", "lexicographic"))

    # -- the build (CreateActionBase.write:124-142, TPU-style) --------------
    def _build_index_data(self, file_names: Optional[List[str]] = None) -> None:
        """Read source columns, run the fused hash+sort kernel, write one
        sorted Parquet file per bucket into the next ``v__=N`` directory.

        Datasets bigger than one device batch take the EXTERNAL build
        (SURVEY §7's "sort at SF100 exceeds HBM" hard part): source files
        stream through the hash kernel one batch at a time, rows spill into
        per-bucket run files, and each bucket is then sorted independently —
        peak memory is bounded by max(batch, largest bucket), not the
        dataset."""
        from hyperspace_tpu.io import integrity

        # Build planning: conf application, source listing, column
        # resolution, and the backend probe (_use_distributed_build's
        # first jax.devices() call initializes the backend — a one-off
        # cost that must not hide between phases).
        with self.build_report.phase("plan"):
            # Spill dirs a SIGKILLed prior process leaked are reaped here —
            # the one moment a build provably needs the temp space back.
            reap_orphan_spill_dirs()
            # Digest-on-write follows THIS session's conf (the recorder is
            # process-global, like the fault injector).
            integrity.configure_from_conf(self.conf)
            relation = self._relation()
            resolved = self._resolved_config()
            lineage = self.lineage_enabled
            files = relation.all_files(self._file_id_tracker)
            if file_names is not None:
                wanted = set(file_names)
                files = [f for f in files if f.name in wanted]
            if not files:
                raise HyperspaceError("No source data files to index")

            columns = resolved.all_columns
            batch_rows = max(1, int(self.conf.device_batch_rows))
            # Datasets beyond one batch stream through the spill build —
            # whose per-chunk route shards over the mesh when one is active
            # (bounded memory AND horizontal scale; parallel/sharded_build).
            # Only an EXPLICIT parallel_build="on" keeps the legacy
            # monolithic all_to_all build, which holds the whole dataset in
            # memory (bit-equal either way — layout never depends on the
            # route).
            streaming = not (
                str(self.conf.parallel_build).lower() in ("on", "true")
                and self._use_distributed_build())
        from hyperspace_tpu.parallel import multihost_build
        if multihost_build.armed(self.conf):
            # Fault-tolerant multi-host build: N subprocess hosts route
            # and finalize under crash-recoverable work claims; this
            # action coordinates, validates the staged union, and keeps
            # the ordinary base_id+2 commit as the single transaction.
            multihost_build.run_multihost_build(
                self, files, columns, relation, resolved, lineage,
                batch_rows)
            return
        if streaming and resolved.layout == "zorder":
            # Z-order builds beyond one batch take a dedicated two-pass
            # path that preserves the GLOBAL layout (hash-partition
            # spilling would fragment the curve into partition-local
            # samples and gut second-dimension pruning).
            self._zorder_streaming_build(files, columns, relation, lineage,
                                         resolved, batch_rows)
            return
        spill = _BucketSpill(self, resolved)
        try:
            self._stream_build(files, columns, relation, lineage, resolved,
                               batch_rows, streaming, spill)
        finally:
            # A FINALLY, not an except: it must join + shut down the
            # route/finalize worker pools and remove the spill dir on
            # every exit — InjectedCrash (a BaseException) included,
            # since a leaked pool thread would outlive the simulated
            # kill.  After a clean finish() this is a no-op.  Only a
            # real SIGKILL escapes it, which is what the orphan reap
            # above exists for.
            spill.cleanup()

    def _read_chunk(self, f, columns, relation, lineage) -> pa.Table:
        """One source file's rows with schema-evolution normalization (a
        file predating an added column yields nulls of the relation's
        type, like the monolithic concat's promotion) and, when enabled,
        the constant-per-file lineage column
        (CreateActionBase.scala:177-222 without the broadcast join)."""
        with self.build_report.phase("read"):
            t = read_table([f.name], relation.read_format, columns,
                           relation.options,
                           partition_roots=relation.root_paths)
        self.build_report.add_bytes(read=t.nbytes)
        missing = [col_name for col_name in columns
                   if col_name not in t.column_names]
        if missing:
            from hyperspace_tpu.io.parquet import _dtype_from_string

            rel_schema = relation.schema()
            for col_name in missing:
                t = t.append_column(col_name, pa.nulls(
                    t.num_rows,
                    type=_dtype_from_string(
                        rel_schema.get(col_name, "string"))))
        if lineage:
            fid = np.full(t.num_rows, f.id, dtype=np.int64)
            t = t.append_column(DATA_FILE_ID_COLUMN, pa.array(fid))
        return t

    def _stream_build(self, files, columns, relation, lineage, resolved,
                      batch_rows, streaming, spill) -> None:
        # The overlapped build pipeline: source decode is prefetched
        # ahead on the reader thread (bounded by
        # hyperspace.index.build.prefetchDepth — the backpressure that
        # keeps peak RSS at ~depth device batches), chunk ROUTING runs
        # on the spill's worker pool when cores allow, and closed bucket
        # groups finalize on their own pool while the tail of the input
        # still routes.  pipeline.enabled=false degrades to the
        # bit-equal forced-serial loop: inline reads, inline routing,
        # sequential finalize (layout NEVER depends on the flag — the
        # pipeline changes scheduling only).
        depth = max(1, int(self.conf.build_prefetch_depth)) \
            if spill.pipelined else 0
        reader = _PrefetchReader(self, files, columns, relation, lineage,
                                 depth, spill=spill)
        buffer: List[pa.Table] = []
        buffered = 0
        try:
            for t in reader:
                buffer.append(t)
                buffered += t.num_rows
                while streaming and buffered > batch_rows:
                    combined = pa.concat_tables(buffer,
                                                promote_options="default")
                    spill.add_chunk(combined.slice(0, batch_rows))
                    rest = combined.slice(batch_rows)
                    buffer = [rest] if rest.num_rows else []
                    buffered = rest.num_rows
        finally:
            reader.close()
        if depth:
            self.build_report.properties.update(
                prefetch_depth=depth,
                prefetch_peak_chunks=reader.peak_chunks)
        remainder = pa.concat_tables(buffer, promote_options="default") \
            if buffer else None
        if not spill.spilled:
            # Everything fit in one batch (or the mesh owns the sharding):
            # the fused monolithic/distributed kernel.
            self._write_table_bucketed(remainder, resolved)
            return
        if remainder is not None and remainder.num_rows:
            spill.add_chunk(remainder)
        spill.finish()

    def _zorder_streaming_build(self, files, columns, relation, lineage,
                                resolved, batch_rows) -> None:
        """Two-pass Z-order build for datasets beyond one device batch,
        producing EXACTLY the monolithic layout:

          A. stream only the INDEXED columns (column-pruned reads).
             Value-mapped types (numeric/temporal/bool — their order words
             are chunk-independent) convert to fixed-width words
             immediately (8 B/row/column); rank-mapped types
             (strings/binary/decimal) must keep the raw column until one
             GLOBAL rank pass — a chunk-local dense rank would not be
             comparable across chunks and the curve would silently
             interleave.  Then compute global Morton
             codes, argsort, and the Z-cell-aligned output-file
             assignment per row;
          B. stream the full rows again, routing each chunk's rows to
             per-output-file run files (codes ride along as a temp
             column); then per output file: concat runs in chunk order,
             stable-sort by code (ties keep original row order, same as
             the monolithic argsort), and write.

        The previous hash-partition spill bounded memory the same way but
        fragmented the curve into partition-local rank samples — per-file
        min/max spanned whole dimensions and second-dimension pruning
        collapsed at scale (measured 50/108 files kept at SF1 for a 5%
        range vs ~1/8 expected)."""
        import tempfile

        import pyarrow.parquet as pq

        from hyperspace_tpu.io import columnar as _columnar
        from hyperspace_tpu.io.files import remove_tree
        from hyperspace_tpu.io.parquet import (
            write_bucket_run,
            zorder_codes_from_order_words,
            zorder_split_chunks,
        )

        key_cols = list(resolved.indexed_columns)

        def build_monolithic() -> None:
            table = pa.concat_tables(
                [self._read_chunk(f, columns, relation, lineage)
                 for f in files], promote_options="default")
            self._write_table_bucketed(table, resolved)

        # Small datasets skip the two-pass machinery entirely when footers
        # can prove the total fits one batch (parquet only; other formats
        # fall through and pay one extra key-column read).
        footer_n = _footer_row_count(files, relation)
        if footer_n is not None and footer_n <= batch_rows:
            build_monolithic()
            return
        # -- pass A: global codes from the indexed columns only ------------
        word_parts: List[List] = [[] for _ in key_cols]
        value_mapped: List[Optional[bool]] = [None] * len(key_cols)
        n = 0
        for f in files:
            kt = self._read_chunk(f, key_cols, relation, lineage=False)
            n += kt.num_rows
            for i, c in enumerate(key_cols):
                arr = kt.column(c)
                if value_mapped[i] is None:
                    value_mapped[i] = columnar.is_numeric_type(
                        kt.schema.field(c).type)
                if value_mapped[i]:
                    word_parts[i].append(
                        np.asarray(_columnar.to_order_words(arr)))
                else:
                    # Rank-mapped type: keep the raw chunks for ONE global
                    # rank pass below.
                    word_parts[i].extend(arr.chunks)
        if n <= batch_rows:
            # Non-parquet source that turned out small: monolithic writer
            # (identical layout, no run files).
            build_monolithic()
            return
        with self.build_report.phase("kernel"):
            per_col_words = []
            for i in range(len(key_cols)):
                if value_mapped[i]:
                    per_col_words.append(
                        np.concatenate(word_parts[i], axis=0))
                else:
                    per_col_words.append(np.asarray(
                        _columnar.to_order_words(
                            pa.chunked_array(word_parts[i]))))
            codes, bits = zorder_codes_from_order_words(per_col_words)
            del word_parts, per_col_words
            order = np.argsort(codes, kind="stable")
            chunks = zorder_split_chunks(codes[order], bits,
                                         self.conf.index_max_rows_per_file)
            file_of_sorted = np.empty(n, np.int32)
            for i, (off, rows) in enumerate(chunks):
                file_of_sorted[off:off + rows] = i
            file_of_row = np.empty(n, np.int32)
            file_of_row[order] = file_of_sorted
            del order, file_of_sorted

        # -- pass B: route full rows to per-output-file runs --------------
        # The routing code rides along as a temp column whose name cannot
        # collide with any indexed/included/lineage column.
        z_col = "__z"
        taken_names = set(columns) | {DATA_FILE_ID_COLUMN}
        while z_col in taken_names:
            z_col += "_"
        run_dir = tempfile.mkdtemp(prefix=_spill_dir_prefix("hs_zbuild_"))
        schema = None
        try:
            offset = 0
            for chunk_no, f in enumerate(files):
                t = self._read_chunk(f, columns, relation, lineage)
                if schema is None:
                    schema = t.schema
                with self.build_report.phase("spill_route"):
                    rows = t.num_rows
                    if offset + rows > n:
                        raise HyperspaceError(
                            "Source grew between Z-order build passes; retry")
                    fids = file_of_row[offset:offset + rows]
                    t = t.append_column(
                        z_col, pa.array(codes[offset:offset + rows]))
                    offset += rows
                    o = np.argsort(fids, kind="stable")
                    sf = fids[o]
                    routed = t.take(pa.array(o))
                    uniq = np.unique(sf)
                    starts = np.searchsorted(sf, uniq, "left")
                    ends = np.searchsorted(sf, uniq, "right")
                    for fid, st, en in zip(uniq, starts, ends):
                        d = os.path.join(run_dir, f"file={int(fid):06d}")
                        os.makedirs(d, exist_ok=True)
                        self.build_report.add_bytes(spill=_write_run(
                            routed.slice(int(st), int(en - st)),
                            os.path.join(d, f"run-{chunk_no:05d}.arrow")),
                            spill_runs=1)
            if offset != n:
                raise HyperspaceError(
                    "Source shrank between Z-order build passes; retry")

            with self.build_report.phase("spill_finish"):
                version = self.data_manager.get_next_version()
                out_dir = self.data_manager.version_path(version)
                os.makedirs(out_dir, exist_ok=True)

                def finish_file(dname: str) -> None:
                    d = os.path.join(run_dir, dname)
                    runs = sorted(os.listdir(d))  # chunk order = ties
                    bt = pa.concat_tables(
                        [_read_run(os.path.join(d, r)) for r in runs],
                        promote_options="default")
                    z = np.asarray(bt.column(z_col).to_numpy(
                        zero_copy_only=False))
                    perm = np.argsort(z, kind="stable")
                    bt = bt.take(pa.array(perm)).drop_columns([z_col])
                    # One output file per pass-A chunk (already
                    # cell-aligned and capped), written as bucket 0 — the
                    # logical index has one bucket.
                    written = write_bucket_run(
                        bt, 0, out_dir, 0,
                        compression=self.conf.index_file_compression)
                    self.build_report.add_bytes(
                        written=sum(os.path.getsize(p) for p in written),
                        files=len(written))
                    remove_tree(d, ignore_errors=True)  # runs consumed

                from hyperspace_tpu.utils.parallel_map import (
                    parallel_map_ordered,
                )

                parallel_map_ordered(finish_file,
                                     sorted(os.listdir(run_dir)),
                                     max_workers=4)
        finally:
            remove_tree(run_dir, ignore_errors=True)
        with self.build_report.phase("sketch"):
            self._write_index_file_sketch(out_dir, resolved)
        self._written_version = version
        self._index_schema = {name: str(t) for name, t in
                              zip(schema.names, schema.types)}

    def _use_distributed_build(self) -> bool:
        import jax

        mode = str(self.conf.parallel_build).lower()
        if mode in ("on", "true"):
            return True
        if mode in ("off", "false"):
            return False
        if mode != "auto":
            raise HyperspaceError(
                f"Invalid {self.conf.parallel_build!r} for parallel_build; "
                f"expected 'auto', 'on', or 'off'")
        return len(jax.devices()) > 1

    def _write_table_bucketed(self, table: pa.Table, resolved: IndexConfig,
                              version: Optional[int] = None) -> None:
        # Z-order: Morton codes are computed ONCE on host (global dense
        # ranks need a global pass, and the codes double as the writer's
        # split keys — files cut at Z-cell boundaries,
        # io/parquet.zorder_split_chunks, so every file's per-dimension
        # min/max stays narrow).  The permutation is simply their argsort:
        # there is no device shuffle to do for a one-bucket index, and a
        # hash shuffle would fragment the curve into per-partition samples,
        # gutting the pruning — so every build mode takes this path and
        # produces the identical, environment-independent layout.
        with self.build_report.phase("kernel"):
            split_keys, split_bits = (None, 0)
            if resolved.layout == "zorder":
                from hyperspace_tpu.io.parquet import zorder_codes_host

                split_keys, split_bits = zorder_codes_host(
                    table, resolved.indexed_columns)
                perm = np.argsort(split_keys, kind="stable")
                buckets = np.zeros(table.num_rows, dtype=np.int32)
            elif self._use_distributed_build():
                from hyperspace_tpu.parallel import (
                    build_mesh,
                    distributed_bucket_sort_permutation,
                )

                buckets, perm = distributed_bucket_sort_permutation(
                    table, resolved.indexed_columns, self.num_buckets,
                    build_mesh(), slack=self.conf.shuffle_capacity_slack,
                    pad_to=self.conf.device_batch_rows)
            else:
                from hyperspace_tpu.ops.sort import (
                    bucket_sort_permutation,
                    bucket_sort_permutation_np,
                )

                word_cols = [columnar.to_hash_words(table.column(c))
                             for c in resolved.indexed_columns]
                order_words = [
                    np.asarray(columnar.to_order_words(table.column(c)))
                    for c in resolved.indexed_columns]
                if table.num_rows < self.conf.device_min_rows("build"):
                    # Host mirror below the threshold — identical layout, no
                    # device transfer/compile latency (see config).
                    buckets, perm = bucket_sort_permutation_np(
                        [np.asarray(w) for w in word_cols], order_words,
                        self.num_buckets)
                else:
                    buckets, perm = bucket_sort_permutation(
                        [np.asarray(w) for w in word_cols],
                        order_words,
                        self.num_buckets,
                        pad_to=self.conf.device_batch_rows)
        version = self.data_manager.get_next_version() if version is None else version
        out_dir = self.data_manager.version_path(version)
        with self.build_report.phase("write"):
            written = write_bucketed(
                table, np.asarray(buckets), np.asarray(perm),
                self.num_buckets, out_dir,
                max_rows_per_file=self.conf.index_max_rows_per_file,
                split_keys=split_keys, split_key_bits=split_bits,
                compression=self.conf.index_file_compression)
        self.build_report.add_bytes(
            written=sum(os.path.getsize(p) for p in written),
            files=len(written))
        with self.build_report.phase("sketch"):
            self._write_index_file_sketch(out_dir, resolved)
        self._written_version = version
        self._index_schema = {name: str(t) for name, t in
                              zip(table.column_names, table.schema.types)}

    def _write_index_file_sketch(self, out_dir: str,
                                 resolved: IndexConfig) -> None:
        """Per-index-file min/max over the indexed columns, written as
        ``_sketch.parquet`` next to the bucket files (underscore-prefixed =
        excluded from data-file listings).  Read from footers — O(footer).
        FilterIndexRule uses it to prune index FILES for range predicates;
        with the Z-order layout every indexed dimension's ranges are narrow
        so the pruning bites on all of them."""
        from hyperspace_tpu.actions.data_skipping import write_index_file_sketch

        write_index_file_sketch(out_dir, resolved.indexed_columns)

    # -- log entry (CreateActionBase.getIndexLogEntry:56-105) ---------------
    def _signature(self) -> Signature:
        provider_name = self.conf.signature_provider
        provider = get_provider(provider_name)
        value = provider.signature(
            self.plan,
            lambda scan: self.session.source_provider_manager
            .get_relation(scan).all_files())
        if value is None:
            raise HyperspaceError("Could not compute plan signature")
        return Signature(provider_name, value)

    def _build_log_entry(self) -> IndexLogEntry:
        relation = self._relation()
        resolved = self._resolved_config()
        rel_meta = relation.create_relation_metadata(self._file_id_tracker)
        # Refresh actions carry forward the previous entry's properties so
        # provider-accumulated state (e.g. the deltaVersions history) survives
        # (CreateActionBase.scala:56-105 + DeltaLakeFileBasedSource enrich).
        prev = getattr(self, "_previous_entry", None)
        properties: Dict[str, str] = dict(prev.properties) if prev is not None else {}
        properties["lineage"] = str(self.lineage_enabled).lower()
        # The log version this entry will commit at (Action end() writes at
        # base_id + 2) — providers record it in their version histories.
        properties["indexLogVersion"] = str(self.base_id + 2)
        properties = self.session.source_provider_manager.enrich_index_properties(
            rel_meta, properties)
        content = Content.from_directory(
            self.data_manager.version_path(self._written_version), FileIdTracker())
        return IndexLogEntry(
            name=self.config.index_name,
            derived_dataset=CoveringIndex(
                indexed_columns=resolved.indexed_columns,
                included_columns=resolved.included_columns,
                num_buckets=self.num_buckets,
                schema=getattr(self, "_index_schema", {}),
                properties={"layout": resolved.layout},
            ),
            content=content,
            source=Source(relations=[rel_meta],
                          fingerprint=LogicalPlanFingerprint([self._signature()])),
            properties=properties,
        )


def _write_chunk_file(routed: pa.Table, path: str, slices) -> int:
    """One (chunk, bucket group) spill file as raw Arrow IPC: one record
    batch per ``(offset, rows)`` slice — bucket-aligned, so finalize
    reads any bucket's run by batch index from a memory map without
    touching the rest.  ``combine_chunks`` pins each slice to ONE chunk
    = ONE batch, keeping batch index == slice position.  Returns the
    bytes landed (the build report's spill accounting)."""
    with pa.OSFile(path, "wb") as sink:
        with pa.ipc.new_file(sink, routed.schema) as writer:
            for off, rows in slices:
                writer.write_table(
                    routed.slice(off, rows).combine_chunks())
    return os.path.getsize(path)


def _write_run(table: pa.Table, path: str) -> int:
    """Temporary spill run file as RAW Arrow IPC: no parquet
    encode/decode for data that is read back exactly once and deleted —
    on a single-core host the encode was most of the spill cost.
    Returns the bytes landed (the build report's spill accounting)."""
    with pa.OSFile(path, "wb") as sink:
        with pa.ipc.new_file(sink, table.schema) as writer:
            writer.write_table(table)
    return os.path.getsize(path)


def _read_run(path: str) -> pa.Table:
    with pa.memory_map(path, "rb") as source:
        return pa.ipc.open_file(source).read_all()


def _footer_row_count(files, relation) -> Optional[int]:
    """Total rows from parquet footers (no decode), or None when any file
    is non-parquet/unreadable — a cheap 'does it fit one batch' probe."""
    import pyarrow.parquet as pq

    if relation.read_format != "parquet":
        return None
    total = 0
    for f in files:
        try:
            total += pq.read_metadata(f.name).num_rows
        except Exception:
            return None
    return total


class _BucketSpill:
    """External-build spill state: per-chunk fused route+partition into
    bucket-aligned Arrow runs, then streaming per-bucket-group finalize.

    Phase 1 (route) runs the SAME fused hash+lexsort program as the
    monolithic build (ops/hash._route_sort_impl — one compiled program,
    every chunk), so bucket assignment and tie order can never diverge
    between build sizes.  The chunk's rows land GROUPED BY BUCKET — and,
    for value-mapped key types, already SORTED within bucket, with the
    monotone uint64 sort codes riding along as temp columns — in ONE
    Arrow IPC file per (chunk, bucket group), one record batch per
    non-empty bucket.  That file layout is the sf10 lever: the old
    per-(chunk, bucket) run files meant chunks × buckets tiny-file
    creates/opens/unlinks (11,400 at sf10), all syscall overhead.

    Phase 2 (finalize) closes bucket GROUPS the moment routing drains
    and merges + parquet-encodes them on a dedicated worker pool,
    CONCURRENT with the tail of routing and with each other.  Pre-sorted
    runs make the merge a lexsort over the ride-along codes instead of
    re-deriving order words for every row; batches read back zero-copy
    from a memory map, and each group's chunk files are deleted the
    moment the group is consumed, so peak disk stays source + runs + a
    few in-flight groups (matters at SF100).  Runs concatenate in chunk
    order, so the stable merge reproduces the monolithic tie order
    exactly.

    ``hyperspace.index.build.pipeline.enabled=false`` forces the serial
    reference: inline reads, inline routing, sequential group finalize —
    the same functions in the same order, so the flag changes SCHEDULING
    only and the output stays bit-equal (tests/test_build_pipeline.py
    holds it to that)."""

    # Route workers: chunk routing (fused kernel + run write) is
    # independent per chunk once its number is assigned, so on
    # multi-core hosts chunks route concurrently while the stream loop
    # keeps decoding.  Single-core hosts degrade to inline routing (a
    # pool of GIL-sharing workers would only add overhead there).
    _MAX_ROUTE_WORKERS = 4
    _MAX_IN_FLIGHT = 3  # each in-flight chunk pins one device batch in RAM
    _MAX_GROUPS = 8     # bucket groups = spill-file + finalize granularity

    def __init__(self, action: "CreateActionBase", resolved: IndexConfig) -> None:
        self.action = action
        self.resolved = resolved
        self.spilled = False
        self.pipelined = bool(getattr(action.conf,
                                      "build_pipeline_enabled", True))
        self._num_buckets = action.num_buckets
        self._groups = min(self._MAX_GROUPS, self._num_buckets)
        # Contiguous bucket ranges per group: group of bucket b is the
        # gid with _bounds[gid] <= b < _bounds[gid + 1] — contiguous in
        # the chunk's sorted order, so a group's rows are one slice.
        # The cuts are the shared ownership contract
        # (parallel/sharded_build.bucket_group_bounds): the multi-host
        # build claims the SAME ranges cross-host.
        from hyperspace_tpu.parallel.sharded_build import bucket_group_bounds

        self._bounds = bucket_group_bounds(self._num_buckets, self._groups)
        self._chunk_no = 0
        self._schema = None
        self._code_cols: tuple = ()
        self._mesh = None       # resolved lazily at first route
        self._mesh_probed = False
        self._dir = None  # created on first spill; non-spilling builds
        # never touch disk
        self._pool = None
        self._futures: List = []
        # Run manifest: bucket -> [(chunk_no, path, batch_index)], plus
        # per-group chunk-file lists for consumed-group deletion.  Route
        # workers append concurrently.
        self._manifest_lock = threading.Lock()
        self._runs: Dict[int, List] = {}
        self._group_files: Dict[int, List[str]] = {}
        # Streaming-finalize state: groups close when the LAST route job
        # lands after end-of-input — possibly on a route worker thread,
        # while finish() is still joining earlier futures.
        self._close_lock = threading.Lock()
        self._routes_pending = 0
        self._input_done = False
        self._closed = False
        self._route_failed = False
        self._finalize_pool = None
        self._finalize_futures: List = []
        self._out_dir: Optional[str] = None

    def _route_pool(self):
        import os as _os

        if not self.pipelined:
            return None  # forced-serial reference: inline routing
        if self._pool is None and (_os.cpu_count() or 1) > 1:
            from concurrent.futures import ThreadPoolExecutor

            self._pool = ThreadPoolExecutor(
                max_workers=min(self._MAX_ROUTE_WORKERS,
                                _os.cpu_count() or 1),
                thread_name_prefix="hs-route")
        return self._pool

    def _drain(self) -> None:
        """Wait for in-flight route jobs; re-raise the first failure."""
        futures, self._futures = self._futures, []
        for fut in futures:
            fut.result()

    def _drain_finalize(self) -> None:
        """Wait for in-flight group-finalize jobs; re-raise the first
        failure."""
        futures, self._finalize_futures = self._finalize_futures, []
        for fut in futures:
            fut.result()

    def cleanup(self) -> None:
        try:
            self._drain()
        # cleanup() on the failure path re-raises the ORIGINAL error
        # right after, so a secondary drain failure is discarded.
        # hslint: allow[exception-discipline] secondary failure in cleanup
        except BaseException:
            pass
        try:
            self._drain_finalize()
        # hslint: allow[exception-discipline] secondary failure in cleanup
        except BaseException:
            pass
        if self._pool is not None:
            self._pool.shutdown(wait=True)
            self._pool = None
        if self._finalize_pool is not None:
            self._finalize_pool.shutdown(wait=True)
            self._finalize_pool = None
        if self._dir is not None:
            from hyperspace_tpu.io.files import remove_tree

            remove_tree(self._dir, ignore_errors=True)
            self._dir = None

    def _plan_code_columns(self, table: pa.Table) -> tuple:
        """Ride-along sort-code column names (one uint64 per indexed
        column), or () when any key type is rank-mapped (strings/binary/
        decimal): chunk-local dense ranks are not comparable across
        chunks, so those builds keep the route grouped-only and
        re-derive order words per bucket at finalize, exactly like the
        pre-pipeline builder."""
        key_cols = list(self.resolved.indexed_columns)
        for c in key_cols:
            if not columnar.is_numeric_type(table.schema.field(c).type):
                return ()
        taken = set(table.column_names)
        names = []
        for i in range(len(key_cols)):
            name = f"__hs_sort{i}"
            while name in taken:
                name += "_"
            taken.add(name)
            names.append(name)
        return tuple(names)

    def add_chunk(self, table: pa.Table) -> None:
        if self._dir is None:
            import tempfile

            self._dir = tempfile.mkdtemp(
                prefix=_spill_dir_prefix("hs_build_spill_"))
        self.spilled = True
        if self._schema is None:
            self._schema = table.schema
            self._code_cols = self._plan_code_columns(table)
        chunk_no = self._chunk_no
        self._chunk_no += 1
        pool = self._route_pool()
        if pool is None:
            self._route_chunk(table, chunk_no)
            return
        if len(self._futures) >= self._MAX_IN_FLIGHT:
            # Backpressure: the consumer waits on the oldest route job.
            with span("build.route_wait"):
                while len(self._futures) >= self._MAX_IN_FLIGHT:
                    self._futures.pop(0).result()
        with self._close_lock:
            self._routes_pending += 1
        self._futures.append(
            pool.submit(self._route_traced, table, chunk_no))

    def _route_traced(self, table: pa.Table, chunk_no: int) -> None:
        """Route one chunk on a worker thread and fire the streaming
        close when this was the LAST route job after end-of-input —
        finalize then starts while finish() is still joining futures."""
        ok = False
        try:
            self._route_chunk(table, chunk_no)
            ok = True
        finally:
            fire = False
            with self._close_lock:
                self._routes_pending -= 1
                if not ok:
                    self._route_failed = True
                elif self._input_done and self._routes_pending == 0 \
                        and not self._closed and not self._route_failed:
                    self._closed = True
                    fire = True
            if fire:
                self._close_groups()

    def _active_mesh(self):
        """The engine mesh for this build's chunk routes, resolved once
        (``hyperspace.parallel.mesh.enabled``; None = single-device)."""
        if not self._mesh_probed:
            from hyperspace_tpu.parallel.mesh import active_mesh

            self._mesh = active_mesh(self.action.conf)
            self._mesh_probed = True
        return self._mesh

    def _route_chunk(self, table: pa.Table, chunk_no: int) -> None:
        import time as _time

        from hyperspace_tpu.ops.hash import (
            route_partition,
            route_partition_mesh,
            route_partition_np,
        )

        with self.action.build_report.phase("spill_route"):
            _t0 = _time.perf_counter()  # the device-kernel attribution
            n = table.num_rows
            # Z-order builds never spill here (they take the dedicated
            # two-pass path that preserves the global curve), so
            # partitions are always real index buckets.
            num_buckets = self._num_buckets
            key_cols = list(self.resolved.indexed_columns)
            word_cols = [
                np.asarray(columnar.to_hash_words(table.column(c)))
                for c in key_cols]
            # Value-mapped keys: monotone sort codes come along, so the
            # ONE fused pass both buckets the rows and sorts them within
            # bucket — and the writer's sort codes are THIS pass's
            # byproduct riding the runs as temp uint64 columns, not a
            # finalize-time recompute over every row.  The host mirror
            # keys on the uint64 codes directly; only the device kernel
            # needs the 32-bit word split.
            codes64 = [columnar.to_order_codes64(table.column(c))
                       for c in key_cols] if self._code_cols else []
            if n < self.action.conf.device_min_rows("build"):
                # Host mirror below the threshold, same cost model as the
                # monolithic build: a per-chunk device round trip
                # (transfer + possible compile, per chunk!) can exceed a
                # host pass — and the mirror is bit-identical, so layout
                # cannot depend on the route.
                buckets, perm = route_partition_np(word_cols, codes64,
                                                   num_buckets)
            elif (mesh := self._active_mesh()) is not None:
                # Sharded route: rows data-parallel over the mesh, each
                # device owning buckets ``b % n_devices``, per-device
                # runs gathered through the attributed host seam (one
                # pull per device per chunk) — bit-identical layout,
                # proven by tests/test_parallel_mesh.py's per-bucket
                # digests.
                devices = list(mesh.devices.flat)
                buckets, perm = route_partition_mesh(
                    word_cols,
                    [columnar.split_words64(k) for k in codes64],
                    num_buckets, mesh,
                    pad_to=max(1,
                               int(self.action.conf.device_batch_rows)))
                ms = (_time.perf_counter() - _t0) * 1000.0
                report = self.action.build_report
                report.properties["mesh_devices"] = len(devices)
                for dev in devices:
                    report.add_device_kernel_ms(
                        int(getattr(dev, "id", -1)), ms)
            else:
                buckets, perm = route_partition(
                    word_cols,
                    [columnar.split_words64(k) for k in codes64],
                    num_buckets,
                    pad_to=max(1,
                               int(self.action.conf.device_batch_rows)))
                import jax

                # Same attribution as the sharded route, on the one
                # device.
                self.action.build_report.add_device_kernel_ms(
                    int(jax.devices()[0].id),
                    (_time.perf_counter() - _t0) * 1000.0)
            buckets = np.asarray(buckets)
            perm = np.asarray(perm)
            sorted_buckets = buckets[perm]
            routed = table.take(pa.array(perm))
            for i, name in enumerate(self._code_cols):
                routed = routed.append_column(name,
                                              pa.array(codes64[i][perm]))
            starts = np.searchsorted(sorted_buckets,
                                     np.arange(num_buckets), "left")
            ends = np.searchsorted(sorted_buckets, np.arange(num_buckets),
                                   "right")
            self._write_chunk_runs(routed, chunk_no, starts, ends)

    def _write_chunk_runs(self, routed: pa.Table, chunk_no: int,
                          starts, ends) -> None:
        """One Arrow IPC file per (chunk, bucket group), one record
        batch per non-empty bucket: per-bucket random access at finalize
        with _groups file ops per chunk instead of num_buckets.  Run
        files are TEMPORARY (read back once, deleted): raw IPC skips the
        parquet encode/decode entirely, and batches read back zero-copy
        from a memory map."""
        for gid in range(self._groups):
            b0, b1 = self._bounds[gid], self._bounds[gid + 1]
            present = [b for b in range(b0, b1) if ends[b] > starts[b]]
            if not present:
                continue
            path = os.path.join(
                self._dir, f"chunk-{chunk_no:05d}-g{gid:03d}.arrow")
            nbytes = _write_chunk_file(
                routed, path,
                [(int(starts[b]), int(ends[b] - starts[b]))
                 for b in present])
            with self._manifest_lock:
                for bi, b in enumerate(present):
                    self._runs.setdefault(b, []).append(
                        (chunk_no, path, bi))
                self._group_files.setdefault(gid, []).append(path)
            self.action.build_report.add_bytes(
                spill=nbytes, spill_runs=len(present))

    def _finalize_pool_get(self):
        import os as _os

        if self._finalize_pool is None:
            from concurrent.futures import ThreadPoolExecutor

            # Capped at the core count: group finalize is CPU-bound
            # (merge + parquet encode), so extra threads on a small host
            # only buy GIL/scheduler contention — measured ~25% slower
            # with 4 workers on 1 core.  One worker still STREAMS
            # (groups start the moment routing drains).
            workers = max(1, min(
                int(getattr(self.action.conf, "build_finalize_workers",
                            4)),
                _os.cpu_count() or 1))
            self._finalize_pool = ThreadPoolExecutor(
                max_workers=workers, thread_name_prefix="hs-finalize")
        return self._finalize_pool

    def _close_groups(self) -> None:
        """Every routed bucket group is now closed: enqueue each on the
        finalize pool (streaming mode) or finish them in order (serial
        reference).  May run on a route worker thread — the finalize
        pool starts draining groups while finish() is still joining the
        earlier route futures."""
        with self._manifest_lock:
            gids = sorted(self._group_files)
        if self.pipelined:
            pool = self._finalize_pool_get()
            self._finalize_futures.extend(
                pool.submit(self._finish_group, gid) for gid in gids)
        else:
            for gid in gids:
                self._finish_group(gid)

    def _finish_group(self, gid: int) -> None:
        """Merge + parquet-encode every bucket of one closed group, then
        delete the group's chunk files — consumed spill space is
        returned while OTHER groups still hold theirs, so peak disk is
        source + runs + in-flight groups, not source + runs + the whole
        final index."""
        from hyperspace_tpu.io.files import remove_file
        from hyperspace_tpu.io.parquet import (
            sort_permutation_from_codes,
            write_bucket_run,
        )

        action = self.action
        with action.build_report.phase("spill_finish"):
            max_rows = action.conf.index_max_rows_per_file
            b0, b1 = self._bounds[gid], self._bounds[gid + 1]
            with self._manifest_lock:
                paths = list(self._group_files.get(gid, ()))
                buckets = sorted(b for b in self._runs if b0 <= b < b1)
            readers = {}
            handles = []
            try:
                for p in paths:
                    mm = pa.memory_map(p, "rb")
                    handles.append(mm)
                    readers[p] = pa.ipc.open_file(mm)
                for b in buckets:
                    with self._manifest_lock:
                        runs = sorted(self._runs[b])  # chunk order = ties
                    batches = [readers[p].get_batch(bi) for _, p, bi in runs]
                    btable = pa.Table.from_batches(batches)
                    if self._code_cols:
                        perm = sort_permutation_from_codes(btable,
                                                           self._code_cols)
                        btable = btable.take(pa.array(perm)).drop_columns(
                            list(self._code_cols))
                    else:
                        perm = self._sort_permutation(btable)
                        btable = btable.take(pa.array(perm))
                    written = write_bucket_run(
                        btable, b, self._out_dir, max_rows,
                        compression=action.conf.index_file_compression)
                    action.build_report.add_bytes(
                        written=sum(os.path.getsize(p) for p in written),
                        files=len(written))
            finally:
                for mm in handles:
                    try:
                        mm.close()
                    except OSError:
                        pass
            for p in paths:
                remove_file(p, missing_ok=True)

    def finish(self) -> None:
        from hyperspace_tpu.io.files import remove_tree

        action = self.action
        resolved = self.resolved
        # The version dir must exist BEFORE end-of-input is announced:
        # the first finalize worker may start while route futures are
        # still draining.
        version = action.data_manager.get_next_version()
        out_dir = action.data_manager.version_path(version)
        os.makedirs(out_dir, exist_ok=True)
        self._out_dir = out_dir
        fire = False
        with self._close_lock:
            self._input_done = True
            if self._routes_pending == 0 and not self._closed \
                    and not self._route_failed:
                self._closed = True
                fire = True
        if fire:
            self._close_groups()
        with span("build.route_wait"):
            self._drain()  # re-raise the first route failure
        if self._pool is not None:
            self._pool.shutdown(wait=True)
            self._pool = None
        # The EXPOSED finalize tail: how long the build still waits on
        # bucket-group encode after routing fully drained — the number
        # the streaming overlap is spent against (``finalize`` phase and
        # timeline lane; the per-group work itself lands in
        # ``spill_finish`` on the pool workers).
        with action.build_report.phase("finalize") if self.pipelined \
                else contextlib.nullcontext():
            self._drain_finalize()
        if self._finalize_pool is not None:
            self._finalize_pool.shutdown(wait=True)
            self._finalize_pool = None
        remove_tree(self._dir, ignore_errors=True)
        self._dir = None
        with action.build_report.phase("sketch"):
            action._write_index_file_sketch(out_dir, resolved)
        action._written_version = version
        action._index_schema = {name: str(t) for name, t in
                                zip(self._schema.names, self._schema.types)}

    def _sort_permutation(self, btable: pa.Table) -> np.ndarray:
        # Always the lexicographic layout here: zorder builds take the
        # dedicated two-pass path and never reach the hash spill.
        from hyperspace_tpu.io.parquet import sort_permutation_host

        return sort_permutation_host(btable, self.resolved.indexed_columns,
                                     self.resolved.layout)


class CreateAction(CreateActionBase):
    transient_state = States.CREATING
    final_state = States.ACTIVE
    event_class = CreateActionEvent

    def validate(self) -> None:
        # CreateAction.scala:45-66
        if self.previous_log_entry is not None and \
                self.previous_log_entry.state not in (States.DOESNOTEXIST,):
            raise HyperspaceError(
                f"Another index with name {self.config.index_name!r} already "
                f"exists in state {self.previous_log_entry.state}")
        leaves = self.plan.leaf_relations()
        if len(leaves) != 1 or not \
                self.session.source_provider_manager.is_supported_relation(leaves[0]):
            raise HyperspaceError("Only plans over one supported file-based "
                                  "relation can be indexed")
        self._resolved_config()  # raises on unresolvable columns

    def log_entry_for_begin(self) -> IndexLogEntry:
        # Fresh entry: the index data hasn't been written yet, so content is
        # a placeholder tree of the (empty) v0 dir.
        relation = self._relation()
        resolved = self._resolved_config()
        rel_meta = relation.create_relation_metadata(FileIdTracker())
        return IndexLogEntry(
            name=self.config.index_name,
            derived_dataset=CoveringIndex(
                indexed_columns=resolved.indexed_columns,
                included_columns=resolved.included_columns,
                num_buckets=self.num_buckets,
                schema={},
            ),
            content=Content.from_leaf_files(
                []) or Content.from_directory(self.data_manager.index_path, FileIdTracker()),
            source=Source(relations=[rel_meta],
                          fingerprint=LogicalPlanFingerprint([self._signature()])),
        )

    def op(self) -> None:
        self._build_index_data()

    def log_entry(self) -> IndexLogEntry:
        return self._build_log_entry()
