"""Closed-loop TPC-H Q3 streams over Delta tables that an ingest job
appends RF1 batches to between index refreshes: Hyperspace's Hybrid Scan
(the index read for the files it covers, the appended files read raw
and merged in) on the served path.

Parameters: those of ``q3_streams`` (``streams``, ``date_lo``/``date_hi``,
``date_per``, ``specs_per_stream``), plus ``orders_per_sf`` (RF1 inserts
SF x 1500 orders, §2.5.2), ``backlog_batches`` (RF1 batches committed
after the index build and before warm-up, never refreshed) and
``commit_interval_s`` (through the window, a child process commits one
more batch per table every so many seconds; ``ingest.py``).

Each answer states the Delta versions it read (schema metadata
``hyperspace.source_versions``, table path to version).  The check
holds it to the reference Q3 over the base and the RF1 batches of those
versions (``q3_answers_wrong``, ``q3_revenue_rel_gap``), and each
version to no older than the table's last commit acknowledged before
the query was sent (``q3_stale_answers``), on the host's one monotonic
clock.  A program that states no versions fails set-up.  The label is
not taken on trust: the appended rows the program read in the window
(counter ``hybrid.appended_rows``) must equal those of the RF1 batches
its answers state, summed over the answers (``q3_unstated_rows``), so an
answer read from an older file list under a fresh label fails even
where Q3's top ten does not move.  The backlog stays under the hybrid
scan's ``maxAppendedRatio`` through the window, so every answer reads
the appended files through it.
"""

from __future__ import annotations

import importlib.util
import json
import os
import pickle
import subprocess
import sys
import time

import numpy as np
import pyarrow as pa

import reference
import tpch
from served import TIMEOUT_S, Served

_HERE = os.path.dirname(os.path.abspath(__file__))
_LIB = os.path.dirname(os.path.abspath(tpch.__file__))
_VERSIONS_KEY = b"hyperspace.source_versions"
TABLES = ("orders", "lineitem")  # the ingest job's commit order


def _q3_streams():
    path = os.path.join(_HERE, "q3_streams.py")
    spec = importlib.util.spec_from_file_location("bench_kind_q3_streams",
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


q3_streams = _q3_streams()


class _Child:
    """A child process that imports no JAX device: prints READY, starts
    on GO, writes its output file and exits."""

    def __init__(self, ctx, script: str, plan, env=None) -> None:
        name = os.path.splitext(script)[0]
        plan_path = os.path.join(ctx.workdir, name + "_plan.json")
        self.out_path = os.path.join(ctx.workdir, name + "_out")
        with open(plan_path, "w") as f:
            json.dump(plan, f)
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(_LIB, script), plan_path,
             self.out_path], stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            text=True, env=env)
        line = self.proc.stdout.readline().strip()
        if line != "READY":
            self.kill()
            raise RuntimeError(f"{name} did not start: {line!r}")

    def go(self) -> None:
        self.proc.stdin.write("GO\n")
        self.proc.stdin.flush()

    def wait(self, timeout: float) -> None:
        self.proc.communicate(timeout=timeout)
        if self.proc.returncode != 0:
            raise RuntimeError(f"{self.proc.args[1]} exited "
                               f"{self.proc.returncode}")

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()


def stated_versions(ctx, answer: pa.Table):
    """{table name: version} an answer states; None when it states no
    version for one of the tables."""
    raw = (answer.schema.metadata or {}).get(_VERSIONS_KEY)
    by_path = {} if raw is None else json.loads(raw)
    out = {}
    for name in TABLES:
        v = by_path.get(os.path.abspath(ctx.paths[name]))
        if v is None:
            return None
        out[name] = int(v)
    return out


def _days(ctx):
    """Each stream's DATEs, drawn as ``q3_streams`` draws them."""
    t = ctx.traffic
    rng = np.random.default_rng([ctx.seed, 4])
    days = np.arange(q3_streams._day(t["date_lo"]),
                     q3_streams._day(t["date_hi"]) + 1)
    n = int(t["specs_per_stream"])
    if t["date_per"] == "query":
        return [np.concatenate([rng.permutation(days)
                                for _ in range(-(-n // days.size))])[:n]
                for _ in range(int(t["streams"]))]
    return [np.full(n, rng.choice(days)) for _ in range(int(t["streams"]))]


def _commit(ctx, batch: int) -> None:
    for name in TABLES:
        version = tpch.append_delta(ctx.batches[batch][name],
                                    ctx.paths[name])
        ctx.commits.append({"table": name, "batch": batch,
                            "version": int(version),
                            "acked": time.monotonic()})


def _first_query(ctx, day: int):
    from hyperspace_tpu.interop.query import dataset_from_spec

    answer = dataset_from_spec(ctx.session, q3_streams.spec(ctx, day)
                               ).collect()
    return answer, ctx.session.last_execution_stats or {}


def setup(ctx) -> None:
    t = ctx.traffic
    sf = float(ctx.config["scale_factor"])
    per_sf = int(t["orders_per_sf"])
    ctx.days = _days(ctx)
    days = np.unique(np.concatenate(ctx.days))
    ctx.base_version = int(ctx.config["files_per_table"]) - 1
    answer, _ = _first_query(ctx, days[0])
    stated = stated_versions(ctx, answer)
    if stated != dict.fromkeys(TABLES, ctx.base_version):
        raise RuntimeError(
            f"the program states {stated} for the tables it read, not "
            f"version {ctx.base_version} of each (schema metadata "
            f"{_VERSIONS_KEY.decode()})")
    ctx.batches = [tpch.rf1_batch(ctx.seed, b, sf, per_sf)
                   for b in range(int(t["backlog_batches"]))]
    ctx.commits = []
    with ctx.annotate("delta_commit"):
        for b in range(len(ctx.batches)):
            _commit(ctx, b)
    answer, stats = _first_query(ctx, days[0])
    scans = stats.get("scans", [])
    ctx.log("executor", joins=[j.get("strategy")
                               for j in stats.get("joins", [])],
            aggregates=[a.get("strategy")
                        for a in stats.get("aggregates", [])],
            index_scans=sum(bool(s.get("is_index")) for s in scans),
            source_files_read=sum(s.get("files_read", 0) for s in scans
                                  if not s.get("is_index")),
            versions=stated_versions(ctx, answer))
    ctx.served = Served(ctx.session, ctx.workdir)
    ctx.served.warm_up([q3_streams.spec(ctx, d) for d in days])
    ctx.ingest = _Child(ctx, "ingest.py", {
        "root": os.path.dirname(os.path.dirname(_LIB)),
        "paths": {n: ctx.paths[n] for n in TABLES}, "seed": ctx.seed,
        "scale_factor": sf, "orders_per_sf": per_sf,
        "first_batch": len(ctx.batches),
        "interval_s": float(t["commit_interval_s"]),
        "seconds": ctx.seconds}, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    ctx.load = _Child(ctx, "loadgen_clock.py", {
        "seconds": ctx.seconds,
        "streams": [[q3_streams.spec(ctx, d) for d in stream]
                    for stream in ctx.days],
        "address": list(ctx.served.server.address), "timeout_s": TIMEOUT_S})


_COUNTERS = ("hybrid.appended_files", "hybrid.appended_rows",
             "cache.device.hits", "cache.device.misses")


def _counts():
    from hyperspace_tpu.telemetry import metrics

    snap = metrics.snapshot()
    return [float(snap.get(k, 0.0)) for k in _COUNTERS]


def window(ctx) -> None:
    before = _counts()
    ctx.ingest.go()
    ctx.load.go()
    ctx.load.wait(ctx.seconds + 2 * TIMEOUT_S)
    ctx.ingest.wait(TIMEOUT_S)
    with open(ctx.load.out_path, "rb") as f:
        out = pickle.load(f)
    with open(ctx.ingest.out_path) as f:
        ingested = json.load(f)["commits"]
    ctx.sent_t0 = out["t0"]
    ctx.records = sorted(out["records"], key=lambda r: tuple(r["i"]))
    ctx.answers = out["answers"]
    ctx.commits += ingested
    ctx.window_s = max(r["done"] for r in ctx.records)
    files, rows, hits, misses = (
        a - b for a, b in zip(_counts(), before))
    ctx.appended_rows_read = rows
    n = max(1, len(ctx.records))
    read = {tuple(sorted(v.items())) for v in (
        stated_versions(ctx, a) for a in ctx.answers.values()) if v}
    ctx.layer.update(requests=ctx.records, log={
        "queries": len(ctx.records),
        "completed": sum(r["ok"] for r in ctx.records),
        "commits_in_window": len(ingested) // len(TABLES),
        "versions_read": len(read),
        "appended_files_per_query": files / n,
        "appended_rows_per_query": rows / n,
        "device_cache_lookups": hits + misses,
        "device_cache_hit_share": 100.0 * hits / (hits + misses)
        if hits + misses else None})


def stop(ctx) -> None:
    for name in ("load", "ingest"):
        child = getattr(ctx, name, None)
        if child is not None:
            child.kill()
            setattr(ctx, name, None)
    served, ctx.served = getattr(ctx, "served", None), None
    if served is not None:
        served.stop()


def end_to_end(ctx):
    return q3_streams.end_to_end(ctx)


def _reference(ctx, batches: int) -> reference.Q3:
    """Q3's reference over the base and the first ``batches`` batches."""
    parts = {name: pa.concat_tables(
        [ctx.tables[name]] + [ctx.batches[b][name] for b in range(batches)])
        for name in TABLES}
    return reference.Q3(parts["orders"], parts["lineitem"])


def check(ctx, dtype):
    sf = float(ctx.config["scale_factor"])
    per_sf = int(ctx.traffic["orders_per_sf"])
    committed = 1 + max(c["batch"] for c in ctx.commits)
    ctx.batches += [tpch.rf1_batch(ctx.seed, b, sf, per_sf)
                    for b in range(len(ctx.batches), committed)]
    acks = {name: sorted((c["acked"], c["version"]) for c in ctx.commits
                         if c["table"] == name) for name in TABLES}

    def floor(name: str, sent: float) -> int:
        """The table's last version acknowledged before ``sent``."""
        return max([v for a, v in acks[name] if a < sent],
                   default=ctx.base_version)

    # Rows of each table's first k batches: what the appended branch of
    # a query that states base + k reads.
    rows_upto = {name: np.concatenate([[0], np.cumsum(
        [ctx.batches[b][name].num_rows for b in range(committed)])])
        for name in TABLES}
    refs, want, lower = {}, {}, {}
    failed = sum(not r["ok"] for r in ctx.records)
    wrong, stale, gap, stated_rows = 0, 0, 0.0, 0
    for r in ctx.records:
        if not r["ok"]:
            continue
        answer = ctx.answers[tuple(r["i"])]
        stated = stated_versions(ctx, answer)
        sent = ctx.sent_t0 + r["sent"]
        if stated is None:
            wrong += 1
            stale += 1
            continue
        stale += any(stated[n] < floor(n, sent) for n in TABLES)
        batches = {n: stated[n] - ctx.base_version for n in TABLES}
        if not all(0 <= k <= committed for k in batches.values()):
            wrong += 1
            continue
        stated_rows += sum(int(rows_upto[n][k]) for n, k in batches.items())
        # Lineitems whose orders the query did not see join nothing.
        k = min(batches.values())
        day = int(ctx.days[r["i"][0]][r["i"][1]])
        if k not in refs:
            refs[k] = _reference(ctx, k)
        if (k, day) not in want:
            want[k, day] = refs[k].answer(day)
            if dtype != np.float64:
                lower[k, day] = refs[k].answer(day, dtype)
        got = answer if dtype == np.float64 else lower[k, day]
        bad, g = reference.q3_gap(got, want[k, day])
        wrong += bad
        if not bad:
            gap = max(gap, g)
    unstated = abs(ctx.appended_rows_read - stated_rows)
    ctx.log("check", answers=len(ctx.records) - failed,
            versions_checked=len(refs), batches_committed=committed,
            appended_rows_read=ctx.appended_rows_read,
            appended_rows_stated=stated_rows)
    return ({"q3_answers_wrong": wrong, "q3_revenue_rel_gap": gap,
             "q3_failed": failed, "q3_stale_answers": stale,
             "q3_unstated_rows": unstated},
            len(ctx.records), failed)
