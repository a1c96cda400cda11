"""Bench resilience proof (the round-5 acceptance criterion): bench.py run
with a tiny budget, or SIGTERM'd mid-section, must still emit JSON that
parses, contains every COMPLETED section's numbers, and marks every
unfinished section ``{"skipped": "<reason>"}`` — with exit code 0.

The bench runs as a real subprocess at toy scale (HS_BENCH_* overrides);
these tests are about the harness contract, not the numbers.  Heavy tier:
excluded from `-m quick` (tests/conftest.py)."""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench.py")

SECTIONS = ("setup", "sf1_queries", "device_agg_probe", "resident_agg",
            "warm_resident_join", "warm_q3", "warm_q10", "window_bench",
            "kernel_bench", "calibration", "telemetry_overhead",
            "advisor", "integrity", "build_profile", "timeline",
            "build_pipeline", "multichip", "multihost", "serving",
            "flight_recorder", "alerts", "fleet_obs", "fleet", "chaos",
            "ingest", "cdc", "sf10", "sf100")


def _env(tmp_path, budget: str) -> dict:
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",          # the bench runs on the CPU only when asked
        HS_XLA_CACHE="0",
        HS_CALIBRATE="0",
        HS_DEVICE_BATCH_ROWS="65536",
        HS_BENCH_LINEITEM="20000",
        HS_BENCH_ORDERS="5000",
        HS_BENCH_FILES="4",
        HS_BENCH_REPS="1",
        HS_BENCH_SF10="0",
        HS_BENCH_SF100="0",
        HS_BENCH_BUDGET=budget,
        HS_BENCH_RESULTS=str(tmp_path / "results.jsonl"),
    )
    return env


def _parse_lines(stdout: str):
    lines = [json.loads(ln) for ln in stdout.splitlines() if ln.strip()]
    assert lines, "bench printed nothing"
    headline = lines[-1]
    assert headline.get("metric") == "tpch_sf1_indexed_query_speedup_geomean"
    assert headline.get("unit") == "x"
    return lines, headline


def _check_contract(headline: dict, results_path) -> None:
    """Every section is accounted for: completed numbers present, or an
    explicit skipped marker with a reason."""
    detail = headline["detail"]
    statuses = {s["section"]: s for s in detail["sections_run"]}
    assert set(statuses) == set(SECTIONS), statuses.keys()
    for name, st in statuses.items():
        if st["status"] == "ok":
            continue
        assert st.get("reason"), st
        assert detail[name]["skipped"] == st["reason"]
    # The checkpoint file holds one parseable record per section outcome
    # (plus a header and, on finalize, the headline) — the un-losable copy.
    records = [json.loads(ln) for ln in
               open(results_path, encoding="utf-8")]
    seen = {r["section"] for r in records if "section" in r}
    assert seen == set(SECTIONS)
    ok_records = {r["section"]: r for r in records
                  if r.get("status") == "ok"}
    for name, st in statuses.items():
        if st["status"] == "ok":
            assert name in ok_records, name
    assert any("headline" in r for r in records)


def test_exhausted_budget_still_emits_full_headline(tmp_path):
    """A budget too small for ANY section: every section is skipped with
    the budget reason, the headline still prints, exit code 0."""
    proc = subprocess.run(
        [sys.executable, BENCH], env=_env(tmp_path, budget="0.000001"),
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines, headline = _parse_lines(proc.stdout)
    _check_contract(headline, tmp_path / "results.jsonl")
    detail = headline["detail"]
    assert headline["value"] is None  # sf1 never ran — no fake number
    for name in SECTIONS:
        assert "budget" in detail[name]["skipped"], detail[name]


def test_sigterm_mid_run_keeps_completed_sections(tmp_path):
    """SIGTERM after the first section completes: its numbers survive in
    the headline AND the checkpoint file; everything unfinished carries a
    skipped marker; exit code 0."""
    err_path = tmp_path / "stderr.txt"
    with open(err_path, "w") as err_sink:
        # stderr goes to a file so an unread pipe can never block the
        # child while this test tails stdout only.
        proc = subprocess.Popen(
            [sys.executable, BENCH], env=_env(tmp_path, budget="0"),
            stdout=subprocess.PIPE, stderr=err_sink, text=True)
    out_lines = []
    deadline = time.monotonic() + 300
    try:
        for line in proc.stdout:
            out_lines.append(line)
            if time.monotonic() > deadline:
                raise AssertionError("setup section never completed")
            rec = json.loads(line) if line.strip() else {}
            if rec.get("section") == "setup":
                assert rec["status"] == "ok", rec
                proc.send_signal(signal.SIGTERM)
                break
        rest, _ = proc.communicate(timeout=300)
        out_lines.append(rest)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert proc.returncode == 0, open(err_path).read()[-2000:]
    lines, headline = _parse_lines("".join(out_lines))
    _check_contract(headline, tmp_path / "results.jsonl")
    detail = headline["detail"]
    # The completed section's numbers are all there...
    assert detail["index_build_s"] > 0
    assert detail["scale"]["lineitem_rows"] == 20000
    assert detail["index_build_phases"]
    # ...and at least one section names SIGTERM as its skip reason.
    skipped = [s for s in detail["sections_run"] if s["status"] != "ok"]
    assert skipped, "SIGTERM mid-run left nothing skipped?"
    assert any("SIGTERM" in s.get("reason", "") for s in skipped), skipped


def test_budget_derives_from_enclosing_timeout(tmp_path):
    """HS_BENCH_BUDGET unset + an enclosing coreutils `timeout`: the
    default budget derives from the timeout's duration (minus finalize
    headroom), so the in-process finalize fires BEFORE the external
    kill — the r05 blackout (rc=124, parsed: null) cannot recur.  The
    headline must parse from stdout whatever exit code the timeout
    wrapper reports."""
    env = _env(tmp_path, budget="0")
    env.pop("HS_BENCH_BUDGET")
    env.pop("HS_BENCH_TIMEOUT_S", None)
    proc = subprocess.run(
        ["timeout", "-k", "10", "45", sys.executable, BENCH],
        env=env, capture_output=True, text=True, timeout=600)
    _lines, headline = _parse_lines(proc.stdout)
    detail = headline["detail"]
    # The derived budget sits under the enclosing 45 s limit.
    assert 0 < detail["budget_s"] < 45, detail["budget_s"]
    # Every section is accounted for even though most were skipped.
    statuses = {s["section"] for s in detail["sections_run"]}
    assert statuses == set(SECTIONS)


def test_budget_derives_through_r05_invocation_shape(tmp_path):
    """BENCH_r05's EXACT invocation shape: the harness wraps the bench
    in `timeout -k 10 <wall> sh -c "if [ -f bench.py ]; then python
    bench.py; else exit 0; fi"` with NO HS_BENCH_BUDGET — so the budget
    derivation must find the `timeout` ancestor THROUGH the `sh -c`
    wrapper layer (r05 died rc=124 with `parsed: null` because nothing
    finalized before the external kill).  The headline must parse from
    stdout with a derived budget under the wall, whatever exit code the
    timeout wrapper reports."""
    env = _env(tmp_path, budget="0")
    env.pop("HS_BENCH_BUDGET")
    env.pop("HS_BENCH_TIMEOUT_S", None)
    proc = subprocess.run(
        ["timeout", "-k", "10", "60", "sh", "-c",
         f"if [ -f {BENCH} ]; then {sys.executable} {BENCH}; "
         f"else exit 0; fi"],
        env=env, capture_output=True, text=True, timeout=600,
        cwd=os.path.dirname(BENCH))
    _lines, headline = _parse_lines(proc.stdout)
    detail = headline["detail"]
    # The derived budget found the timeout through the sh layer and
    # sits under the enclosing 60 s wall.
    assert 0 < detail["budget_s"] < 60, detail["budget_s"]
    statuses = {s["section"] for s in detail["sections_run"]}
    assert statuses == set(SECTIONS)


def test_timeout_duration_parser():
    """The coreutils-timeout argv parser behind the derived budget:
    options with values are skipped, the first positional is the
    duration, suffixes scale."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("hs_bench", BENCH)
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    parse = bench._timeout_duration_from_argv
    assert parse(["timeout", "-k", "10", "870", "python"]) == 870.0
    assert parse(["/usr/bin/timeout", "2m", "sleep", "999"]) == 120.0
    assert parse(["timeout", "--kill-after=10", "-s", "TERM",
                  "1.5h", "x"]) == 5400.0
    assert parse(["timeout", "--foreground", "30s", "x"]) == 30.0
    assert parse(["python", "bench.py"]) is None
    assert parse(["timeout", "-k", "10"]) is None
    assert parse(["timeout", "notanumber", "x"]) is None


def test_headline_shape_matches_prior_rounds(tmp_path):
    """A full tiny run keeps the BENCH_r04-compatible shape: metric /
    value / unit / vs_baseline / detail, detail carrying the per-workload
    scan/indexed/speedup triples and the scale block."""
    proc = subprocess.run(
        [sys.executable, BENCH], env=_env(tmp_path, budget="0"),
        capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    _lines, headline = _parse_lines(proc.stdout)
    assert isinstance(headline["value"], float)
    assert headline["vs_baseline"] == headline["value"]
    detail = headline["detail"]
    for w in ("filter", "join", "q3_shape", "q10_shape", "ds_range",
              "zorder", "hybrid", "hybrid_join"):
        assert f"{w}_scan_s" in detail
        assert f"{w}_indexed_s" in detail
        assert f"{w}_speedup" in detail
    assert detail["scale"]["num_buckets"] == 16
    assert detail["sf10"]["skipped"] == "HS_BENCH_SF10=0"
    assert detail["sf100"]["skipped"] == "HS_BENCH_SF100=0"
    assert detail["platform"]
    # Telemetry contract: the overhead section ran its gate and the JSONL
    # trace sink holds the required span kinds (the CI smoke step greps
    # the same names, so the sink format cannot silently drift).
    to = detail["telemetry_overhead"]
    assert to["span_disabled_ns_per_call"] < 10_000
    assert "tracing_on_overhead_pct" in to
    trace_path = str(tmp_path / "results.jsonl") + ".trace.jsonl"
    assert detail["trace_file"] == trace_path
    roots = [json.loads(ln) for ln in open(trace_path, encoding="utf-8")]
    names = {s["name"] for r in roots for s in _walk(r)}
    for required in ("bench.setup", "bench.sf1_queries", "query.collect",
                     "optimize", "optimize.rule.filter", "execute",
                     "exec.scan", "io.read"):
        assert required in names, (required, sorted(names)[:40])
    assert all("duration_ms" in r and "status" in r for r in roots)


def _walk(span_dict):
    yield span_dict
    for c in span_dict.get("children", ()):
        yield from _walk(c)


def test_sigterm_during_sf10_build_keeps_headline(tmp_path):
    """The kill-with-headline path over the build sections (ROADMAP item
    3, second half): SIGTERM while an index build runs, with sf10 still
    ahead, must still produce the headline (the handler finalizes
    in-line), rc 0, with the interrupted section and sf10 marked — or,
    if the tiny sf10 won the race and completed, its numbers present."""
    env = _env(tmp_path, budget="0")
    env.update(HS_BENCH_SF10="1",
               HS_BENCH_SF10_LINEITEM="400000",
               HS_BENCH_SF10_ORDERS="100000",
               HS_BENCH_SF10_FILES="4")
    err_path = tmp_path / "stderr.txt"
    with open(err_path, "w") as err_sink:
        proc = subprocess.Popen(
            [sys.executable, BENCH], env=env,
            stdout=subprocess.PIPE, stderr=err_sink, text=True)
    out_lines = []
    try:
        for line in proc.stdout:
            out_lines.append(line)
            rec = json.loads(line) if line.strip() else {}
            # TERM goes out as soon as build_profile is done, so it lands
            # in the timeline section's index builds: a later TERM races
            # build_pipeline's wall-clock gate (< 1 s after build_profile
            # at this scale), whose failure exits 1 before sf10 is reached.
            if rec.get("section") == "build_profile":
                proc.send_signal(signal.SIGTERM)
                break
        rest, _ = proc.communicate(timeout=300)
        out_lines.append(rest)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert proc.returncode == 0, open(err_path).read()[-2000:]
    _lines, headline = _parse_lines("".join(out_lines))
    _check_contract(headline, tmp_path / "results.jsonl")
    detail = headline["detail"]
    # sf1 completed before the TERM, so the headline VALUE survives.
    assert isinstance(headline["value"], float)
    sf10 = detail["sf10"]
    assert "skipped" in sf10 and "SIGTERM" in sf10["skipped"] \
        or "index_build_s" in sf10, sf10


def test_finalize_from_reconstructs_headline(tmp_path):
    """A run SIGKILLed before any finalize: --finalize-from rebuilds the
    headline from the checkpoint file alone — completed sections' numbers
    in, a partial geomean from the sf1 speedups, every missing section
    marked."""
    results = tmp_path / "results.jsonl"
    with open(results, "w") as f:
        f.write(json.dumps({"bench": "hyperspace-tpu",
                            "scale": {"lineitem_rows": 100}}) + "\n")
        f.write(json.dumps({"section": "setup", "status": "ok",
                            "elapsed_s": 1.0, "index_build_s": 0.5}) + "\n")
        f.write(json.dumps({"section": "sf1_queries", "status": "ok",
                            "elapsed_s": 1.0, "filter_speedup": 4.0,
                            "join_speedup": 1.0}) + "\n")
        f.write('{"torn line')  # the kill's last, partial write
    proc = subprocess.run(
        [sys.executable, BENCH, "--finalize-from", str(results)],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    headline = json.loads(proc.stdout.strip().splitlines()[-1])
    assert headline["metric"] == "tpch_sf1_indexed_query_speedup_geomean"
    assert headline["value"] == 2.0  # geomean(4.0, 1.0)
    detail = headline["detail"]
    assert detail["index_build_s"] == 0.5
    assert detail["finalized_from"] == str(results)
    statuses = {s["section"]: s["status"] for s in detail["sections_run"]}
    assert statuses["setup"] == "ok"
    assert statuses["sf100"] == "skipped"
    assert set(statuses) == set(SECTIONS)


def test_compare_only_cli_wiring(tmp_path):
    """--compare-only diffs two artifacts without running the bench:
    exit 0 on parity, 3 on a flagged regression (with the attribution
    table), 2 on a missing baseline."""
    def write(path, build_s, speedup):
        with open(path, "w") as f:
            f.write(json.dumps({"bench": "hyperspace-tpu"}) + "\n")
            f.write(json.dumps({
                "section": "setup", "status": "ok", "elapsed_s": 1.0,
                "index_build_s": build_s,
                "index_build_phases": [{"index": "li", "read_s": 0.1,
                                        "spill_route_s": build_s - 0.1}],
            }) + "\n")
            f.write(json.dumps({"section": "sf1_queries", "status": "ok",
                                "elapsed_s": 1.0,
                                "filter_speedup": speedup}) + "\n")
        return str(path)

    base = write(tmp_path / "base.jsonl", build_s=2.0, speedup=4.0)
    same = write(tmp_path / "same.jsonl", build_s=2.0, speedup=4.0)
    slow = write(tmp_path / "slow.jsonl", build_s=8.0, speedup=1.0)

    def run(current, baseline):
        return subprocess.run(
            [sys.executable, BENCH, "--compare", baseline,
             "--compare-only", current],
            capture_output=True, text=True, timeout=120)

    ok = run(same, base)
    assert ok.returncode == 0, ok.stderr[-2000:]
    assert "no regression" in ok.stdout

    bad = run(slow, base)
    assert bad.returncode == 3, (bad.stdout, bad.stderr[-500:])
    assert "index_build_s" in bad.stdout
    assert "filter_speedup" in bad.stdout
    assert "per-phase attribution" in bad.stdout
    assert "spill_route" in bad.stdout

    missing = run(same, str(tmp_path / "nope.jsonl"))
    assert missing.returncode == 2
