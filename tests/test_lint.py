"""hslint (hyperspace_tpu/lint): per-rule fixture tests — one snippet
that FIRES and one that stays QUIET per rule — plus the baseline
add/expire round-trip, the JSON output schema, the CLI exit codes on a
seeded violation, the trace catalog check, and the self-clean gate
(the linter over the real repo reports zero new findings).

The fixtures build a miniature repo with the same layout the parsers
expect (hyperspace_tpu/config.py, docs/02, docs/16, io/faults.py,
interop/server.py), so every registry parser runs for real."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from hyperspace_tpu.lint import catalog as lint_catalog
from hyperspace_tpu.lint import engine as lint_engine

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Fixture repo
# ---------------------------------------------------------------------------
CONFIG_PY = '''\
FOO = "hyperspace.test.foo"
BAR = "hyperspace.test.bar"


class Conf:
    _FIELD_BY_KEY = {
        FOO: "test_foo",
        BAR: "test_bar",
    }
'''

DOCS_02 = '''\
# Configuration

| Key | Field | Default | Meaning |
|---|---|---|---|
| `hyperspace.test.foo` | `test_foo` | 1 | Foo |
| `hyperspace.test.bar` | `test_bar` | 2 | Bar |
'''

DOCS_16 = '''\
# Observability

## Metrics

| Metric | Type | Fed by |
|---|---|---|
| `m.one` | counter | x |
| `m.two.<slug>.count` | counter | y |

### Span taxonomy

| Span | Where | Tags |
|---|---|---|
| `s.root` | x | — |
'''

FAULTS_PY = '''\
SITES = (
    "a.one",
    "b.two",
)


def check(site):
    pass
'''

SERVER_PY = '''\
import threading

ERR_BUSY = "BUSY"
ERR_FAILED = "FAILED"


class WireError(Exception):
    def __init__(self, code, message):
        super().__init__(message)
        self.code = code


class Pool:
    def __init__(self):
        self._lock = threading.Lock()
        self._n = 0

    def good(self):
        with self._lock:
            self._n += 1
'''

ENGINE_PY = '''\
from hyperspace_tpu.io import faults


def use(conf):
    conf.set("hyperspace.test.foo", 1)
    faults.check("a.one")
    faults.check("b.two")
    return conf.test_bar
'''

EMITTER_PY = '''\
from hyperspace_tpu.telemetry import metrics
from hyperspace_tpu.telemetry.trace import span


def go(slug):
    metrics.inc("m.one")
    metrics.inc(f"m.two.{slug}.count")
    with span("s.root"):
        pass
'''

DEFAULT_FILES = {
    "hyperspace_tpu/config.py": CONFIG_PY,
    "hyperspace_tpu/engine.py": ENGINE_PY,
    "hyperspace_tpu/emitter.py": EMITTER_PY,
    "hyperspace_tpu/io/faults.py": FAULTS_PY,
    "hyperspace_tpu/interop/server.py": SERVER_PY,
    "docs/02-configuration.md": DOCS_02,
    "docs/16-observability.md": DOCS_16,
}


def make_repo(tmp_path, overrides=None):
    files = dict(DEFAULT_FILES)
    files.update(overrides or {})
    for rel, content in files.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(content)
    return str(tmp_path)


def run(root, rules=None, baseline=None):
    findings, expired = lint_engine.run_lint(root, rules, baseline or set())
    return findings, expired


def new_of(findings, rule=None):
    return [f for f in findings if not f.baselined
            and (rule is None or f.rule == rule)]


@pytest.mark.quick
class TestFixtureRepoClean:
    def test_default_fixture_is_clean(self, tmp_path):
        findings, expired = run(make_repo(tmp_path))
        assert new_of(findings) == []
        assert expired == []


@pytest.mark.quick
class TestConfRegistry:
    def test_undeclared_key_with_near_miss(self, tmp_path):
        root = make_repo(tmp_path, {
            "hyperspace_tpu/engine.py":
                ENGINE_PY.replace("hyperspace.test.foo",
                                  "hyperspace.test.fooo")})
        got = new_of(run(root)[0], "conf-registry")
        assert any("hyperspace.test.fooo" in f.message and
                   "did you mean" in f.message and
                   "hyperspace.test.foo" in f.message for f in got)

    def test_undocumented_key(self, tmp_path):
        root = make_repo(tmp_path, {
            "docs/02-configuration.md":
                DOCS_02.replace(
                    "| `hyperspace.test.foo` | `test_foo` | 1 | Foo |\n",
                    "")})
        got = new_of(run(root)[0], "conf-registry")
        assert any(f.ident == "undocumented:hyperspace.test.foo"
                   for f in got)

    def test_documented_but_undeclared(self, tmp_path):
        root = make_repo(tmp_path, {
            "docs/02-configuration.md": DOCS_02 +
                "| `hyperspace.test.ghost` | `ghost` | 0 | Vapor |\n"})
        got = new_of(run(root)[0], "conf-registry")
        assert any(f.ident == "doc-undeclared:hyperspace.test.ghost"
                   for f in got)

    def test_dead_key(self, tmp_path):
        # bar's field access removed -> neither literal, constant, nor
        # field referenced anywhere.
        root = make_repo(tmp_path, {
            "hyperspace_tpu/engine.py":
                ENGINE_PY.replace("return conf.test_bar", "return None"),
            "docs/02-configuration.md": DOCS_02})
        got = new_of(run(root)[0], "conf-registry")
        assert any(f.ident == "unused:hyperspace.test.bar" for f in got)

    def test_unwired_key(self, tmp_path):
        root = make_repo(tmp_path, {
            "hyperspace_tpu/config.py":
                CONFIG_PY.replace("        BAR: \"test_bar\",\n", ""),
            # keep bar "used" so only the unwired finding fires
        })
        got = new_of(run(root)[0], "conf-registry")
        assert any(f.ident == "unwired:hyperspace.test.bar" for f in got)


@pytest.mark.quick
class TestTelemetryCatalog:
    def test_uncataloged_metric_fires(self, tmp_path):
        root = make_repo(tmp_path, {
            "hyperspace_tpu/emitter.py":
                EMITTER_PY.replace('metrics.inc("m.one")',
                                   'metrics.inc("m.oen")')})
        got = new_of(run(root)[0], "telemetry-catalog")
        idents = {f.ident for f in got}
        assert "uncataloged:metric:m.oen" in idents
        # ...and the now-unemitted catalog row is flagged from the other
        # direction.
        assert "unemitted:metric:m.one" in idents

    def test_uncataloged_span_fires(self, tmp_path):
        root = make_repo(tmp_path, {
            "hyperspace_tpu/emitter.py":
                EMITTER_PY.replace('span("s.root")', 'span("s.rot")')})
        got = new_of(run(root)[0], "telemetry-catalog")
        assert any(f.ident == "uncataloged:span:s.rot" for f in got)

    def test_pattern_matches_placeholder_row(self, tmp_path):
        # f"m.two.{slug}.count" matches `m.two.<slug>.count` — the clean
        # fixture already proves it; flip the literal tail to break it.
        root = make_repo(tmp_path, {
            "hyperspace_tpu/emitter.py":
                EMITTER_PY.replace("m.two.{slug}.count",
                                   "m.two.{slug}.size")})
        got = new_of(run(root)[0], "telemetry-catalog")
        assert any(f.ident.startswith("uncataloged:metric:m.two.")
                   for f in got)

    def test_fully_dynamic_name_rejected(self, tmp_path):
        root = make_repo(tmp_path, {
            "hyperspace_tpu/emitter.py":
                EMITTER_PY + '\n\ndef bad(name):\n'
                             '    metrics.inc(f"{name}")\n'})
        got = new_of(run(root)[0], "telemetry-catalog")
        assert any(f.ident.startswith("dynamic:metric") for f in got)


@pytest.mark.quick
class TestIoSeam:
    def test_direct_write_fires(self, tmp_path):
        root = make_repo(tmp_path, {
            "hyperspace_tpu/actions/foo.py":
                "import os\n\n\ndef nuke(p):\n    os.remove(p)\n"})
        got = new_of(run(root)[0], "io-seam")
        assert any(f.ident == "os.remove:nuke" for f in got)

    def test_write_mode_open_fires_read_is_quiet(self, tmp_path):
        root = make_repo(tmp_path, {
            "hyperspace_tpu/actions/foo.py":
                'def w(p):\n    open(p, "w").write("x")\n'
                '\n\ndef r(p):\n    return open(p).read()\n'})
        got = new_of(run(root)[0], "io-seam")
        assert any(f.ident == "open-write:w" for f in got)
        assert not any("r" == f.ident.split(":")[-1] for f in got)

    def test_inside_io_is_quiet(self, tmp_path):
        root = make_repo(tmp_path, {
            "hyperspace_tpu/io/writer.py":
                "import os\n\n\ndef nuke(p):\n    os.remove(p)\n"})
        assert new_of(run(root)[0], "io-seam") == []

    def test_pragma_suppresses(self, tmp_path):
        root = make_repo(tmp_path, {
            "hyperspace_tpu/actions/foo.py":
                "import os\n\n\ndef nuke(p):\n"
                "    # hslint: allow[io-seam] test fixture\n"
                "    os.remove(p)\n"})
        assert new_of(run(root)[0], "io-seam") == []


@pytest.mark.quick
class TestFaultSiteRegistry:
    def test_typo_site_fires_with_near_miss(self, tmp_path):
        root = make_repo(tmp_path, {
            "hyperspace_tpu/engine.py":
                ENGINE_PY.replace('faults.check("a.one")',
                                  'faults.check("a.oen")')})
        got = new_of(run(root)[0], "fault-site-registry")
        assert any(f.ident == "unknown-site:a.oen" and "did you mean"
                   in f.message for f in got)
        # a.one is now unused in the engine -> dead registry entry too.
        assert any(f.ident == "unused-site:a.one" for f in got)

    def test_faultplan_site_checked(self, tmp_path):
        root = make_repo(tmp_path, {
            "tests/test_x.py":
                'from hyperspace_tpu.io.faults import FaultPlan\n'
                'PLAN = FaultPlan(site="c.three", kind="eio")\n'})
        got = new_of(run(root)[0], "fault-site-registry")
        assert any(f.ident == "unknown-site:c.three" for f in got)

    def test_registered_and_used_is_quiet(self, tmp_path):
        assert new_of(run(make_repo(tmp_path))[0],
                      "fault-site-registry") == []


@pytest.mark.quick
class TestExceptionDiscipline:
    def test_bare_except_fires(self, tmp_path):
        root = make_repo(tmp_path, {
            "hyperspace_tpu/utils/x.py":
                "def f():\n    try:\n        return 1\n"
                "    except:\n        return 0\n"})
        got = new_of(run(root)[0], "exception-discipline")
        assert any(f.ident == "bare-except:f" for f in got)

    def test_swallow_on_hot_path_fires(self, tmp_path):
        body = ("def f():\n    try:\n        return 1\n"
                "    except Exception:\n        pass\n")
        root = make_repo(tmp_path, {"hyperspace_tpu/actions/x.py": body})
        got = new_of(run(root)[0], "exception-discipline")
        assert any(f.ident == "swallow:f" for f in got)

    def test_swallow_off_hot_path_is_quiet(self, tmp_path):
        body = ("def f():\n    try:\n        return 1\n"
                "    except Exception:\n        pass\n")
        root = make_repo(tmp_path, {"hyperspace_tpu/utils/x.py": body})
        assert new_of(run(root)[0], "exception-discipline") == []

    def test_unknown_wire_code_fires(self, tmp_path):
        root = make_repo(tmp_path, {
            "hyperspace_tpu/interop/handler.py":
                'from hyperspace_tpu.interop.server import WireError\n\n\n'
                'def f():\n    raise WireError("BUZY", "oops")\n'})
        got = new_of(run(root)[0], "exception-discipline")
        assert any(f.ident == "wire-code:BUZY" for f in got)

    def test_err_literal_checked(self, tmp_path):
        root = make_repo(tmp_path, {
            "hyperspace_tpu/interop/handler.py":
                'def f(sock):\n    sock.send(b"x")\n'
                '    return "ERR BUZY try later"\n'})
        got = new_of(run(root)[0], "exception-discipline")
        assert any(f.ident == "err-literal:BUZY" for f in got)

    def test_known_code_is_quiet(self, tmp_path):
        root = make_repo(tmp_path, {
            "hyperspace_tpu/interop/handler.py":
                'from hyperspace_tpu.interop.server import (\n'
                '    ERR_BUSY,\n    WireError,\n)\n\n\n'
                'def f():\n    raise WireError(ERR_BUSY, "shed")\n'
                '\n\ndef g():\n    return f"ERR {ERR_BUSY} shed"\n'})
        assert new_of(run(root)[0], "exception-discipline") == []


@pytest.mark.quick
class TestLockDiscipline:
    def test_unlocked_write_of_guarded_state_fires(self, tmp_path):
        root = make_repo(tmp_path, {
            "hyperspace_tpu/interop/server.py": SERVER_PY +
                "\n    def bad(self):\n        self._n = 0\n"})
        got = new_of(run(root)[0], "lock-discipline")
        assert any(f.ident.startswith("unlocked:Pool.self._n")
                   for f in got)

    def test_unlocked_rmw_fires(self, tmp_path):
        root = make_repo(tmp_path, {
            "hyperspace_tpu/interop/server.py": SERVER_PY +
                "\n    def bump(self):\n        self._m += 1\n"})
        got = new_of(run(root)[0], "lock-discipline")
        assert any(f.ident.startswith("rmw:Pool.self._m") for f in got)

    def test_init_writes_are_exempt(self, tmp_path):
        assert new_of(run(make_repo(tmp_path))[0], "lock-discipline") == []

    def test_lock_cycle_detected(self, tmp_path):
        cyc = ("import threading\n\n"
               "A = threading.Lock()\n"
               "B = threading.Lock()\n\n\n"
               "def f():\n    with A:\n        with B:\n            pass\n"
               "\n\ndef g():\n    with B:\n        with A:\n"
               "            pass\n")
        root = make_repo(tmp_path, {"hyperspace_tpu/locky.py": cyc})
        got = new_of(run(root)[0], "lock-discipline")
        assert any(f.ident.startswith("cycle:") and "deadlock"
                   in f.message for f in got)

    def test_consistent_order_is_quiet(self, tmp_path):
        ok = ("import threading\n\n"
              "A = threading.Lock()\n"
              "B = threading.Lock()\n\n\n"
              "def f():\n    with A:\n        with B:\n            pass\n"
              "\n\ndef g():\n    with A:\n        with B:\n"
              "            pass\n")
        root = make_repo(tmp_path, {"hyperspace_tpu/locky.py": ok})
        assert new_of(run(root)[0], "lock-discipline") == []


@pytest.mark.quick
class TestHygiene:
    def test_duplicate_import_same_block_fires(self, tmp_path):
        root = make_repo(tmp_path, {
            "hyperspace_tpu/dup.py":
                "import os\nimport os\n\nprint(os.sep)\n"})
        got = new_of(run(root)[0], "hygiene")
        assert any(f.ident == "dup-import:<module>:os" for f in got)

    def test_branch_local_lazy_imports_are_quiet(self, tmp_path):
        body = ("def f(x):\n"
                "    if x:\n        import json\n"
                "        return json.dumps(x)\n"
                "    else:\n        import json\n"
                "        return json.loads(x)\n")
        root = make_repo(tmp_path, {"hyperspace_tpu/lazy.py": body})
        assert new_of(run(root)[0], "hygiene") == []

    def test_redundant_function_reimport_fires(self, tmp_path):
        root = make_repo(tmp_path, {
            "hyperspace_tpu/re.py":
                "import os\n\n\ndef f():\n    import os\n"
                "    return os.sep\n"})
        got = new_of(run(root)[0], "hygiene")
        assert any(f.ident == "redundant-import:f:os" for f in got)

    def test_dead_import_fires_and_noqa_exempts(self, tmp_path):
        root = make_repo(tmp_path, {
            "hyperspace_tpu/dead.py": "import os\n\nX = 1\n",
            "hyperspace_tpu/alive.py":
                "import os  # noqa: F401  (side effect)\n\nX = 1\n"})
        got = new_of(run(root)[0], "hygiene")
        paths = {f.path for f in got if f.ident == "dead-import:os"}
        assert "hyperspace_tpu/dead.py" in paths
        assert "hyperspace_tpu/alive.py" not in paths

    def test_mutable_default_fires(self, tmp_path):
        root = make_repo(tmp_path, {
            "hyperspace_tpu/mut.py":
                "def f(x=[]):\n    return x\n"})
        got = new_of(run(root)[0], "hygiene")
        assert any(f.ident == "mutable-default:f" for f in got)

    def test_string_annotation_counts_as_use(self, tmp_path):
        root = make_repo(tmp_path, {
            "hyperspace_tpu/ann.py":
                "from typing import Tuple\n\n\n"
                'def f() -> "Tuple[int, int]":\n    return (1, 2)\n'})
        assert new_of(run(root)[0], "hygiene") == []


@pytest.mark.quick
class TestBaselineRoundTrip:
    def test_add_then_expire(self, tmp_path):
        bad = "import os\n\nX = 1\n"  # dead import
        root = make_repo(tmp_path, {"hyperspace_tpu/dead.py": bad})
        findings, _ = run(root)
        assert len(new_of(findings)) == 1

        # Baseline it: the same run is now clean.
        bl_path = os.path.join(root, ".hslint-baseline.json")
        lint_engine.write_baseline(bl_path, findings)
        baseline = lint_engine.load_baseline(bl_path)
        findings2, expired2 = run(root, baseline=baseline)
        assert new_of(findings2) == []
        assert [f for f in findings2 if f.baselined]
        assert expired2 == []

        # Fix the file: the baseline entry expires.
        (tmp_path / "hyperspace_tpu/dead.py").write_text("X = 1\n")
        findings3, expired3 = run(root, baseline=baseline)
        assert new_of(findings3) == []
        assert len(expired3) == 1
        assert expired3[0].startswith("hygiene:")

    def test_fingerprint_survives_line_drift(self, tmp_path):
        bad = "import os\n\nX = 1\n"
        root = make_repo(tmp_path, {"hyperspace_tpu/dead.py": bad})
        findings, _ = run(root)
        fp = new_of(findings)[0].fingerprint
        # Shift the finding down two lines; the fingerprint is unchanged.
        (tmp_path / "hyperspace_tpu/dead.py").write_text(
            "# a\n# b\nimport os\n\nX = 1\n")
        findings2, _ = run(root)
        assert new_of(findings2)[0].fingerprint == fp


@pytest.mark.quick
class TestCliAndJson:
    def _run_cli(self, *args):
        return subprocess.run(
            [sys.executable, "-m", "hyperspace_tpu.lint", *args],
            capture_output=True, text=True, cwd=REPO_ROOT)

    def test_json_schema_and_exit_codes(self, tmp_path):
        root = make_repo(tmp_path)
        clean = self._run_cli("--root", root, "--json", "--no-baseline")
        assert clean.returncode == 0, clean.stdout + clean.stderr
        payload = json.loads(clean.stdout)
        assert payload["version"] == 1
        assert payload["new_count"] == 0
        assert isinstance(payload["findings"], list)
        assert isinstance(payload["rules"], list)
        assert payload["expired_baseline"] == []

        # Seed a violation: the lane must fail with exit 1 and name it.
        (tmp_path / "hyperspace_tpu" / "seeded.py").write_text(
            'def f(conf):\n'
            '    conf.set("hyperspace.test.fooo", 1)\n')
        seeded = self._run_cli("--root", root, "--json", "--no-baseline")
        assert seeded.returncode == 1
        payload = json.loads(seeded.stdout)
        assert payload["new_count"] >= 1
        finding = [f for f in payload["findings"]
                   if f["rule"] == "conf-registry"][0]
        for field in ("rule", "path", "line", "message", "fingerprint",
                      "baselined"):
            assert field in finding

    def test_unknown_rule_is_usage_error(self, tmp_path):
        root = make_repo(tmp_path)
        r = self._run_cli("--root", root, "--rules", "bogus")
        assert r.returncode == 2
        assert "unknown rule" in r.stderr

    def test_list_rules(self):
        r = self._run_cli("--list-rules")
        assert r.returncode == 0
        for name in ("conf-registry", "telemetry-catalog", "io-seam",
                     "fault-site-registry", "exception-discipline",
                     "lock-discipline", "hygiene"):
            assert name in r.stdout

    def test_nodeps_shim_runs_clean(self):
        # tools/hslint.py must work without importing the engine — the
        # CI lint lane installs nothing (docs/18-static-analysis.md).
        r = subprocess.run(
            [sys.executable, os.path.join("tools", "hslint.py")],
            capture_output=True, text=True, cwd=REPO_ROOT)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "0 new finding(s)" in r.stdout


@pytest.mark.quick
class TestTraceCheck:
    def _write_trace(self, tmp_path, names):
        path = tmp_path / "trace.jsonl"
        with open(path, "w") as f:
            for name in names:
                f.write(json.dumps({"name": name, "duration_ms": 1,
                                    "status": "ok"}) + "\n")
        return str(path)

    def test_complete_trace_passes(self, tmp_path):
        path = self._write_trace(
            tmp_path, lint_catalog.REQUIRED_SPANS)
        # Entries that make every required span name legal.
        entries = list(lint_catalog.REQUIRED_SPANS)
        assert lint_catalog.check_trace(path, entries) == []

    def test_missing_required_span_flagged(self, tmp_path):
        names = [n for n in lint_catalog.REQUIRED_SPANS
                 if n != "serve.request"]
        path = self._write_trace(tmp_path, names)
        problems = lint_catalog.check_trace(
            path, list(lint_catalog.REQUIRED_SPANS))
        assert any("serve.request" in p for p in problems)

    def test_undocumented_span_in_trace_flagged(self, tmp_path):
        names = list(lint_catalog.REQUIRED_SPANS) + ["mystery.span"]
        path = self._write_trace(tmp_path, names)
        problems = lint_catalog.check_trace(
            path, list(lint_catalog.REQUIRED_SPANS))
        assert any("mystery.span" in p for p in problems)

    def test_torn_line_tolerated(self, tmp_path):
        path = self._write_trace(
            tmp_path, lint_catalog.REQUIRED_SPANS)
        with open(path, "a") as f:
            f.write('{"name": "torn')  # SIGTERM mid-write
        assert lint_catalog.check_trace(
            path, list(lint_catalog.REQUIRED_SPANS)) == []

    def test_required_spans_are_in_real_catalog(self):
        # The required list must stay a subset of what docs/16 documents
        # (names the catalog can't match would always fail the smoke).
        ctx = lint_engine.build_context(REPO_ROOT)
        _metrics, spans = lint_catalog.telemetry_catalog(ctx)
        for name in lint_catalog.REQUIRED_SPANS:
            assert any(lint_catalog.name_matches_entry(name, e)
                       for e in spans), name


@pytest.mark.quick
class TestSelfClean:
    def test_repo_is_lint_clean(self):
        """The acceptance gate: the linter over the real repository
        reports zero non-baselined findings (and the checked-in baseline
        carries no stale entries)."""
        baseline = lint_engine.load_baseline(
            os.path.join(REPO_ROOT, lint_engine.BASELINE_NAME))
        findings, expired = lint_engine.run_lint(
            REPO_ROOT, None, baseline)
        new = [f for f in findings if not f.baselined]
        assert new == [], "\n".join(
            f"{f.path}:{f.line}: [{f.rule}] {f.message}" for f in new)
        assert expired == [], expired

    def test_fault_sites_registry_matches_runtime(self):
        from hyperspace_tpu.io import faults

        ctx = lint_engine.build_context(REPO_ROOT)
        sites, _line = lint_catalog.fault_sites(ctx)
        assert sites == set(faults.SITES)

    def test_faultplan_rejects_unknown_site(self):
        from hyperspace_tpu.io import faults

        with pytest.raises(ValueError, match="Unknown fault site"):
            faults.FaultPlan(site="stoer.put", kind="eio")


# ---------------------------------------------------------------------------
# Call graph (lint/callgraph.py)
# ---------------------------------------------------------------------------
CG_A = '''\
from hyperspace_tpu.b import middle


def entry():
    return middle()
'''

CG_B = '''\
from hyperspace_tpu import c


def middle():
    return c.leaf()
'''

CG_C = '''\
import time

from hyperspace_tpu import a


def leaf():
    time.sleep(0.1)
    return a.entry()  # cycle back to the entry point
'''

CG_LOCKED = '''\
import threading

from hyperspace_tpu.b import middle

_lock = threading.Lock()


def locked_entry():
    with _lock:
        return middle()


def unlocked_entry():
    return middle()
'''


@pytest.mark.quick
class TestCallGraph:
    def _graph(self, tmp_path, extra=None):
        from hyperspace_tpu.lint import callgraph

        files = {"hyperspace_tpu/a.py": CG_A,
                 "hyperspace_tpu/b.py": CG_B,
                 "hyperspace_tpu/c.py": CG_C}
        files.update(extra or {})
        root = make_repo(tmp_path, files)
        ctx = lint_engine.build_context(root)
        return callgraph.CallGraph(ctx), ctx

    def test_cross_module_resolution(self, tmp_path):
        g, _ = self._graph(tmp_path)
        entry = g.function("hyperspace_tpu/a.py", "entry")
        assert entry is not None
        sites = g.sites_of(entry.fid)
        assert any(s.targets == ("hyperspace_tpu/b.py::middle",)
                   for s in sites)
        mid_sites = g.sites_of("hyperspace_tpu/b.py::middle")
        assert any("hyperspace_tpu/c.py::leaf" in s.targets
                   for s in mid_sites)

    def test_cycle_tolerant_reachability_with_witness(self, tmp_path):
        from hyperspace_tpu.lint import callgraph

        g, _ = self._graph(tmp_path)
        hit = g.find_path("hyperspace_tpu/a.py::entry",
                          lambda s: s.name == "time.sleep")
        assert hit is not None
        chain, site = hit
        assert site.caller == "hyperspace_tpu/c.py::leaf"
        text = callgraph.describe_chain(g, chain, site)
        assert "entry" in text and "time.sleep()" in text
        # The a -> b -> c -> a cycle must not hang an unsatisfiable scan.
        assert g.find_path("hyperspace_tpu/a.py::entry",
                           lambda s: s.name == "never.matches") is None

    def test_lock_held_context_propagates_to_call_sites(self, tmp_path):
        g, _ = self._graph(
            tmp_path, {"hyperspace_tpu/locked.py": CG_LOCKED})
        locked = [s for s in g.sites_of("hyperspace_tpu/locked.py::"
                                        "locked_entry")
                  if s.name == "middle"]
        unlocked = [s for s in g.sites_of("hyperspace_tpu/locked.py::"
                                          "unlocked_entry")
                    if s.name == "middle"]
        assert locked and locked[0].locks \
            == ("hyperspace_tpu/locked.py:<module>._lock",)
        assert unlocked and unlocked[0].locks == ()

    def test_deadline_scope_propagation(self, tmp_path):
        dl = ("def check(phase=\"\"):\n    pass\n\n\n"
              "def scope(seconds):\n    pass\n")
        caller = ("from hyperspace_tpu.utils import deadline as _dl\n\n\n"
                  "def dispatch():\n    _dl.check(\"node\")\n\n\n"
                  "def outer():\n    return dispatch()\n")
        g, _ = self._graph(tmp_path, {
            "hyperspace_tpu/utils/deadline.py": dl,
            "hyperspace_tpu/exec2.py": caller})
        assert g.reaches(
            "hyperspace_tpu/exec2.py::outer",
            lambda s: s.name.endswith(".check")
            and any("utils/deadline.py" in t for t in s.targets))

    def test_self_method_and_base_class_resolution(self, tmp_path):
        src = ("class Base:\n"
               "    def helper(self):\n        pass\n\n\n"
               "class Impl(Base):\n"
               "    def run(self):\n        self.helper()\n")
        g, _ = self._graph(tmp_path, {"hyperspace_tpu/cls.py": src})
        sites = g.sites_of("hyperspace_tpu/cls.py::Impl.run")
        assert any(s.targets == ("hyperspace_tpu/cls.py::Base.helper",)
                   for s in sites)


# ---------------------------------------------------------------------------
# device-discipline
# ---------------------------------------------------------------------------
DEVICE_OK = '''\
import jax.numpy as jnp

from hyperspace_tpu.execution import sync_guard


def kernel(x):
    y = jnp.cumsum(x)
    total = int(sync_guard.scalar(jnp.sum(y), "t.total"))
    host = sync_guard.pull(y, "t.pull")
    return host, total


def host_only(arr):
    import numpy as np

    return np.asarray(arr)  # parameter: no device taint
'''


@pytest.mark.quick
class TestDeviceDiscipline:
    def _run(self, tmp_path, files):
        root = make_repo(tmp_path, files)
        return new_of(run(root)[0], "device-discipline")

    def test_sanctioned_seams_and_host_params_are_quiet(self, tmp_path):
        assert self._run(
            tmp_path, {"hyperspace_tpu/ops/k.py": DEVICE_OK}) == []

    def test_implicit_scalar_sync_fires(self, tmp_path):
        got = self._run(tmp_path, {"hyperspace_tpu/ops/k.py":
                                   "import jax.numpy as jnp\n\n\n"
                                   "def bad(x):\n"
                                   "    return float(jnp.sum(x))\n"})
        assert any("implicit-sync" in f.ident and "float()" in f.message
                   for f in got)

    def test_asarray_pull_fires(self, tmp_path):
        got = self._run(tmp_path, {"hyperspace_tpu/ops/k.py":
                                   "import jax.numpy as jnp\n"
                                   "import numpy as np\n\n\n"
                                   "def bad(x):\n"
                                   "    y = jnp.sort(x)\n"
                                   "    return np.asarray(y)\n"})
        assert any("sync_guard.pull" in f.message for f in got)

    def test_unattributed_mesh_gather_fires_in_parallel(self, tmp_path):
        """A sharded wrapper in parallel/ whose cross-device gather
        bypasses the attributed seam (raw np.asarray of the sharded
        program's output) must fire; the sync_guard.pull form with a
        site name must stay quiet — the contract the mesh kernels'
        host-gather seam is held to."""
        seeded = ("import functools\n\n"
                  "import jax\n"
                  "import numpy as np\n\n\n"
                  "@functools.partial(jax.jit, static_argnames=('mesh',))\n"
                  "def _program(x, *, mesh):\n"
                  "    return x + 1\n\n\n"
                  "def mesh_gather_bad(x, mesh):\n"
                  "    out = _program(x, mesh=mesh)\n"
                  "    return np.asarray(out)  # unattributed gather\n")
        got = self._run(
            tmp_path, {"hyperspace_tpu/parallel/sharded.py": seeded})
        assert any("implicit-sync" in f.ident
                   and "sync_guard.pull" in f.message for f in got), got
        sanctioned = seeded.replace(
            "    return np.asarray(out)  # unattributed gather\n",
            "    from hyperspace_tpu.execution import sync_guard\n\n"
            "    return sync_guard.pull(out, 'mesh.gather.d0')\n")
        assert self._run(
            tmp_path, {"hyperspace_tpu/parallel/sharded.py":
                       sanctioned}) == []

    def test_interprocedural_taint_through_helper(self, tmp_path):
        src = ("import jax.numpy as jnp\n\n\n"
               "def make(x):\n"
               "    return jnp.cumsum(x)\n\n\n"
               "def bad(x):\n"
               "    y = make(x)\n"
               "    return y.item()\n")
        got = self._run(tmp_path, {"hyperspace_tpu/ops/k.py": src})
        assert any(".item()" in f.message for f in got)

    def test_branching_on_device_value_fires(self, tmp_path):
        src = ("import jax.numpy as jnp\n\n\n"
               "def bad(x):\n"
               "    m = jnp.any(x)\n"
               "    if m:\n"
               "        return 1\n"
               "    return 0\n")
        got = self._run(tmp_path, {"hyperspace_tpu/ops/k.py": src})
        assert any("branching on a device value" in f.message for f in got)

    def test_device_loop_fires(self, tmp_path):
        src = ("import jax.numpy as jnp\n\n\n"
               "def bad(x):\n"
               "    y = jnp.sort(x)\n"
               "    out = 0\n"
               "    for v in y:\n"
               "        out = out + 1\n"
               "    return out\n")
        got = self._run(tmp_path, {"hyperspace_tpu/ops/k.py": src})
        assert any("device-loop" in f.ident for f in got)

    def test_untimed_block_until_ready_fires(self, tmp_path):
        src = ("import jax\n\n\n"
               "def bad(x):\n"
               "    jax.block_until_ready(x)\n"
               "    return x\n")
        got = self._run(tmp_path, {"hyperspace_tpu/ops/k.py": src})
        assert any("untimed-sync" in f.ident for f in got)

    def test_float64_outside_x64_fires_and_inside_is_quiet(self, tmp_path):
        bad = ("import jax.numpy as jnp\n\n\n"
               "def bad(x):\n"
               "    return x.astype(jnp.float64)\n")
        ok = ("import jax.numpy as jnp\n\n"
              "from jax import enable_x64 as "
              "_enable_x64\n\n\n"
              "def good(x):\n"
              "    with _enable_x64():\n"
              "        return x.astype(jnp.float64)\n")
        got = self._run(tmp_path, {"hyperspace_tpu/ops/k.py": bad})
        assert any("float64-literal" in f.ident for f in got)
        assert self._run(tmp_path, {"hyperspace_tpu/ops/k.py": ok}) == []

    def test_jit_conf_read_and_mutable_default_fire(self, tmp_path):
        src = ("import os\n\n"
               "import jax\n\n\n"
               "@jax.jit\n"
               "def bad(x, opts=[]):\n"
               "    flag = os.environ.get(\"HS_FLAG\")\n"
               "    return x\n")
        got = self._run(tmp_path, {"hyperspace_tpu/ops/k.py": src})
        idents = {f.ident.split(":")[0] for f in got}
        assert "jit-unsafe" in idents
        msgs = " ".join(f.message for f in got)
        assert "trace time" in msgs and "mutable default" in msgs

    def test_static_arg_literal_list_fires(self, tmp_path):
        src = ("from functools import partial\n\n"
               "import jax\n\n\n"
               "@partial(jax.jit, static_argnames=(\"ops\",))\n"
               "def kern(x, ops):\n"
               "    return x\n\n\n"
               "def caller(x):\n"
               "    return kern(x, ops=[\"sum\"])\n")
        got = self._run(tmp_path, {"hyperspace_tpu/ops/k.py": src})
        assert any("static arg" in f.message for f in got)

    def test_pragma_suppresses(self, tmp_path):
        src = ("import jax.numpy as jnp\n\n\n"
               "def boundary(x):\n"
               "    # hslint: allow[device-discipline] calibration probe\n"
               "    return float(jnp.sum(x))\n")
        assert self._run(
            tmp_path, {"hyperspace_tpu/ops/k.py": src}) == []

    def test_jitted_bodies_are_exempt_from_sync_checks(self, tmp_path):
        src = ("import jax\n"
               "import jax.numpy as jnp\n\n\n"
               "@jax.jit\n"
               "def kern(x):\n"
               "    if jnp.issubdtype(x.dtype, jnp.floating):\n"
               "        return x\n"
               "    return x * 2\n")
        assert self._run(
            tmp_path, {"hyperspace_tpu/ops/k.py": src}) == []


# ---------------------------------------------------------------------------
# blocking-discipline
# ---------------------------------------------------------------------------
BLOCK_SERVER_OK = SERVER_PY + '''

    def also_good(self):
        with self._lock:
            self._n -= 1
'''


@pytest.mark.quick
class TestBlockingDiscipline:
    def _run(self, tmp_path, files):
        root = make_repo(tmp_path, files)
        return new_of(run(root)[0], "blocking-discipline")

    def test_clean_server_fixture_is_quiet(self, tmp_path):
        assert self._run(tmp_path, {
            "hyperspace_tpu/interop/server.py": BLOCK_SERVER_OK}) == []

    def test_direct_sleep_under_lock_fires(self, tmp_path):
        src = (SERVER_PY +
               "\n    def bad(self):\n"
               "        import time\n\n"
               "        with self._lock:\n"
               "            time.sleep(1)\n")
        got = self._run(tmp_path,
                        {"hyperspace_tpu/interop/server.py": src})
        assert any("lock-held-blocking" in f.ident and
                   "time.sleep" in f.message for f in got)

    def test_transitive_store_put_under_lock_fires_with_chain(
            self, tmp_path):
        helper = ("def persist(store, payload):\n"
                  "    store.put(\"k\", payload)\n")
        src = (SERVER_PY +
               "\n    def bad(self, store):\n"
               "        from hyperspace_tpu.telemetry.sink import persist\n\n"
               "        with self._lock:\n"
               "            persist(store, b\"x\")\n")
        got = self._run(tmp_path, {
            "hyperspace_tpu/interop/server.py": src,
            "hyperspace_tpu/telemetry/sink.py": helper})
        assert any("store .put()" in f.message and
                   "persist" in f.message for f in got)

    def test_same_call_outside_lock_is_quiet(self, tmp_path):
        helper = ("def persist(store, payload):\n"
                  "    store.put(\"k\", payload)\n")
        src = (SERVER_PY +
               "\n    def fine(self, store):\n"
               "        from hyperspace_tpu.telemetry.sink import persist\n\n"
               "        persist(store, b\"x\")\n")
        assert self._run(tmp_path, {
            "hyperspace_tpu/interop/server.py": src,
            "hyperspace_tpu/telemetry/sink.py": helper}) == []

    def test_missing_entry_check_in_execute_node_fires(self, tmp_path):
        dl = ("def check(phase=\"\"):\n    pass\n\n\n"
              "def scope(seconds):\n    pass\n")
        ex = ("from hyperspace_tpu.utils import deadline as _deadline\n\n\n"
              "class Executor:\n"
              "    def execute(self, plan):\n"
              "        out = self._execute_node(plan)\n"
              "        _deadline.check(\"exit\")\n"
              "        return out\n\n"
              "    def _execute_node(self, plan):\n"
              "        return plan\n")
        got = self._run(tmp_path, {
            "hyperspace_tpu/utils/deadline.py": dl,
            "hyperspace_tpu/execution/executor.py": ex})
        assert any(f.ident == "deadline:Executor._execute_node:entry"
                   for f in got)

    def test_missing_exit_check_in_execute_fires(self, tmp_path):
        dl = ("def check(phase=\"\"):\n    pass\n\n\n"
              "def scope(seconds):\n    pass\n")
        ex = ("from hyperspace_tpu.utils import deadline as _deadline\n\n\n"
              "class Executor:\n"
              "    def execute(self, plan):\n"
              "        return self._execute_node(plan)\n\n"
              "    def _execute_node(self, plan):\n"
              "        _deadline.check(\"entry\")\n"
              "        return plan\n")
        got = self._run(tmp_path, {
            "hyperspace_tpu/utils/deadline.py": dl,
            "hyperspace_tpu/execution/executor.py": ex})
        assert any(f.ident == "deadline:Executor.execute:exit"
                   for f in got)

    def test_checked_executor_is_quiet(self, tmp_path):
        dl = ("def check(phase=\"\"):\n    pass\n\n\n"
              "def scope(seconds):\n    pass\n")
        ex = ("from hyperspace_tpu.utils import deadline as _deadline\n\n\n"
              "class Executor:\n"
              "    def execute(self, plan):\n"
              "        out = self._execute_node(plan)\n"
              "        _deadline.check(\"exit\")\n"
              "        return out\n\n"
              "    def _execute_node(self, plan):\n"
              "        _deadline.check(\"entry\")\n"
              "        return plan\n")
        assert self._run(tmp_path, {
            "hyperspace_tpu/utils/deadline.py": dl,
            "hyperspace_tpu/execution/executor.py": ex}) == []

    def test_external_operator_dispatch_fires(self, tmp_path):
        dl = ("def check(phase=\"\"):\n    pass\n\n\n"
              "def scope(seconds):\n    pass\n")
        ex = ("from hyperspace_tpu.utils import deadline as _deadline\n\n\n"
              "class Executor:\n"
              "    def execute(self, plan):\n"
              "        out = self._execute_node(plan)\n"
              "        _deadline.check(\"exit\")\n"
              "        return out\n\n"
              "    def _execute_node(self, plan):\n"
              "        _deadline.check(\"entry\")\n"
              "        return self._execute_scan(plan)\n\n"
              "    def _execute_scan(self, plan):\n"
              "        return plan\n")
        rogue = ("from hyperspace_tpu.execution.executor import Executor\n"
                 "\n\ndef shortcut(plan):\n"
                 "    return Executor._execute_scan(None, plan)\n")
        got = self._run(tmp_path, {
            "hyperspace_tpu/utils/deadline.py": dl,
            "hyperspace_tpu/execution/executor.py": ex,
            "hyperspace_tpu/rogue.py": rogue})
        assert any("bypassing the deadline-checked dispatcher"
                   in f.message for f in got)


# ---------------------------------------------------------------------------
# --fix autofix
# ---------------------------------------------------------------------------
FIXABLE = '''\
import os
import os
import json
import sys


def f(x=[], y=2):
    """Doc."""
    return os.path.join(str(x), str(y), sys.prefix)
'''


@pytest.mark.quick
class TestAutofix:
    def _main(self, argv):
        from hyperspace_tpu.lint.__main__ import main

        return main(argv)

    def test_dry_run_prints_diff_and_writes_nothing(self, tmp_path,
                                                    capsys):
        root = make_repo(tmp_path, {"hyperspace_tpu/mod.py": FIXABLE})
        rc = self._main(["--root", root, "--no-baseline", "--fix",
                         "--dry-run"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "-import os" in out and "+def f(x=None, y=2):" in out
        assert (tmp_path / "hyperspace_tpu/mod.py").read_text() == FIXABLE

    def test_fix_then_relint_is_clean(self, tmp_path, capsys):
        root = make_repo(tmp_path, {"hyperspace_tpu/mod.py": FIXABLE})
        self._main(["--root", root, "--no-baseline", "--fix"])
        capsys.readouterr()
        findings, _ = run(root)
        assert new_of(findings, "hygiene") == []
        fixed = (tmp_path / "hyperspace_tpu/mod.py").read_text()
        assert fixed.count("import os") == 1
        assert "import json" not in fixed
        assert "if x is None:" in fixed and "x = []" in fixed
        # The rewritten module still parses and behaves.
        import ast as _ast

        _ast.parse(fixed)

    def test_fix_refuses_design_findings(self, tmp_path):
        from hyperspace_tpu.lint import fix as fixer

        root = make_repo(tmp_path, {
            "hyperspace_tpu/ops/k.py":
                "import jax.numpy as jnp\n\n\n"
                "def bad(x):\n"
                "    return float(jnp.sum(x))\n"})
        ctx = lint_engine.build_context(root)
        findings, _ = lint_engine.run_lint(root, None, set(), ctx=ctx)
        assert any(f.rule == "device-discipline" for f in findings)
        assert fixer.plan_fixes(ctx, findings) == []

    def test_multi_alias_import_keeps_other_bindings(self, tmp_path):
        src = ("import json, sys\n\n\n"
               "def g():\n"
               "    return sys.prefix\n")
        root = make_repo(tmp_path, {"hyperspace_tpu/mod.py": src})
        self._main(["--root", root, "--no-baseline", "--fix"])
        fixed = (tmp_path / "hyperspace_tpu/mod.py").read_text()
        assert "import sys" in fixed and "json" not in fixed


# ---------------------------------------------------------------------------
# SARIF
# ---------------------------------------------------------------------------
@pytest.mark.quick
class TestSarif:
    def test_sarif_schema_and_exit_codes_unchanged(self, tmp_path,
                                                   capsys):
        from hyperspace_tpu.lint.__main__ import main

        root = make_repo(tmp_path, {
            "hyperspace_tpu/ops/k.py":
                "import jax.numpy as jnp\n\n\n"
                "def bad(x):\n"
                "    return float(jnp.sum(x))\n"})
        out_path = str(tmp_path / "out.sarif")
        rc = main(["--root", root, "--no-baseline", "--sarif", out_path])
        capsys.readouterr()
        assert rc == 1  # exit code contract unchanged by --sarif
        doc = json.loads((tmp_path / "out.sarif").read_text())
        assert doc["version"] == "2.1.0"
        run_obj = doc["runs"][0]
        assert run_obj["tool"]["driver"]["name"] == "hslint"
        results = run_obj["results"]
        assert any(r["ruleId"] == "device-discipline" for r in results)
        loc = results[0]["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"].endswith(".py")
        assert loc["region"]["startLine"] >= 1

    def test_clean_repo_writes_empty_results(self, tmp_path, capsys):
        from hyperspace_tpu.lint.__main__ import main

        root = make_repo(tmp_path)
        out_path = str(tmp_path / "out.sarif")
        rc = main(["--root", root, "--no-baseline", "--sarif", out_path])
        capsys.readouterr()
        assert rc == 0
        doc = json.loads((tmp_path / "out.sarif").read_text())
        assert doc["runs"][0]["results"] == []


# ---------------------------------------------------------------------------
# Seeded-violation must-fail (the CI lint lane's bark check, in-proc)
# ---------------------------------------------------------------------------
@pytest.mark.quick
class TestSeededViolationsMustFail:
    def _rc(self, root, capsys):
        from hyperspace_tpu.lint.__main__ import main

        rc = main(["--root", root, "--no-baseline"])
        capsys.readouterr()
        return rc

    def test_planted_host_sync_fails(self, tmp_path, capsys):
        root = make_repo(tmp_path, {
            "hyperspace_tpu/ops/_seed.py":
                "import jax.numpy as jnp\n\n\n"
                "def seed(x):\n"
                "    return float(jnp.sum(x))\n"})
        assert self._rc(root, capsys) == 1

    def test_planted_lock_held_blocking_call_fails(self, tmp_path,
                                                   capsys):
        root = make_repo(tmp_path, {
            "hyperspace_tpu/telemetry/_seed.py":
                "import threading\n"
                "import time\n\n"
                "_lock = threading.Lock()\n\n\n"
                "def seed():\n"
                "    with _lock:\n"
                "        time.sleep(1.0)\n"})
        assert self._rc(root, capsys) == 1


# ---------------------------------------------------------------------------
# Doctor lint-freshness check
# ---------------------------------------------------------------------------
@pytest.mark.quick
class TestDoctorLintCheck:
    def test_missing_baseline_is_ok(self, tmp_path):
        from hyperspace_tpu.telemetry.doctor import _check_lint

        check = _check_lint(None, path=str(tmp_path / "nope.json"))
        assert check.status == "ok"

    def test_empty_current_baseline_is_ok(self, tmp_path):
        from hyperspace_tpu.lint.rules import CATALOG_VERSION
        from hyperspace_tpu.telemetry.doctor import _check_lint

        p = tmp_path / ".hslint-baseline.json"
        p.write_text(json.dumps({"version": 1,
                                 "catalog_version": CATALOG_VERSION,
                                 "entries": []}))
        assert _check_lint(None, path=str(p)).status == "ok"

    def test_nonempty_baseline_warns_and_publishes_gauge(self, tmp_path):
        from hyperspace_tpu.telemetry import metrics
        from hyperspace_tpu.telemetry.doctor import _check_lint

        p = tmp_path / ".hslint-baseline.json"
        p.write_text(json.dumps({
            "version": 1, "catalog_version": 999,
            "entries": ["hygiene:x.py:dead-import:os"]}))
        check = _check_lint(None, path=str(p))
        assert check.status == "warn"
        assert "grandfathered" in check.summary
        assert float(metrics.snapshot().get("lint.baseline.entries",
                                            0)) == 1.0

    def test_stale_catalog_version_warns(self, tmp_path):
        from hyperspace_tpu.lint.rules import CATALOG_VERSION
        from hyperspace_tpu.telemetry.doctor import _check_lint

        p = tmp_path / ".hslint-baseline.json"
        p.write_text(json.dumps({"version": 1,
                                 "catalog_version": CATALOG_VERSION - 1,
                                 "entries": []}))
        check = _check_lint(None, path=str(p))
        assert check.status == "warn"
        assert "catalog" in check.summary

    def test_doctor_runs_the_lint_check_never_raising(self, tmp_path):
        """The real doctor() includes the lint check, graded like the
        other seven (the real repo's baseline is empty -> ok)."""
        from hyperspace_tpu import HyperspaceSession
        from hyperspace_tpu.telemetry.doctor import doctor

        session = HyperspaceSession(system_path=str(tmp_path / "ix"))
        report = doctor(session)
        lint_check = report.check("lint")
        assert lint_check is not None
        assert lint_check.status == "ok"
