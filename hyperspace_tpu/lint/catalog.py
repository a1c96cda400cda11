"""Registry parsers: the single-source-of-truth artifacts the rules
check code against.

Everything here reads the checked-in sources with ``ast`` / text
parsing — never imports — so the registries are exactly what review
sees, not what a particular interpreter resolved.

  - conf registry: module-level ``NAME = "hyperspace..."`` constants in
    ``hyperspace_tpu/config.py`` plus its ``_FIELD_BY_KEY`` wiring
  - documented conf keys: the docs/02-configuration.md tables
  - telemetry catalog: the docs/16-observability.md metric and span
    tables (placeholder rows like ``rule.<slug>.applied`` become
    segment wildcards)
  - fault sites: the ``SITES`` tuple in ``hyperspace_tpu/io/faults.py``
  - wire codes: the ``ERR_* = "..."`` constants in
    ``hyperspace_tpu/interop/server.py``
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, List, Optional, Sequence, Set, Tuple

from hyperspace_tpu.lint.engine import LintContext

CONFIG_PATH = "hyperspace_tpu/config.py"
CONF_DOC_PATH = "docs/02-configuration.md"
OBS_DOC_PATH = "docs/16-observability.md"
FAULTS_PATH = "hyperspace_tpu/io/faults.py"
SERVER_PATH = "hyperspace_tpu/interop/server.py"

_CONF_KEY_RE = re.compile(r"^hyperspace\.[A-Za-z0-9_.]+$")
_DOC_KEY_RE = re.compile(r"`(hyperspace\.[A-Za-z0-9_.]+)`")


# ---------------------------------------------------------------------------
# Conf registry (config.py + docs/02)
# ---------------------------------------------------------------------------
def conf_registry(ctx: LintContext):
    """``(declared, wired, line_of, field_of)`` from config.py:
    ``declared`` maps key string -> constant name, ``wired`` is the set
    of key strings reachable through ``_FIELD_BY_KEY``, ``line_of`` maps
    key -> line, ``field_of`` maps key -> dataclass field name."""
    src = ctx.file(CONFIG_PATH)
    declared: Dict[str, str] = {}
    line_of: Dict[str, int] = {}
    wired: Set[str] = set()
    field_of: Dict[str, str] = {}
    if src is None or src.tree is None:
        return declared, wired, line_of, field_of
    const_to_key: Dict[str, str] = {}
    for node in src.tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name):
            value = node.value
            if isinstance(value, ast.Constant) and \
                    isinstance(value.value, str) and \
                    _CONF_KEY_RE.match(value.value):
                name = node.targets[0].id
                declared[value.value] = name
                line_of[value.value] = node.lineno
                const_to_key[name] = value.value
    # _FIELD_BY_KEY lives inside the dataclass body; keys are Name refs
    # to the module constants (or raw strings), values are field names.
    for node in ast.walk(src.tree):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and node.targets[0].id == "_FIELD_BY_KEY" \
                and isinstance(node.value, ast.Dict):
            for k, v in zip(node.value.keys, node.value.values):
                key = None
                if isinstance(k, ast.Name) and k.id in const_to_key:
                    key = const_to_key[k.id]
                elif isinstance(k, ast.Constant) and \
                        isinstance(k.value, str):
                    key = k.value
                if key is None:
                    continue
                wired.add(key)
                if isinstance(v, ast.Constant) and \
                        isinstance(v.value, str):
                    field_of[key] = v.value
    return declared, wired, line_of, field_of


def documented_conf_keys(ctx: LintContext) -> Dict[str, int]:
    """Conf keys documented in docs/02 TABLE ROWS (first cell), key ->
    line number."""
    text = ctx.read_doc(CONF_DOC_PATH) or ""
    out: Dict[str, int] = {}
    for i, line in enumerate(text.splitlines(), start=1):
        if not line.lstrip().startswith("|"):
            continue
        first_cell = line.split("|")[1] if line.count("|") >= 2 else ""
        for m in _DOC_KEY_RE.finditer(first_cell):
            out.setdefault(m.group(1), i)
    return out


# ---------------------------------------------------------------------------
# Telemetry catalog (docs/16)
# ---------------------------------------------------------------------------
_TOKEN_RE = re.compile(r"`([A-Za-z0-9_.<>-]+)`")
_PLACEHOLDER_SEG_RE = re.compile(r"^<[A-Za-z0-9_]+>$")


def _expand_cell_tokens(cell: str) -> List[str]:
    """Backticked names from one table cell, expanding the catalog's
    leading-dot shorthand: ``advisor.capture.dropped`` / ``.errors``
    means advisor.capture.errors (the shorthand replaces that many
    trailing segments of the cell's first full token)."""
    tokens = _TOKEN_RE.findall(cell)
    out: List[str] = []
    anchor: Optional[str] = None
    for tok in tokens:
        if tok.startswith("."):
            if anchor is None:
                continue  # malformed; the reverse check will catch drift
            short = tok[1:].split(".")
            base = anchor.split(".")
            if len(short) >= len(base):
                continue
            out.append(".".join(base[:-len(short)] + short))
        else:
            out.append(tok)
            if anchor is None:
                anchor = tok
    return out


def _table_first_cells(text: str, start_heading: str,
                       stop_prefix: str = "#") -> List[Tuple[str, int]]:
    """(first-cell, line) of each table row between ``start_heading`` and
    the next heading."""
    lines = text.splitlines()
    out: List[Tuple[str, int]] = []
    in_section = False
    for i, line in enumerate(lines, start=1):
        if line.strip().startswith(start_heading):
            in_section = True
            continue
        if in_section and line.startswith(stop_prefix):
            break
        if in_section and line.lstrip().startswith("|") \
                and line.count("|") >= 2:
            cell = line.split("|")[1]
            if set(cell.strip()) <= {"-", ":", " "}:
                continue  # separator row
            out.append((cell, i))
    return out


def telemetry_catalog(ctx: LintContext):
    """``(metrics, spans)``: each a dict of catalog name (may contain
    ``<placeholder>`` segments) -> docs/16 line number."""
    text = ctx.read_doc(OBS_DOC_PATH) or ""
    metrics: Dict[str, int] = {}
    spans: Dict[str, int] = {}
    for cell, line in _table_first_cells(text, "| Metric "):
        for tok in _expand_cell_tokens(cell):
            metrics.setdefault(tok, line)
    for cell, line in _table_first_cells(text, "| Span "):
        for tok in _expand_cell_tokens(cell):
            spans.setdefault(tok, line)
    return metrics, spans


_MD_LINK_RE = re.compile(r"\[([^\]]+)\]\([^)]*\)")


def metric_help_entries() -> List[Tuple[str, str]]:
    """RUNTIME view of the docs/16 metric catalog for the Prometheus
    ``# HELP`` lines (``telemetry/metrics.render_prometheus``):
    ``(name-pattern, help-text)`` pairs, parsed from the same table the
    telemetry-catalog lint rule enforces — one registry, two consumers.
    Reads the repo-relative docs (no :class:`LintContext` needed); an
    installed package without ``docs/`` simply yields no entries."""
    root = __file__
    for _ in range(3):  # lint/catalog.py -> lint -> hyperspace_tpu -> repo
        root = os.path.dirname(root)
    try:
        with open(os.path.join(root, OBS_DOC_PATH),
                  "r", encoding="utf-8") as f:
            text = f.read()
    except OSError:
        return []
    out: List[Tuple[str, str]] = []
    lines = text.splitlines()
    for cell, lineno in _table_first_cells(text, "| Metric "):
        row = lines[lineno - 1]
        cells = [c.strip() for c in row.split("|")]
        doc = cells[-2] if len(cells) >= 4 else ""
        doc = _MD_LINK_RE.sub(r"\1", doc).replace("`", "")
        doc = " ".join(doc.split())
        for tok in _expand_cell_tokens(cell):
            out.append((tok, doc))
    return out


def _segs(name: str) -> List[str]:
    return name.split(".")


def name_matches_entry(name: str, entry: str) -> bool:
    """Does a concrete-or-pattern usage name match a catalog entry?
    ``name`` segments of ``\\x00``-bearing text are wildcards (from
    f-strings); entry segments like ``<slug>`` are placeholders."""
    a, b = _segs(name), _segs(entry)
    if len(a) != len(b):
        return False
    for ua, ub in zip(a, b):
        if "\x00" in ua or _PLACEHOLDER_SEG_RE.match(ub):
            continue
        if ua != ub:
            return False
    return True


def entry_concrete(entry: str) -> bool:
    return not any(_PLACEHOLDER_SEG_RE.match(s) for s in _segs(entry))


# ---------------------------------------------------------------------------
# Fault sites (io/faults.py) and wire codes (interop/server.py)
# ---------------------------------------------------------------------------
def fault_sites(ctx: LintContext) -> Tuple[Set[str], int]:
    """The declared fault-site registry: the ``SITES`` tuple in
    io/faults.py, plus the line it is declared on."""
    src = ctx.file(FAULTS_PATH)
    if src is None or src.tree is None:
        return set(), 0
    for node in src.tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and node.targets[0].id == "SITES":
            value = node.value
            if isinstance(value, (ast.Tuple, ast.List)):
                sites = {e.value for e in value.elts
                         if isinstance(e, ast.Constant)
                         and isinstance(e.value, str)}
                return sites, node.lineno
    return set(), 0


def wire_codes(ctx: LintContext) -> Set[str]:
    """The ERR taxonomy: values of module-level ``ERR_* = "..."``
    constants in interop/server.py."""
    src = ctx.file(SERVER_PATH)
    out: Set[str] = set()
    if src is None or src.tree is None:
        return out
    for node in src.tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and node.targets[0].id.startswith("ERR_") \
                and isinstance(node.value, ast.Constant) \
                and isinstance(node.value.value, str):
            out.add(node.value.value)
    return out


# ---------------------------------------------------------------------------
# Trace span check
# ---------------------------------------------------------------------------
# Span kinds the chip benchmark's per-layer metrics read
# (benchmark/layer_metrics/): a trace of a served Q3, a hybrid-scan Q3 and
# a full refresh must hold every one (tests/test_measured_spans.py).
# Kept next to the catalog parser so the list and the docs/16 taxonomy
# are checked together (lint --check-catalog --trace <file>).
REQUIRED_SPANS = (
    "serve.request",
    "source.snapshot",
    "exec.scan",
    "scan.files",
    "io.read",
    "exec.hybrid_appended",
    "exec.filter",
    "exec.join",
    "exec.aggregate",
    "device.sync",
    "build.phase.read",
    "build.phase.prefetch",
    "build.phase.spill_route",
    "build.phase.spill_finish",
    "build.phase.write",
    "build.phase.validate",
    "build.phase.commit",
)


def check_trace(path: str, span_entries: Sequence[str]) -> List[str]:
    """Problems with a JSONL trace: required span kinds missing, and
    span names present in the trace but absent from the docs/16
    taxonomy."""
    import json as _json

    seen: Set[str] = set()

    def walk(span: dict) -> None:
        name = span.get("name")
        if isinstance(name, str):
            seen.add(name)
        for child in span.get("children", ()) or ():
            if isinstance(child, dict):
                walk(child)

    try:
        with open(path, "r", encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    walk(_json.loads(line))
                except ValueError:
                    continue  # torn line (SIGTERM mid-write) — tolerated
    except OSError as e:
        return [f"cannot read trace {path}: {e}"]

    problems = [f"required span kind missing from trace: {name}"
                for name in REQUIRED_SPANS if name not in seen]
    for name in sorted(seen):
        if not any(name_matches_entry(name, e) for e in span_entries):
            problems.append(
                f"trace span {name!r} is not in the docs/16 span taxonomy")
    return problems
