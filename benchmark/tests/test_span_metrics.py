"""The readers of the program's device-sync, scan and index-log spans,
on recorded span trees: ``test_layer_metrics``'s served request with
the spans the program opens inside it, and refresh actions with their
``build.phase.*`` spans."""

import pytest

import harness
from test_layer_metrics import _request, _span


def _q3_request(files_ms, ident_ms, filter_ms, sync_ms):
    """A served Q3 as the program traces it: the scan's file resolution
    and fingerprint beside its decode, and the filter and the device
    waits inside the aggregate."""
    req = _request(2.0, 10.0, 4.0, 30.0, 5.0 + filter_ms + sync_ms)
    execute = req.children[1]
    scan, _join, agg = execute.children
    scan.children += [_span("scan.files", files_ms),
                      _span("scan.identity", ident_ms)]
    agg.children += [_span("exec.filter", filter_ms,
                           _span("device.sync", sync_ms)),
                     _span("device.sync", sync_ms)]
    return req


def _refresh(validate_ms, commit_ms):
    """One refresh action: its protocol phases on its own thread."""
    return _span("action.RefreshAction", 500.0,
                 _span("build.phase.validate", validate_ms),
                 _span("build.phase.commit", commit_ms / 2),
                 _span("build.phase.plan", 1.0),
                 _span("build.phase.commit", commit_ms / 2))


@pytest.fixture
def ctx():
    layer = {
        "spans": [_q3_request(1.0, 2.0, 8.0, 4.0),
                  _q3_request(3.0, 4.0, 10.0, 6.0),
                  _refresh(100.0, 40.0), _refresh(200.0, 60.0),
                  # A route worker's phase: a root of its own.
                  _span("build.phase.spill_route", 70.0)],
        "cycles": [{"rows": 1000, "reports": []}],
    }
    return harness.Ctx(layer=layer)


def read(name, ctx):
    return harness.layer_reader(name)(ctx)


@pytest.mark.parametrize("name,want", [
    # device.sync: (4 + 4) and (6 + 6) ms over two served queries.
    ("device_wait_ms.analytic", 10.0),
    # Each exec.scan's whole duration, its children included.
    ("scan_ms.analytic", 10.0),
    # validate + commit of both refreshes, over one cycle, in s.
    ("index_log_s.refresh", 0.4),
    # The operators' host time: the filter and the device waits inside
    # exec.aggregate are its children now, so only 30 + 5 ms remain.
    ("join_agg_ms.analytic", 35.0),
])
def test_span_readers(ctx, name, want):
    assert read(name, ctx) == pytest.approx(want)


@pytest.mark.parametrize("name,empty", [
    ("device_wait_ms.analytic", {"spans": []}),
    ("scan_ms.analytic", {"spans": []}),
    ("index_log_s.refresh", {"cycles": []}),
])
def test_window_with_nothing_served_or_refreshed_gives_none(
        ctx, name, empty):
    ctx.layer.update(empty)
    assert read(name, ctx) is None


@pytest.mark.parametrize("name,absent", [
    ("device_wait_ms.analytic", "device.sync"),
    ("index_log_s.refresh", "build.phase.validate"),
])
def test_program_without_the_spans_gives_none(ctx, name, absent):
    """A program that opens no such span (the commit before the spans
    existed) reads as nothing, not as zero."""
    drop = {absent, "build.phase.commit"}

    def prune(s):
        s.children = [prune(c) for c in s.children if c.name not in drop]
        return s

    ctx.layer["spans"] = [prune(r) for r in ctx.layer["spans"]]
    assert read(name, ctx) is None
