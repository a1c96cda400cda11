"""The ``resident_side_share.analytic`` reader on planted counter deltas."""

import pytest

import harness


def _read(before, after):
    ctx = harness.Ctx(layer={"metrics_before": before,
                             "metrics_after": after})
    return harness.layer_reader("resident_side_share.analytic")(ctx)


@pytest.mark.parametrize("before,after,want", [
    ({}, {}, None),  # a program without the counters
    ({"exec.join_agg.sides": 4.0}, {"exec.join_agg.sides": 4.0}, None),
    ({"exec.join_agg.sides": 2.0, "exec.join_agg.sides_resident": 0.0},
     {"exec.join_agg.sides": 10.0, "exec.join_agg.sides_resident": 6.0},
     75.0),
    ({}, {"exec.join_agg.sides": 8.0}, 0.0),
])
def test_share_of_sides_served_from_hbm(before, after, want):
    got = _read(before, after)
    assert got == (None if want is None else pytest.approx(want))
