"""Time in hybrid scans' appended branches per served query, in ms: the
whole of each ``exec.hybrid_appended`` span (listing, decode and bucket
routing of the source files the index does not cover yet) in the
window's ``serve.request`` trees."""

from span_totals import per_served_query_ms


def read(ctx):
    return per_served_query_ms(ctx, ["exec.hybrid_appended"])
