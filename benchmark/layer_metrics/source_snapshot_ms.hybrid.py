"""Time resolving versioned sources' snapshots per served query, in ms:
the whole of each ``source.snapshot`` span (the log listing that fixes
a Delta table's version, and the replay that gives its files) in the
window's ``serve.request`` trees."""

from span_totals import per_served_query_ms


def read(ctx):
    return per_served_query_ms(ctx, ["source.snapshot"])
