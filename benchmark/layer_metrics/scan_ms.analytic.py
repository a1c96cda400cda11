"""Time in the executor's scans per served query, in ms: the whole of
each ``exec.scan`` span (file resolution and stats ``scan.files``,
decode ``io.read``, the cache fingerprint ``scan.identity``) in the
window's ``serve.request`` trees."""

from span_totals import per_served_query_ms


def read(ctx):
    return per_served_query_ms(ctx, ["exec.scan"])
