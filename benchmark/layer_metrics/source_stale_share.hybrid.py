"""Plan-cache entries dropped because a source's version moved, per
served query, in % (counter ``serve.plan_cache.source_stale``,
execution/plan_cache.py); None where the program resolves no source
snapshot (no ``source.snapshot`` span), so it has no such counter."""

from layer import counter_delta, served_requests
from span_totals import total_ms


def read(ctx):
    served = served_requests(ctx)
    _ms, snapshots = total_ms(ctx.layer["spans"], ["source.snapshot"])
    if not served or not snapshots:
        return None
    return 100.0 * counter_delta(ctx, "serve.plan_cache.source_stale") / served
