"""Time the host waits on the device per served query, in ms: the
``device.sync`` spans (the blocking device-to-host conversions at the
program's sync seams) in the window's ``serve.request`` trees."""

from span_totals import per_served_query_ms


def read(ctx):
    return per_served_query_ms(ctx, ["device.sync"])
