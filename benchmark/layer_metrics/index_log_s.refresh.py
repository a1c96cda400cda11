"""Seconds per refresh cycle in the index-log protocol: the
``build.phase.validate`` spans (the source diff) and the
``build.phase.commit`` spans (begin and end entries, the log entry's
content and signature) of every refresh in the window."""

from span_totals import total_ms

PHASES = ("build.phase.validate", "build.phase.commit")


def read(ctx):
    cycles = ctx.layer.get("cycles") or []
    ms, count = total_ms(ctx.layer.get("spans", []), PHASES)
    return ms / 1000.0 / len(cycles) if cycles and count else None
