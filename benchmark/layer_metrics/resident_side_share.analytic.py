"""Share (%) of the fused join-aggregate's sides in the window that were
served from HBM with none of their files opened (counters
``exec.join_agg.sides_resident`` over ``exec.join_agg.sides``,
execution/executor.py); None where the window took up no side, as with
a program that has no such counters."""

from layer import counter_delta


def read(ctx):
    sides = counter_delta(ctx, "exec.join_agg.sides")
    if not sides:
        return None
    return 100.0 * counter_delta(ctx, "exec.join_agg.sides_resident") / sides
