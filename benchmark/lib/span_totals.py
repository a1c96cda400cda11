"""Whole durations of named program spans (``layer.self_ms`` gives self
time instead), for the per-layer readers that count a span with all
that runs inside it."""

from __future__ import annotations

from typing import Iterable, Optional, Tuple


def total_ms(roots, names: Iterable[str]) -> Tuple[float, int]:
    """(summed duration in ms, number of spans) of the spans named
    ``names`` under the finished root spans."""
    names = set(names)
    total, count = 0.0, 0
    for root in roots:
        for s in root.walk():
            if s.name in names:
                total += s.duration_ms
                count += 1
    return total, count


def per_served_query_ms(ctx, names: Iterable[str]) -> Optional[float]:
    """ms per served query inside the spans ``names``, over the window's
    ``serve.request`` trees; None with no served query, or where the
    program opens no such span."""
    served = [r for r in ctx.layer.get("spans", [])
              if r.name == "serve.request"]
    ms, count = total_ms(served, names)
    return ms / len(served) if served and count else None
