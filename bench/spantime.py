"""Host time of the program's own spans, per request of the window.

The program opens a ``jax.profiler.TraceAnnotation`` named ``sparse.*``
around every stage of a request (``src/repro/core/spans.py``); they
share the profiler's clock with the harness's ``bench.request``.  A
stage's *self time* is its span's duration less the part of it that
other ``sparse.*`` spans inside it cover, so the plan LRU lookup does
not count the planning it wraps, nor the executable lookup the compile.
Every cell has one caller, so the spans inside a request are its own.
"""
from __future__ import annotations

import bisect

from bench import tracereduce

#: the prefix of every span the program opens
PREFIX = "sparse."


def _inside(spans: list, starts: list, lo: float, hi: float) -> list:
    """The spans of ``spans`` (sorted by start) that lie in ``[lo, hi]``."""
    i = bisect.bisect_left(starts, lo)
    j = bisect.bisect_right(starts, hi)
    return [s for s in spans[i:j] if s.end <= hi]


def self_ms(ctx, names) -> float | None:
    """Self time of the spans named ``names`` inside the window's
    requests, in ms per request; ``None`` where there is none."""
    reqs = ctx.requests()
    program = sorted((h for h in ctx.trace.host if h.name.startswith(PREFIX)),
                     key=lambda h: h.start)
    starts = [h.start for h in program]
    total, found = 0.0, False
    for r in reqs:
        inner = _inside(program, starts, r.start, r.end)
        inner_starts = [h.start for h in inner]
        for s in inner:
            if s.name not in names:
                continue
            found = True
            nested = [(h.start, h.end) for h in
                      _inside(inner, inner_starts, s.start, s.end)
                      if h is not s]
            total += s.dur - tracereduce.covered(
                tracereduce.union(nested), s.start, s.end)
    if not found:
        return None
    return total / len(reqs) / 1e6
