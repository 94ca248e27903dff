"""Host time and stats of the program's own spans, per request of the
window.

The program opens a ``jax.profiler.TraceAnnotation`` named ``sparse.*``
around every stage of a request (``src/repro/core/spans.py``); they
share the profiler's clock with the harness's ``bench.request``.  A
stage's *self time* is its span's duration less the part of it that
other ``sparse.*`` spans inside it cover, so the plan LRU lookup does
not count the planning it wraps, nor the executable lookup the compile.
Every cell has one caller, so the spans inside a request are its own.
A span's stats (``bytes`` of ``sparse.upload``, ``hit`` of
``sparse.plan_key``) are read per request by :func:`stat_per_request`.
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


def _by_request(ctx) -> list:
    """The program's spans inside each request of the window, one list
    per request."""
    program = sorted((h for h in ctx.trace.host if h.name.startswith(PREFIX)),
                     key=lambda h: h.start)
    starts = [h.start for h in program]
    return [_inside(program, starts, r.start, r.end)
            for r in ctx.requests()]


def self_ms(ctx, names) -> float | None:
    """Self time of the spans named ``names`` inside the window's
    requests, in ms per request; ``None`` where there is none."""
    reqs = _by_request(ctx)
    total, found = 0.0, False
    for inner in reqs:
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


def stat_per_request(ctx, names, key: str) -> float | None:
    """Stat ``key`` of the spans named ``names`` inside the window's
    requests, summed and divided by the requests; ``None`` where no
    such span carries it."""
    reqs = _by_request(ctx)
    values = [s.stats[key] for inner in reqs for s in inner
              if s.name in names and key in s.stats]
    if not values:
        return None
    return sum(values) / len(reqs)
