"""Seconds of the program's set-up spans (``telemetry.finished_spans()`` of
this process): every span whose whole name matches ``span`` and that has no
ancestor matching it, among those that start before the FIRST ``root`` span
of the process (a span with no parent; the warm-up unit) ends. Nested
matches count once, through their outermost. No root, or no such span:
nothing."""

from __future__ import annotations

import re


def first_root_end(spans, root: str):
    """End (tracer seconds) of the earliest finished parentless ``root``
    span, or None."""
    roots = [s for s in spans
             if s.name == root and s.parent_id is None and s.dur is not None]
    if not roots:
        return None
    first = min(roots, key=lambda s: s.ts)
    return first.ts + first.dur


def outermost(spans, pattern: str) -> list:
    """The finished spans matching ``pattern`` with no matching ancestor."""
    match = re.compile(pattern).fullmatch
    by_id = {s.span_id: s for s in spans}
    out = []
    for s in spans:
        if s.dur is None or not match(s.name):
            continue
        parent = by_id.get(s.parent_id)
        while parent is not None and not match(parent.name):
            parent = by_id.get(parent.parent_id)
        if parent is None:
            out.append(s)
    return out


def read(ctx, span, root="coordinate_descent"):
    from photon_ml_tpu import telemetry

    spans = telemetry.finished_spans()
    end = first_root_end(spans, root)
    if end is None:
        return None
    found = [s for s in outermost(spans, span) if s.ts < end]
    if not found:
        return None
    return float(sum(s.dur for s in found))
