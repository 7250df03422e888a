"""Set-up that no reading names: ``setup_s`` less the program's import
seconds (counter ``counter`` at ``setup_end``), less the driver's own
``driver_spans`` (its data draw), less the wall time the program's root
spans (spans with no parent) cover from the start of the process to the
end of its FIRST ``root`` span (the warm-up unit): dataset assembly, the
coordinates' build, the warm-up fit. Overlapping roots (another thread's)
count once. What is left is the interpreter's start, the runtime's start,
and the driver's glue between the program's calls. A program without the
counter, the root or the driver's spans: nothing."""

from __future__ import annotations

from benchmark.readers import setup_span_seconds


def covered(intervals) -> float:
    """Seconds the union of ``(start, end)`` intervals covers."""
    total, reach = 0.0, None
    for lo, hi in sorted(intervals):
        if reach is None or lo > reach:
            total += hi - lo
            reach = hi
        elif hi > reach:
            total += hi - reach
            reach = hi
    return total


def read(ctx, counter, driver_spans, root="coordinate_descent"):
    from photon_ml_tpu import telemetry

    mark = ctx["counters"].get("setup_end", {})
    if counter not in mark or any(s not in ctx["spans"] for s in driver_spans):
        return None
    spans = telemetry.finished_spans()
    end = setup_span_seconds.first_root_end(spans, root)
    if end is None:
        return None
    roots = [(s.ts, s.ts + s.dur) for s in spans
             if s.parent_id is None and s.dur is not None and s.ts < end]
    return float(ctx["setup_s"] - mark[counter]
                 - sum(ctx["spans"][s] for s in driver_spans)
                 - covered(roots))
