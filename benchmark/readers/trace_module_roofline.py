"""A family of launched device programs' share of its roofline: the least
time the chip could take for the work one fit requires of them (per
coordinate the larger of operations / peak FLOP/s and bytes / peak bytes/s,
from a counts function of the coordinate's shape and of the per-fit growth
of the program's counters ``<prefix>.<coordinate>.<name>`` over the
window), over the summed device seconds per fit of the ``XLA Modules``
launches whose name matches ``pattern`` (``readers/trace_module.py``).
A program without the counters, or a trace without the launches: nothing."""

import importlib

from benchmark.readers import trace_module


def read(ctx, pattern, counts, prefix, counters, kind):
    peaks, marks = ctx.get("peaks"), ctx["counters"]
    fits = [f for f in ctx["fits"] if f["ok"]]
    if (peaks is None or not fits or "window_start" not in marks
            or "window_end" not in marks):
        return None
    seconds = trace_module.read(ctx, pattern=pattern)
    if not seconds:
        return None
    fn = importlib.import_module("benchmark.counts." + counts)
    least = 0.0
    for name, shape in ctx["shapes"]["coordinates"].items():
        if shape.get("kind") != kind:
            continue
        grown = []
        for c in counters:
            key = f"{prefix}.{name}.{c}"
            if key not in marks["window_end"]:
                return None
            grown.append((marks["window_end"][key]
                          - marks["window_start"].get(key, 0)) / len(fits))
        flops, nbytes = fn.per_fit(shape, *grown)
        least += max(flops / peaks["flops_per_s"],
                     nbytes / peaks["bytes_per_s"])
    if least <= 0:
        return None
    return 100.0 * least / seconds
