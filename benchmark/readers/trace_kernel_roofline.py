"""A kernel family's share of its roofline, from the device trace: the
least time the chip could take for the calls seen (per call the larger of
operations / peak FLOP/s and bytes / peak bytes/s, from a counts function
of the layout's shapes) over the summed device time of the events whose
names match ``pattern``. Nothing matched -> nothing returned."""

import importlib

from benchmark import tracing


def read(ctx, pattern, counts, coordinate):
    trace = ctx.get("trace")
    shape = ctx["shapes"]["coordinates"].get(coordinate)
    if (trace is None or not trace.devices or shape is None
            or "T" not in shape or ctx["peaks"] is None):
        return None
    fn = importlib.import_module("benchmark.counts." + counts)
    flops, nbytes = fn.per_call(shape)
    peaks = ctx["peaks"]
    least = max(flops / peaks["flops_per_s"], nbytes / peaks["bytes_per_s"])
    shares = []
    for events in trace.devices.values():
        seconds, calls = tracing.kernel_seconds(events, pattern)
        if calls and seconds > 0:
            shares.append(100.0 * calls * least / seconds)
    ctx.setdefault("notes", {})[f"{coordinate}.kernel_bound"] = (
        "flops" if flops / peaks["flops_per_s"]
        > nbytes / peaks["bytes_per_s"] else "bytes")
    return sum(shares) / len(shares) if shares else None
