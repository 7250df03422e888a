"""The whole unit's share of the chip's peak: the least time the chip could
take for every pass the algorithm requires in one fit (a counts function of
the shapes and of the solver iterations the program's trackers report),
over the measured seconds per fit."""

import importlib


def read(ctx, counts):
    fits = [f for f in ctx["fits"] if f["ok"]]
    if not fits or ctx["peaks"] is None:
        return None
    fn = importlib.import_module("benchmark.counts." + counts)
    peaks = ctx["peaks"]
    least = 0.0
    for flops, nbytes in fn.per_fit(ctx["shapes"], fits[-1]["steps"]):
        least += max(flops / peaks["flops_per_s"],
                     nbytes / peaks["bytes_per_s"])
    seconds = sum(f["end"] - f["start"] for f in fits) / len(fits)
    if least <= 0 or seconds <= 0:
        return None
    return 100.0 * least / seconds / ctx["chips"]
