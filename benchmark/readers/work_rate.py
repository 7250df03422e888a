"""All the work of the window over all its measured seconds. A unit's work is
what its driver counted for it: for a trainer, training rows x the
coordinate-descent iterations of one whole fit."""


def read(ctx):
    done = [f for f in ctx["fits"] if f["ok"]]
    if not done or ctx["window_s"] <= 0:
        return None
    return sum(f["work"] for f in done) / ctx["window_s"]
