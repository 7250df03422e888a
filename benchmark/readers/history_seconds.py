"""Seconds per fit from the coordinate-descent history (each entry's clock
stops after a fetch of that coordinate's scores): one coordinate's updates,
or with ``residual`` what of a fit's seconds no coordinate update holds
(initial scores, residual sums, validation evaluation)."""


def read(ctx, coordinate=None, residual=False):
    fits = [f for f in ctx["fits"] if f["ok"]]
    if not fits:
        return None
    if residual:
        held = sum(s["seconds"] for f in fits for s in f["steps"])
        return (sum(f["end"] - f["start"] for f in fits) - held) / len(fits)
    steps = [s for f in fits for s in f["steps"]
             if s["coordinate"] == coordinate]
    if not steps:
        return None
    return sum(s["seconds"] for s in steps) / len(fits)
