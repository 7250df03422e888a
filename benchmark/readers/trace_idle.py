"""Share of the traced unit in which no operation ran on the device:
1 - union of the device-op intervals over the unit's span, averaged over
the chips used (the harness takes both from the trace for the result line's
``device``)."""


def read(ctx):
    traced = ctx.get("traced")
    if not traced or not traced["busy_s"] > 0:
        return None
    return 100.0 * (1.0 - traced["busy_s"] / traced["window_s"])
