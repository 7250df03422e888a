"""Device seconds of one kernel family in the traced unit: the summed
durations of the ``XLA Ops`` events whose names match ``pattern`` (a
pallas_call's ``name`` starts its events' names), averaged over the devices
that ran any. No trace, or nothing matched: nothing returned."""

from benchmark import tracing


def read(ctx, pattern):
    trace = ctx.get("trace")
    if trace is None or not trace.devices:
        return None
    seconds = [
        s for s, calls in (
            tracing.kernel_seconds(events, pattern)
            for events in trace.devices.values())
        if calls]
    return sum(seconds) / len(seconds) if seconds else None
