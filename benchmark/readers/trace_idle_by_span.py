"""Device idle seconds of the traced fit that the program's spans do NOT
explain: gaps between device ops inside ``photon:coordinate_descent`` whose
midpoint no LEAF span of the program holds (the host was between spans: the
CD loop's own bookkeeping). Idle seconds by leaf span go into the result
line's ``notes`` (``idle_by_span``), so a gap has a name: ``update`` is a
wait inside the solve's dispatch, ``validate`` the evaluators' eager ops.
The device plane is first moved onto the host's clock (``program_trace``:
its stamps run a millisecond or two ahead), and that lead goes into
``notes`` too. Averaged over the chips, per fit. No spans in the trace:
nothing."""

from benchmark import program_trace


def read(ctx):
    trace = program_trace.load(ctx)
    devices = getattr(ctx.get("trace"), "devices", None)
    if trace is None or not devices:
        return None
    units = trace.units()
    if not units:
        return None
    by_span: dict = {}
    for plane, events in devices.items():
        events = trace.on_host_clock(plane, events)
        for unit in units:
            for name, s in program_trace.idle_by_leaf(events, unit).items():
                by_span[name] = by_span.get(name, 0.0) + s
    scale = 1.0 / (len(devices) * len(units))
    ctx.setdefault("notes", {})["device_clock_lead_ms"] = {
        plane: ns * 1e-6 for plane, ns in trace.lead.items()}
    ctx["notes"]["idle_by_span"] = {
        k: v * scale for k, v in sorted(by_span.items(), key=lambda kv: -kv[1])}
    return by_span.get("unattributed", 0.0) * scale
