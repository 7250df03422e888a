"""Launched device programs of the traced fit, from the ``XLA Modules`` line
of the profiler's trace (one event per launch, named ``jit_<function>(..)``;
PR 24 names the program's executables, so ``jit_fe_solve`` is the FE solve).

``pattern``: summed device seconds of the launches whose name matches, per
fit. ``unnamed``: how many launches per fit are NONE of the program's named
executables (``telemetry.XLA_REGISTRY``: what ``instrumented_jit``
compiled) — eager one-op programs and bare jits. Both look only inside the
program's ``coordinate_descent`` spans (the device plane first moved onto
the host's clock, ``program_trace``); without them, or without the line (off
the chip), nothing."""

import re

from benchmark import program_trace


def read(ctx, pattern=None, unnamed=False):
    trace = program_trace.load(ctx)
    if trace is None or not trace.modules:
        return None
    units = trace.units()
    if not units:
        return None
    windows = [(u.start, u.end) for u in units]
    if unnamed:
        from photon_ml_tpu import telemetry

        named = {"jit_" + r.name for r in telemetry.XLA_REGISTRY.executables()}
    else:
        rx = re.compile(pattern)
    values = []
    for plane, events in trace.modules.items():
        events = trace.on_host_clock(plane, events)
        if unnamed:
            values.append(float(len(
                program_trace.unnamed_modules(events, named, windows))))
            continue
        hits = [e for e in program_trace.inside(events, windows)
                if rx.search(e[0])]
        if hits:
            values.append(sum(e[2] for e in hits) * 1e-9)
    if not values:
        return None
    return sum(values) / len(values) / len(units)
