"""The program's own spans and executables, read from the profiler's trace.

Since PR 24 every ``telemetry.span`` of the program enters a
``jax.profiler.TraceAnnotation`` named ``photon:<span name>``, so a capture
holds the program's span tree on a host line, on the clock of the device
planes (nanoseconds from the start of the trace). What a v5e trace holds
besides ``XLA Ops`` (looked at by hand, PR 24): the line ``XLA Modules`` of
``/device:TPU:<n>`` carries one event per launched device program, named
``jit_<function>(<fingerprint>)`` — ``jit_fe_solve(...)`` for the program's
named executables, ``jit_convert_element_type(...)`` and the like for eager
one-op programs.

The two planes' clocks are NOT one clock to the millisecond (PR 24, both
v5e traces on record): a device event is stamped 1.0–2.7 ms EARLIER than the
host span that provably dispatched it (``jit_fe_score_tiled`` starts 1.04 ms
before ``photon:initial_scores`` opens). Span durations are read from the
host plane alone, so they do not care. Whatever sets device events against
spans (a fit's launches, idle by span) first moves the device plane later by
its ``lead``: the distance from the end of the plane's last event to the end
of the program's last leaf span, which closes on a fetch and so cannot end
before the device does. What stays is that fetch's own latency (~0.3 ms).

``tracing.py`` keeps the benchmark's own ``bench:`` annotations and ``XLA
Ops``, and ``run.py`` hands no path on; so :func:`load` finds the newest
``.xplane.pb`` under ``out/*/trace`` itself, once per run (cached in the
run's ``ctx``). A program without the spans (the parent of PR 24) gives a
trace without them, and every reader built on this then returns nothing.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

from benchmark import tracing

HERE = os.path.dirname(os.path.abspath(__file__))
SPAN_PREFIX = "photon:"
MODULES_LINE = "XLA Modules"
#: the span of one whole unit of work (one fit)
UNIT_SPAN = "coordinate_descent"


@dataclasses.dataclass
class Span:
    name: str
    start: float  # ns
    dur: float  # ns
    parent: "Span | None" = None
    children: list = dataclasses.field(default_factory=list)

    @property
    def end(self) -> float:
        return self.start + self.dur

    @property
    def self_ns(self) -> float:
        return self.dur - sum(c.dur for c in self.children)

    def walk(self):
        yield self
        for c in self.children:
            yield from c.walk()

    def leaves(self):
        return [s for s in self.walk() if not s.children]


@dataclasses.dataclass
class ProgramTrace:
    #: root spans of the program (``photon:`` annotations nested by their
    #: intervals, line by line), by start
    roots: list
    #: device plane name -> [(module event name, start_ns, duration_ns)]
    modules: dict
    #: device plane name -> ns its stamps run ahead of the host plane's
    lead: dict = dataclasses.field(default_factory=dict)

    def on_host_clock(self, plane: str, events) -> list:
        """A device plane's events, moved later by the plane's lead."""
        lead = self.lead.get(plane, 0.0)
        return [(n, s + lead, d) for n, s, d in events] if lead else events

    def units(self) -> list:
        """Every ``coordinate_descent`` span: one per traced fit."""
        return [s for r in self.roots for s in r.walk()
                if s.name == UNIT_SPAN]


def nest(events) -> list:
    """[(name, start, dur)] of ONE thread -> root :class:`Span` s, each
    holding the spans its interval contains."""
    roots: list = []
    stack: list = []
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        span = Span(name, float(start), float(dur))
        while stack and start >= stack[-1].end:
            stack.pop()
        if stack:
            span.parent = stack[-1]
            stack[-1].children.append(span)
        else:
            roots.append(span)
        stack.append(span)
    return roots


def read(path: str) -> ProgramTrace:
    from jax.profiler import ProfileData

    roots: list = []
    modules: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(tracing.DEVICE_PLANE):
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules[plane.name] = sorted(
                        ((e.name, float(e.start_ns), float(e.duration_ns))
                         for e in line.events), key=lambda e: e[1])
        elif plane.name == tracing.HOST_PLANE:
            for line in plane.lines:
                mine = [
                    (e.name[len(SPAN_PREFIX):], e.start_ns, e.duration_ns)
                    for e in line.events if e.name.startswith(SPAN_PREFIX)]
                roots.extend(nest(mine))
    roots.sort(key=lambda s: s.start)
    trace = ProgramTrace(roots=roots, modules=modules)
    trace.lead = device_lead(trace.units(), modules)
    return trace


def device_lead(units, modules) -> dict:
    """Per device plane, how far its stamps run ahead of the host's: the
    program's last leaf span ends on a fetch, after the plane's last event."""
    if not units:
        return {}
    last_fetch = max(leaf.end for u in units for leaf in u.leaves())
    return {
        plane: max(last_fetch - max(s + d for _, s, d in events), 0.0)
        for plane, events in modules.items() if events}


def load(ctx) -> ProgramTrace | None:
    """This run's trace (None when the run is not a traced one), read once."""
    if ctx.get("trace") is None:
        return None
    if "program_trace" not in ctx:
        found = []
        for directory in glob.glob(os.path.join(HERE, "out", "*", "trace")):
            try:
                found.append(tracing.find_xplane(directory))
            except FileNotFoundError:
                pass
        ctx["program_trace"] = (
            read(max(found, key=os.path.getmtime)) if found else None)
    return ctx["program_trace"]


def select(roots, span: str, parent: str | None = None) -> list:
    """Spans under ``roots`` whose name matches ``span`` (a regular
    expression, the whole name) and, if given, whose parent's matches
    ``parent``."""
    rx, prx = re.compile(span), parent and re.compile(parent)
    return [
        s for r in roots for s in r.walk()
        if rx.fullmatch(s.name) and (
            prx is None
            or (s.parent is not None and prx.fullmatch(s.parent.name)))]


def module_name(event_name: str) -> str:
    """``jit_fe_solve(1234)`` -> ``jit_fe_solve``."""
    return event_name.partition("(")[0]


def inside(events, windows) -> list:
    """Events that start inside one of ``windows`` ([(start, end)] in ns);
    all of them when ``windows`` is None."""
    if windows is None:
        return list(events)
    return [e for e in events if any(a <= e[1] < b for a, b in windows)]


def unnamed_modules(events, named, windows=None) -> list:
    """Launched programs that are none of ``named`` (module names)."""
    return [e for e in inside(events, windows)
            if module_name(e[0]) not in named]


def idle_by_leaf(device_events, unit: Span) -> dict:
    """Device idle seconds inside ``unit``, by the LEAF span of it that
    holds each gap's midpoint (a gap that no leaf holds falls to
    ``unattributed``: the host was between the program's spans)."""
    leaves = [(path(s, unit), s.start, s.dur)
              for s in unit.leaves() if s is not unit]
    return dict(tracing.idle_gaps(
        device_events, (unit.start, unit.end), leaves, top=len(leaves) + 1))


def path(span: Span, top: Span) -> str:
    """``coordinate:fixed>update``: the names from below ``top`` down."""
    names = []
    while span is not None and span is not top:
        names.append(span.name)
        span = span.parent
    return ">".join(reversed(names))
