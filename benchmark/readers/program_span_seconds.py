"""Seconds of the program's own spans (``telemetry.span``; PR 24), per unit.

``source: "trace"`` reads the spans' mirrors in the profiler's trace
(``photon:<name>`` annotations) under every ``root`` span of the traced
window and divides by the number of roots (one per fit). ``source:
"process"`` reads ``telemetry.finished_spans()`` of this process under the
FIRST ``root`` span (set-up: the first build of the coordinates; later
calls are cache hits). ``span`` and ``parent`` are regular expressions over
whole names. With ``self_time`` it is each matched span's duration less
what its child spans cover. No root, or no span under it: nothing."""

from benchmark import program_trace


def process_roots(root: str) -> list:
    """The program's finished spans as :class:`program_trace.Span` trees
    (seconds scaled to ns, like a trace's), those named ``root``."""
    from photon_ml_tpu import telemetry

    spans = {}
    for s in telemetry.finished_spans():
        if s.dur is not None:
            spans[s.span_id] = (s, program_trace.Span(
                s.name, s.ts * 1e9, s.dur * 1e9))
    for s, node in spans.values():
        if s.parent_id in spans:
            node.parent = spans[s.parent_id][1]
            node.parent.children.append(node)
    return sorted((n for _, n in spans.values() if n.name == root),
                  key=lambda n: n.start)


def read(ctx, span, root, parent=None, source="trace", self_time=False):
    if source == "process":
        roots = process_roots(root)[:1]
    else:
        trace = program_trace.load(ctx)
        roots = [] if trace is None else program_trace.select(
            trace.roots, root)
    found = program_trace.select(roots, span, parent)
    if not found:
        return None
    total = sum(s.self_ns if self_time else s.dur for s in found)
    return total * 1e-9 / len(roots)
