"""``counter_delta`` over counters the program keeps: the sum of their
changes between two marks of the run, and nothing where any of them is
missing at ``until`` (a program without them, such as the parent of the PR
that brought them, prints no number rather than 0)."""

from benchmark.readers import counter_delta


def read(ctx, counters, since, until):
    mark = ctx["counters"].get(until)
    if mark is None or any(c not in mark for c in counters):
        return None
    return counter_delta.read(ctx, counters, since, until)
