"""Sum of the program's counters between two marks of the run
(``process_start`` reads as zero, ``setup_end``, ``window_start``,
``window_end``)."""


def read(ctx, counters, since, until):
    marks = ctx["counters"]
    if until not in marks or (since != "process_start" and since not in marks):
        return None
    lo = {} if since == "process_start" else marks[since]
    return float(sum(marks[until].get(c, 0) - lo.get(c, 0) for c in counters))
