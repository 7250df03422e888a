"""One of the program's counters over another, both read at one mark of the
run (``setup_end``, ``window_start``, ``window_end``). A program without
either counter, or a zero denominator: nothing returned."""


def read(ctx, numerator, denominator, at):
    mark = ctx["counters"].get(at)
    if mark is None or not mark.get(denominator) or numerator not in mark:
        return None
    return float(mark[numerator]) / float(mark[denominator])
