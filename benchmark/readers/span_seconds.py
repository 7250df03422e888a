"""Seconds of one of the benchmark's own spans around a call into a layer."""


def read(ctx, span):
    return ctx["spans"].get(span)
