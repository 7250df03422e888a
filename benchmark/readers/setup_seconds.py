"""Process start to the end of the warm-up unit: the host's clock."""


def read(ctx):
    return ctx["setup_s"]
