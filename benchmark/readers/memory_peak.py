"""The allocator's peak on the fullest chip after the window, in GB (1e9)."""


def read(ctx):
    peak = ctx["memory"].get("peak_bytes_in_use")
    return None if not peak else peak / 1e9
