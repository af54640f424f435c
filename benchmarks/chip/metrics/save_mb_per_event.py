"""Megabytes the store took in per save event in the window, from the
manager's ``bytes_written`` counter."""


def read(ctx):
    c = ctx["counters"]
    if not c["window_saves"]:
        return None
    return c["window_save_bytes"] / c["window_saves"] / 1e6
