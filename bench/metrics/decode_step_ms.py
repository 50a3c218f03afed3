"""Decode step: the engine's decode seconds (``metrics["decode_s"]``,
host clock around each jitted paged decode step, ending in
``block_until_ready``) per decode iteration in the window, in ms."""


def read(ctx):
    win = ctx["win"]
    n = win.counters["iterations"]
    return win.counters["decode_s"] * 1e3 / n if n else None
