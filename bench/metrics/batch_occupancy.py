"""Scheduler: the share of the decode batch's rows that held a request,
over the iterations in the window: the engine's ``decode_rows`` (active
rows summed over decode steps) over iterations times the cell's slots,
in %."""


def read(ctx):
    n = ctx["win"].counters
    if n.get("decode_rows") is None or not n["iterations"]:
        return None
    return 100.0 * n["decode_rows"] / (n["iterations"]
                                       * ctx["traffic"]["slots"])
