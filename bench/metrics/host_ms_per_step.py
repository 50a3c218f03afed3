"""Host loop: wall time inside ``run_iteration`` less the engine's
prefill and decode seconds (sampling, routed-id streaming, page table,
Python bookkeeping), per iteration in the window, in ms. Waiting for
arrivals is outside ``run_iteration`` and not counted."""


def read(ctx):
    win = ctx["win"]
    return win.host_s * 1e3 / win.iterations if win.iterations else None
