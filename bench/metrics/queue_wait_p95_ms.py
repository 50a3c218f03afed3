"""Scheduler: 95th percentile of the wait from when a request was due to
when the scheduler admitted it into a slot (``Request.t_admit``), over
the requests due in the window, in ms."""
import numpy as np


def read(ctx):
    win = ctx["win"]
    waits = [(r.req.t_admit - r.due) * 1e3 for r in win.records
             if r.due < win.end and r.req.t_admit is not None]
    return float(np.percentile(waits, 95)) if waits else None
