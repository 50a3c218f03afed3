"""Device: the share of the traced window in which the chip was idle
while the host waited for it inside a prefill or decode call (idle gaps
whose innermost span is ``engine.prefill.wait`` or
``engine.decode.wait``), in %; nothing for a trace without engine
spans."""
from benchlib.trace import idle_in_wait_share


def read(ctx):
    return idle_in_wait_share(ctx["trace"]) if ctx["trace"] else None
