"""Prefill: the engine's prefill seconds (``metrics["prefill_s"]``, host
clock around each jitted prefill, ending in ``block_until_ready``) spent
in the window, per 1000 prompt tokens prefilled there, in ms."""


def read(ctx):
    win = ctx["win"]
    if not win.prefill_tokens:
        return None
    return win.counters["prefill_s"] * 1e3 / (win.prefill_tokens / 1e3)
