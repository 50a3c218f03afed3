"""Prefill: the share of the prefilled bucket tokens that were padding,
over the prefills in the window: the engine's ``prefill_padded_tokens``
(each prompt at its compiled bucket) less ``prefill_tokens`` (the real
prompt tokens), over ``prefill_padded_tokens``, in %."""


def read(ctx):
    n = ctx["win"].counters
    padded, real = n.get("prefill_padded_tokens"), n.get("prefill_tokens")
    if padded is None or real is None or padded <= 0:
        return None
    return 100.0 * (padded - real) / padded
