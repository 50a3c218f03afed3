"""Prefill: model operations of the prompts prefilled in the window
(real prompt tokens, the experts they are routed to, causal attention;
the architecture file's ``prefill_flops``) over the engine's prefill
seconds times the chip's bf16 peak, in %."""


def read(ctx):
    win, pk = ctx["win"], ctx["peaks"]
    if pk is None or not win.prefill_tokens or win.counters["prefill_s"] <= 0:
        return None
    return 100.0 * win.prefill_flops / (win.counters["prefill_s"]
                                        * pk["bf16_flops"])
