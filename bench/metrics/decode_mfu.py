"""Decode step: model operations of the tokens decoded in the window
(each at its own context, with its logits; the architecture file's
``decode_flops``) over the engine's decode seconds times the chip's bf16
peak, in %. The whole step's share of the peak."""


def read(ctx):
    win, pk = ctx["win"], ctx["peaks"]
    if pk is None or win.counters["decode_s"] <= 0 or not win.decode_flops:
        return None
    return 100.0 * win.decode_flops / (win.counters["decode_s"]
                                       * pk["bf16_flops"])
