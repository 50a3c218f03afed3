"""Host loop: the engine's sampling seconds (``metrics["sample_s"]``, the
``engine.sample`` span: key split, sampling and the copy to the host, at
prefill and decode) per iteration in the window, in ms."""


def read(ctx):
    n = ctx["win"].counters
    if n.get("sample_s") is None or not n["iterations"]:
        return None
    return n["sample_s"] * 1e3 / n["iterations"]
