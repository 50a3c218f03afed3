"""KV cache: the engine's seconds releasing a finished request's pages
(``metrics["kv_release_s"]``, the ``engine.kv_release`` span) per
iteration in the window, in ms."""


def read(ctx):
    n = ctx["win"].counters
    if n.get("kv_release_s") is None or not n["iterations"]:
        return None
    return n["kv_release_s"] * 1e3 / n["iterations"]
