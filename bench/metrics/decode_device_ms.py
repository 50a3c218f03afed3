"""Decode step: the device time of the engine's paged decode program
(``jit_engine_decode_paged`` on the trace's ``XLA Modules`` line) per
call in the traced window, in ms: the step without the host's part."""


def read(ctx):
    mod = ((ctx["trace"] or {}).get("modules") or {}).get(
        "jit_engine_decode_paged")
    if not mod or not mod["calls"]:
        return None
    return mod["device_s"] * 1e3 / mod["calls"]
