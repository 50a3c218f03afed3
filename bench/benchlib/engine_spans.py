"""Read the engine's own spans and program names from a profiler trace of
the window: each idle gap named by the innermost span, harness or
engine, that covers its middle; the idle seconds under each span name;
and the device seconds and calls of each jitted program on the
``XLA Modules`` line.

The engine writes its spans through ``repro.serving.tracing.span``
(``engine.iteration``, ``engine.prefill.wait``, ...); a span with
arguments is written ``name#k=v,...#``, so spans are matched by the name
up to ``#``. A program is named by its ``XLA Modules`` event up to
``(`` (``jit_engine_decode_paged(<fingerprint>)``). A trace of a program
without these spans reads as the harness's spans alone.

``benchlib.trace.reduce`` does not call this yet: wiring it in, and the
per-layer metrics that read it, take a benchmark change (PERF.md §7).
"""
from __future__ import annotations

from typing import Dict, List, Tuple

from benchlib.trace import HOST_SPANS, union_length

ENGINE = "engine."
#: the host blocked on the device's answer
WAIT_SPANS = ("engine.prefill.wait", "engine.decode.wait")


def span_name(event_name: str) -> str:
    return event_name.split("#", 1)[0]


def module_name(event_name: str) -> str:
    return event_name.split("(", 1)[0]


def reduce(pd) -> dict:
    host: List[Tuple[float, float, str]] = []
    ops: List[Tuple[float, float]] = []
    mods: List[Tuple[float, float, str]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                evs = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                       for ev in line.events]
                if line.name == "XLA Ops":
                    ops.extend((a, b) for a, b, _ in evs)
                elif line.name == "XLA Modules":
                    mods.extend(evs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    name = span_name(ev.name)
                    if name in HOST_SPANS or name.startswith(ENGINE):
                        host.append((ev.start_ns,
                                     ev.start_ns + ev.duration_ns, name))
    windows = [h for h in host if h[2] == "bench.window"]
    if not windows:
        raise RuntimeError("the trace holds no bench.window span")
    w0, w1 = windows[0][0], windows[0][1]
    _, pieces = union_length([(max(a, w0), min(b, w1)) for a, b in ops
                              if b > w0 and a < w1])
    gaps = []
    edges = [w0] + [x for p in pieces for x in p] + [w1]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            mid = (a + b) / 2
            cover = [h for h in host if h[0] <= mid <= h[1]]
            what = min(cover, key=lambda h: h[1] - h[0])[2] if cover \
                else "outside harness spans"
            gaps.append([what, (b - a) * 1e-9])
    idle_by_span: Dict[str, float] = {}
    for what, s in gaps:
        idle_by_span[what] = idle_by_span.get(what, 0.0) + s
    modules: Dict[str, dict] = {}
    for a, b, name in mods:
        if w0 <= a < w1:
            m = modules.setdefault(module_name(name),
                                   {"device_s": 0.0, "calls": 0})
            m["device_s"] += (min(b, w1) - a) * 1e-9
            m["calls"] += 1
    gaps.sort(key=lambda g: -g[1])
    return {"idle_gaps": gaps[:10], "idle_by_span": idle_by_span,
            "modules": modules}


def idle_in_wait_share(spans: dict, window_s: float) -> float:
    """The share of the window, in %, in which the device was idle while
    the host waited for it inside a prefill or decode call."""
    return 100.0 * sum(spans["idle_by_span"].get(n, 0.0)
                       for n in WAIT_SPANS) / window_s
