"""Reduce a profiler trace of the window to device busy time, per-kernel
time and roofline share, each jitted program's device time, the longest
device operations and the idle gaps, each named by what the host was
doing.

Device operations are the events of the ``XLA Ops`` line of each TPU
plane; busy time is the length of their union inside the window, which
is the span of the harness's ``bench.window`` annotation on the host.
An XLA Ops event is named by its instruction's whole HLO text. A kernel
is found by its stable Pallas name, which names the instruction, through
the kernel counters under ``bench/kernels/``: each lists the names it
matches, reads a call's shapes from that text and counts its operations,
bytes and least time. An op that other ops run inside (a loop) is left
out of the per-op and per-kernel times.

An idle gap is named by the innermost span, the harness's or the
engine's, that covers its middle (``bench.wait``: waiting for arrivals;
``bench.iteration``: inside ``run_iteration``; ``bench.window``: the
harness between iterations; ``engine.*``: the engine's own spans, written
through ``repro.serving.tracing.span``). A span with arguments is written
``name#k=v,...#``, so spans are matched by the name up to ``#``. A
jitted program is named by its ``XLA Modules`` event up to ``(``
(``jit_engine_decode_paged(<fingerprint>)``). A trace of a program
without engine spans reads as the harness's spans alone.
"""
from __future__ import annotations

import glob
import heapq
import re
from typing import Dict, List, Optional, Tuple

HOST_SPANS = ("bench.wait", "bench.iteration", "bench.window")
ENGINE = "engine."
#: the host blocked on the device's answer
WAIT_SPANS = ("engine.prefill.wait", "engine.decode.wait")
OUTSIDE = "outside harness spans"


def find_xplane(tdir: str) -> str:
    paths = glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {tdir}, found {paths}")
    return paths[0]


def reduce_dir(tdir: str, peaks: Optional[dict], config: dict,
               counters: Dict[str, object]) -> dict:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(find_xplane(tdir)), peaks, config,
                  counters)


def op_name(event_name: str) -> str:
    """The HLO instruction an XLA Ops event is named by: its text up to
    `` = `` (the event name is the instruction's whole HLO text)."""
    return event_name.split(" = ", 1)[0].strip()


def span_name(event_name: str) -> str:
    return event_name.split("#", 1)[0]


def module_name(event_name: str) -> str:
    return event_name.split("(", 1)[0]


def kernel_pattern(counters: Dict[str, object]) -> re.Pattern:
    """Matches an instruction named ``<kernel>`` or ``<kernel>.<n>`` for
    each kernel name the counters list (nothing, where they list none)."""
    if not counters:
        return re.compile(r"(?!)")
    names = "|".join(re.escape(n) for n in sorted(counters))
    return re.compile(r"^%?(" + names + r")(\.\d+)?$")


def kernel_of(event_name: str, pattern: re.Pattern) -> Optional[str]:
    m = pattern.match(op_name(event_name))
    return m.group(1) if m else None


def union_length(intervals: List[Tuple[float, float]]) -> Tuple[float, list]:
    """Total length of the union, and the union's pieces."""
    pieces: List[List[float]] = []
    for a, b in sorted(intervals):
        if pieces and a <= pieces[-1][1]:
            pieces[-1][1] = max(pieces[-1][1], b)
        else:
            pieces.append([a, b])
    return sum(b - a for a, b in pieces), pieces


def name_gaps(gaps: List[Tuple[float, float]],
              host: List[Tuple[float, float, str]]) -> List[list]:
    """``[name, seconds]`` for each gap ``(a, b)``, given in increasing
    order: the name of the shortest host span that covers the gap's
    middle (of equal ones the first listed), or ``OUTSIDE``. One sweep
    over the spans by start, with a heap of those begun by the middle."""
    order = sorted(range(len(host)), key=lambda i: host[i][0])
    heap: list = []
    j, out = 0, []
    for a, b in gaps:
        mid = (a + b) / 2
        while j < len(order) and host[order[j]][0] <= mid:
            i = order[j]
            s, e, name = host[i]
            heapq.heappush(heap, (e - s, i, e, name))
            j += 1
        while heap and heap[0][2] < mid:
            heapq.heappop(heap)
        out.append([heap[0][3] if heap else OUTSIDE, (b - a) * 1e-9])
    return out


def _collect(pd):
    """Host spans (harness and engine), XLA ops and XLA modules, and the
    number of TPU planes."""
    host: List[Tuple[float, float, str]] = []
    ops: List[Tuple[float, float, str]] = []
    mods: List[Tuple[float, float, str]] = []
    n_dev = 0
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            n_dev += 1
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name) for ev in line.events)
                elif line.name == "XLA Modules":
                    mods.extend((ev.start_ns, ev.start_ns + ev.duration_ns,
                                 ev.name) for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    name = span_name(ev.name)
                    if name in HOST_SPANS or name.startswith(ENGINE):
                        host.append((ev.start_ns,
                                     ev.start_ns + ev.duration_ns, name))
    return host, ops, mods, n_dev


def reduce(pd, peaks: Optional[dict], config: dict,
           counters: Dict[str, object]) -> dict:
    """The window's reduction; ``counters`` maps kernel names to their
    counters (``spec.kernel_counters``)."""
    pattern = kernel_pattern(counters)
    host, ops, mods, n_dev = _collect(pd)
    windows = [h for h in host if h[2] == "bench.window"]
    if not windows:
        raise RuntimeError("the trace holds no bench.window span")
    w0, w1 = windows[0][0], windows[0][1]
    inside = sorted((max(a, w0), min(b, w1), n) for a, b, n in ops
                    if b > w0 and a < w1)
    busy_ns, pieces = union_length([(a, b) for a, b, _ in inside])
    per_op: Dict[str, float] = {}
    kernels: Dict[str, dict] = {}
    for i, (a, b, name) in enumerate(inside):
        # an op another op starts inside (a loop) holds that op's time
        if i + 1 < len(inside) and inside[i + 1][0] < b:
            continue
        op = op_name(name)
        per_op[op] = per_op.get(op, 0.0) + (b - a)
        k = kernel_of(name, pattern)
        if k is None:
            continue
        kk = kernels.setdefault(k, {"device_s": 0.0, "least_s": 0.0,
                                    "calls": 0, "flops": 0.0,
                                    "bytes": 0.0, "unshaped": 0})
        kk["device_s"] += (b - a) * 1e-9
        kk["calls"] += 1
        counter = counters[k]
        shp = counter.shapes(name)
        if shp is None:
            kk["unshaped"] += 1
            continue
        kk["flops"] += counter.flops(k, shp, config)
        kk["bytes"] += counter.nbytes(k, shp, config)
        if peaks is not None:
            kk["least_s"] += counter.least_s(k, shp, peaks, config)
    edges = [w0] + [x for p in pieces for x in p] + [w1]
    gaps = name_gaps([(a, b) for a, b in zip(edges[0::2], edges[1::2])
                      if b > a], host)
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
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": busy_ns * 1e-9 / max(n_dev, 1),
        "window_s": (w1 - w0) * 1e-9,
        "devices": n_dev,
        "kernels": kernels,
        "modules": modules,
        "idle_by_span": idle_by_span,
        "engine_spans": sum(1 for h in host if h[2].startswith(ENGINE)
                            and h[1] > w0 and h[0] < w1),
        "breakdown": {"device_ops": [[n, t * 1e-9] for n, t in top],
                      "idle_gaps": gaps[:10]},
    }


def idle_in_wait_share(reduced: dict) -> Optional[float]:
    """The share of the window, in %, in which the device was idle while
    the host waited for it inside a prefill or decode call; ``None`` for
    a trace without engine spans."""
    if not reduced["engine_spans"] or reduced["window_s"] <= 0:
        return None
    return 100.0 * sum(reduced["idle_by_span"].get(n, 0.0)
                       for n in WAIT_SPANS) / reduced["window_s"]
