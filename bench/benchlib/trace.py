"""Reduce a profiler trace of the window to device busy time, per-kernel
time and roofline share, the longest device operations and the longest
idle gaps.

Device operations are the events of the ``XLA Ops`` line of each TPU
plane; busy time is the length of their union inside the window, which
is the span of the harness's ``bench.window`` annotation on the host.
An XLA Ops event is named by its instruction's whole HLO text; a
kernel is found by its stable Pallas name, which names the instruction,
and its call's shapes come from that text. An op that other ops run
inside (a loop) is left out of the per-op times. An idle gap is
named by the innermost harness span that covers its middle
(``bench.wait``: waiting for arrivals; ``bench.iteration``: inside
``run_iteration``; ``bench.window``: the harness between iterations).
"""
from __future__ import annotations

import glob
import re
from typing import Dict, List, Optional, Tuple

from benchlib.roofline import KERNEL_BITS, KernelCall

HOST_SPANS = ("bench.wait", "bench.iteration", "bench.window")
_SHAPE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def find_xplane(tdir: str) -> str:
    paths = glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {tdir}, found {paths}")
    return paths[0]


def reduce_dir(tdir: str, peaks: Optional[dict], config: dict) -> dict:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(find_xplane(tdir)), peaks,
                  config["plan"]["group_size"])


def op_name(event_name: str) -> str:
    """The HLO instruction an XLA Ops event is named by: its text up to
    `` = `` (the event name is the instruction's whole HLO text)."""
    return event_name.split(" = ", 1)[0].strip()


_KERNEL = re.compile(r"^%?(" + "|".join(KERNEL_BITS) + r")(\.\d+)?$")


def kernel_of(event_name: str) -> Optional[str]:
    m = _KERNEL.match(op_name(event_name))
    return m.group(1) if m else None


def call_shapes(text: str) -> Optional[Tuple[int, int, int, int]]:
    """(G, M, K, N) of a grouped call from its HLO text: the result is
    ``[G, M, N]`` and the first operand the activations ``[G, M, K]``."""
    dims = [[int(x) for x in d.split(",") if x] for _, d in
            _SHAPE.findall(text)]
    dims = [d for d in dims if len(d) == 3]
    if len(dims) < 2:
        return None
    (g, m, n), (g2, m2, k) = dims[0], dims[1]
    if (g, m) != (g2, m2):
        return None
    return g, m, k, n


def union_length(intervals: List[Tuple[float, float]]) -> Tuple[float, list]:
    """Total length of the union, and the union's pieces."""
    pieces: List[List[float]] = []
    for a, b in sorted(intervals):
        if pieces and a <= pieces[-1][1]:
            pieces[-1][1] = max(pieces[-1][1], b)
        else:
            pieces.append([a, b])
    return sum(b - a for a, b in pieces), pieces


def reduce(pd, peaks: Optional[dict], group_size: int) -> dict:
    host: List[Tuple[float, float, str]] = []
    ops: List[Tuple[float, float, str]] = []
    n_dev = 0
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            n_dev += 1
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name) for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((ev.start_ns, ev.start_ns + ev.duration_ns,
                             ev.name) for ev in line.events
                            if ev.name in HOST_SPANS)
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
        k = kernel_of(name)
        if k is None:
            continue
        kk = kernels.setdefault(k, {"device_s": 0.0, "least_s": 0.0,
                                    "calls": 0, "flops": 0.0,
                                    "bytes": 0.0, "unshaped": 0})
        kk["device_s"] += (b - a) * 1e-9
        kk["calls"] += 1
        shp = call_shapes(name)
        if shp is None:
            kk["unshaped"] += 1
            continue
        call = KernelCall(k, *shp, group_size=group_size)
        kk["flops"] += call.flops()
        kk["bytes"] += call.bytes()
        if peaks is not None:
            kk["least_s"] += call.least_s(peaks)
    gaps = []
    edges = [w0] + [x for p in pieces for x in p] + [w1]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            mid = (a + b) / 2
            cover = [h for h in host if h[0] <= mid <= h[1]]
            what = min(cover, key=lambda h: h[1] - h[0])[2] if cover \
                else "outside harness spans"
            gaps.append([what, (b - a) * 1e-9])
    gaps.sort(key=lambda g: -g[1])
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": busy_ns * 1e-9 / max(n_dev, 1),
        "window_s": (w1 - w0) * 1e-9,
        "devices": n_dev,
        "kernels": kernels,
        "breakdown": {"device_ops": [[n, t * 1e-9] for n, t in top],
                      "idle_gaps": gaps[:10]},
    }
