"""The trace reduction's engine spans and programs (``benchlib.trace``) on
two windows of the chat cell recorded on one TPU v5e: one from before the
engine wrote spans, on which the reduction is pinned and must read the
harness's spans alone, and one with the engine's spans and program
names."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from benchlib import roofline, spec
from benchlib import trace as TR

DATA = Path(__file__).parent / "data"
CONFIG = json.loads((spec.BENCH / "configs" / "mixtral-8x7b-mixed.json")
                    .read_text())
OLD = DATA / "chat_window.xplane.pb"
NEW = DATA / "chat_engine_window.xplane.pb"
PROGRAMS = {"jit_engine_prefill_paged", "jit_engine_decode_paged",
            "jit_engine_paged_reset"}


def _load(path):
    import jax
    return jax.profiler.ProfileData.from_file(str(path))


@pytest.fixture(scope="module")
def old():
    return _load(OLD)


@pytest.fixture(scope="module")
def new_pd():
    return _load(NEW)


@pytest.fixture(scope="module")
def new(new_pd):
    return TR.reduce(new_pd, roofline.peaks("TPU v5 lite"), CONFIG,
                     spec.kernel_counters())


def _host_events(pd):
    return [(TR.span_name(ev.name), ev.start_ns, ev.start_ns + ev.duration_ns,
             dict(ev.stats))
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if TR.span_name(ev.name).startswith(("engine.", "bench."))]


def test_chip_trace_holds_engine_spans_inside_iterations(new_pd, new):
    evs = _host_events(new_pd)
    names = {n for n, *_ in evs}
    assert {"engine.iteration", "engine.admit", "engine.prefill",
            "engine.prefill.dispatch", "engine.prefill.wait",
            "engine.sample", "engine.kv_map", "engine.decode",
            "engine.decode.dispatch", "engine.decode.wait",
            "engine.stream_experts", "engine.retire",
            "engine.kv_release"} <= names
    its = [(a, b) for n, a, b, _ in evs if n == "bench.iteration"]
    for n, a, b, _ in evs:
        if n.startswith("engine."):
            assert any(ia <= a and b <= ib for ia, ib in its), n
    prefill = [st for n, _, _, st in evs if n == "engine.prefill"]
    assert prefill and all(
        {"rid", "slot", "tokens", "bucket"} <= set(st) for st in prefill)
    # the decode program's device time fits inside the host's decode span
    decode = [b - a for n, a, b, _ in evs if n == "engine.decode"]
    mods = new["modules"]["jit_engine_decode_paged"]
    assert mods["device_s"] / mods["calls"] <= sum(decode) / len(decode) \
        * 1e-9


def test_harness_reduction_reads_as_before(old):
    r = TR.reduce(old, roofline.peaks("TPU v5 lite"), CONFIG,
                     spec.kernel_counters())
    assert r["busy_s"] == pytest.approx(0.55046202, rel=1e-12)
    assert r["window_s"] == pytest.approx(1.162836706, rel=1e-12)
    want = {"grouped_bf16_matmul": (0.04786906400000001, 0.01790221917390187,
                                    2029372047360.0, 11911299072.0),
            "grouped_q4_matmul": (0.3020359789999999, 0.023057124230466512,
                                  4058744094720.0, 7263485952.0),
            "grouped_q8_matmul": (0.036673412, 0.01367948724343839,
                                  2029372047360.0, 6450315264.0)}
    assert set(r["kernels"]) == set(want)
    for k, (dev, least, flops, nbytes) in want.items():
        got = r["kernels"][k]
        assert (got["calls"], got["unshaped"]) == (48, 0)
        assert got["device_s"] == pytest.approx(dev, rel=1e-12)
        assert got["least_s"] == pytest.approx(least, rel=1e-12)
        assert (got["flops"], got["bytes"]) == (flops, nbytes)


def test_trace_without_engine_spans_reads_harness_spans(old):
    r = TR.reduce(old, None, CONFIG, spec.kernel_counters())
    assert r["engine_spans"] == 0
    assert set(r["idle_by_span"]) <= set(TR.HOST_SPANS + (TR.OUTSIDE,))
    assert all(g[0] in TR.HOST_SPANS + (TR.OUTSIDE,)
               for g in r["breakdown"]["idle_gaps"])
    assert TR.idle_in_wait_share(r) is None
    # before program names: the engine's prefill and decode were unnamed
    assert "jit__unknown" in r["modules"]


def test_idle_gaps_name_engine_spans(new):
    r = new
    names = [g[0] for g in r["breakdown"]["idle_gaps"]]
    assert any(n.startswith(TR.ENGINE) for n in names)
    assert r["engine_spans"] > 0
    assert sum(r["idle_by_span"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-9)


@pytest.mark.parametrize("path", [OLD, NEW], ids=["old", "new"])
def test_gap_names_are_the_shortest_covering_span(path):
    """The sweep names every gap as a plain search over all spans would:
    the shortest span covering its middle, the first listed of equal
    ones."""
    pd = _load(path)
    host = [(a, b, n) for n, a, b, _ in _host_events(pd)
            if n in TR.HOST_SPANS or n.startswith(TR.ENGINE)]
    ops = sorted((ev.start_ns, ev.start_ns + ev.duration_ns)
                 for plane in pd.planes
                 if plane.name.startswith("/device:TPU:")
                 for line in plane.lines if line.name == "XLA Ops"
                 for ev in line.events)
    _, pieces = TR.union_length(ops)
    edges = [x for p in pieces for x in p]
    gaps = [(a, b) for a, b in zip(edges[1::2], edges[2::2]) if b > a]
    # two equal spans, one that ends at a gap's middle, and no span
    m0, m1 = (gaps[0][0] + gaps[0][1]) / 2, (gaps[1][0] + gaps[1][1]) / 2
    host += [(m0 - 1, m0 + 1, "x.first"), (m0 - 1, m0 + 1, "x.equal"),
             (m1 - 1, m1, "x.ends_at_mid")]
    last = max(h[1] for h in host)
    gaps.append((last + 10, last + 20))
    want = []
    for a, b in gaps:
        mid = (a + b) / 2
        cover = [h for h in host if h[0] <= mid <= h[1]]
        want.append([min(cover, key=lambda h: h[1] - h[0])[2] if cover
                     else TR.OUTSIDE, (b - a) * 1e-9])
    assert TR.name_gaps(gaps, host) == want
    assert want[0][0] == "x.first" and want[1][0] == "x.ends_at_mid"
    assert want[-1][0] == TR.OUTSIDE


def test_modules_name_the_engine_programs(new):
    mods = new["modules"]
    assert PROGRAMS <= set(mods)
    assert "jit__unknown" not in mods
    for name in PROGRAMS:
        assert mods[name]["calls"] > 0 and mods[name]["device_s"] > 0


def test_idle_in_wait_lies_within_idle(new):
    idle = 100.0 * (1.0 - new["busy_s"] / new["window_s"])
    assert 0.0 < TR.idle_in_wait_share(new) <= idle


def test_span_and_module_names():
    assert TR.span_name("engine.prefill#rid=3,slot=0#") == "engine.prefill"
    assert TR.span_name("engine.decode") == "engine.decode"
    assert TR.module_name("jit_engine_decode_paged(1234)") == \
        "jit_engine_decode_paged"
