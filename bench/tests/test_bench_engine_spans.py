"""The engine-span reduction (``benchlib.engine_spans``) on two windows of
the chat cell recorded on one TPU v5e: one from before the engine wrote
spans, on which the harness's reduction is pinned and the engine-span
reduction must read the harness's spans alone, and one with the engine's
spans and program names."""
from __future__ import annotations

from pathlib import Path

import pytest

from benchlib import engine_spans as ES
from benchlib import roofline
from benchlib import trace as TR

DATA = Path(__file__).parent / "data"
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
    return (TR.reduce(new_pd, roofline.peaks("TPU v5 lite"), 64),
            ES.reduce(new_pd))


def _host_events(pd):
    return [(ES.span_name(ev.name), ev.start_ns, ev.start_ns + ev.duration_ns,
             dict(ev.stats))
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ES.span_name(ev.name).startswith(("engine.", "bench."))]


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
    mods = new[1]["modules"]["jit_engine_decode_paged"]
    assert mods["device_s"] / mods["calls"] <= sum(decode) / len(decode) \
        * 1e-9


def test_harness_reduction_reads_as_before(old):
    r = TR.reduce(old, roofline.peaks("TPU v5 lite"), 64)
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
    r = TR.reduce(old, None, 64)
    e = ES.reduce(old)
    assert e["idle_gaps"] == r["breakdown"]["idle_gaps"]
    assert set(e["idle_by_span"]) <= set(TR.HOST_SPANS)
    # before program names: the engine's prefill and decode were unnamed
    assert "jit__unknown" in e["modules"]


def test_idle_gaps_name_engine_spans(new):
    r, e = new
    names = [g[0] for g in e["idle_gaps"]]
    assert any(n.startswith(ES.ENGINE) for n in names)
    # the harness's own reduction still names only its spans
    assert all(g[0] in TR.HOST_SPANS + ("outside harness spans",)
               for g in r["breakdown"]["idle_gaps"])
    assert sum(e["idle_by_span"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-9)


def test_modules_name_the_engine_programs(new):
    _, e = new
    mods = e["modules"]
    assert PROGRAMS <= set(mods)
    assert "jit__unknown" not in mods
    for name in PROGRAMS:
        assert mods[name]["calls"] > 0 and mods[name]["device_s"] > 0


def test_idle_in_wait_lies_within_idle(new):
    r, e = new
    idle = 100.0 * (1.0 - r["busy_s"] / r["window_s"])
    assert 0.0 <= ES.idle_in_wait_share(e, r["window_s"]) <= idle


def test_span_and_module_names():
    assert ES.span_name("engine.prefill#rid=3,slot=0#") == "engine.prefill"
    assert ES.span_name("engine.decode") == "engine.decode"
    assert ES.module_name("jit_engine_decode_paged(1234)") == \
        "jit_engine_decode_paged"
