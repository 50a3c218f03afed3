"""The engine's spans, counters and program names (serving/tracing.py):
the counters add up over a served run, a profiler trace of two
iterations holds every span of the paged path nested under
``engine.iteration``, and every jitted program carries a stable name."""
import glob
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_for_smoke
from repro.core.pareto import QoSTarget
from repro.models.model import build_model
from repro.serving.api import EngineConfig
from repro.serving.engine import AdaptiveServingEngine

PAGE = 8
PAGED_SPANS = {"engine.iteration", "engine.admit", "engine.prefill",
               "engine.prefill.dispatch", "engine.prefill.wait",
               "engine.sample", "engine.kv_map", "engine.decode",
               "engine.decode.dispatch", "engine.decode.wait",
               "engine.stream_experts", "engine.retire",
               "engine.kv_release"}


@pytest.fixture(scope="module")
def smoke():
    cfg = reduce_for_smoke(get_config("mixtral-8x7b"))
    return cfg, build_model(cfg).init(jax.random.key(0))


def _engine(smoke):
    cfg, params = smoke
    eng = AdaptiveServingEngine(cfg, params, config=EngineConfig(
        max_slots=2, max_len=32, page_size=PAGE))
    eng.apply_target(QoSTarget(min_tokens_per_s=float("inf")))
    return eng


def test_counters_add_up(smoke):
    eng = _engine(smoke)
    rows = []
    update = eng._update_kv_metrics

    def count_rows(active):
        rows.append(len(active))
        update(active)
    eng._update_kv_metrics = count_rows
    prompts = [np.arange(1, 6), np.arange(1, 10), np.arange(2, 5)]
    for p, n in zip(prompts, (3, 1, 4)):
        eng.submit(p, max_new_tokens=n)
    t0 = time.perf_counter()
    assert eng.step() == 3
    wall = time.perf_counter() - t0
    m = eng.metrics
    assert m["decode_rows"] == sum(rows) > 0
    # each prefill and each decoded row emits exactly one token
    assert m["decode_rows"] == m["tokens_generated"] - m["prefill_calls"]
    assert m["prefill_calls"] == len(prompts)
    assert m["prefill_tokens"] == sum(len(p) for p in prompts)
    assert m["prefill_padded_tokens"] == sum(
        -(-len(p) // PAGE) * PAGE for p in prompts)
    assert m["prefill_padded_tokens"] >= m["prefill_tokens"]
    for k in ("sample_s", "kv_release_s", "prefill_s", "decode_s"):
        assert 0 < m[k] < wall, k
    eng.reset_counters()
    for k in ("sample_s", "kv_release_s", "prefill_calls",
              "prefill_tokens", "prefill_padded_tokens", "decode_rows"):
        assert m[k] == 0, k
    eng.close()


def _host_spans(tdir):
    """(name, start, end, line) of every ``engine.*`` event on the host."""
    path, = glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    return [(ev.name.split("#", 1)[0], ev.start_ns,
             ev.start_ns + ev.duration_ns, line.name)
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith("engine.")]


def test_trace_holds_spans_nested_under_iteration(smoke, tmp_path):
    eng = _engine(smoke)
    eng.submit(np.arange(1, 6), max_new_tokens=2)   # retires in the first
    eng.submit(np.arange(1, 10), max_new_tokens=4)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):
            eng.run_iteration()
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(tmp_path)
    assert {s[0] for s in spans} == PAGED_SPANS
    its = [s for s in spans if s[0] == "engine.iteration"]
    assert len(its) == 2
    for name, a, b, line in spans:
        if name != "engine.iteration":
            assert any(ia <= a and b <= ib and il == line
                       for _, ia, ib, il in its), name
    # the inner spans sit inside their parents
    for child, parent in (("engine.prefill.wait", "engine.prefill"),
                          ("engine.decode.wait", "engine.decode"),
                          ("engine.kv_release", "engine.retire")):
        for _, a, b, _ in (s for s in spans if s[0] == child):
            assert any(pa <= a and b <= pb for n, pa, pb, _ in spans
                       if n == parent), child
    eng.close()


def test_programs_carry_stable_names(smoke):
    eng = _engine(smoke)
    eng.submit(np.arange(1, 6), max_new_tokens=2)
    eng.step()
    assert set(eng._compiled) == {("prefill_paged", PAGE), "decode_paged",
                                  "paged_reset"}
    assert {f.__name__ for f in eng._compiled.values()} == {
        "engine_prefill_paged", "engine_decode_paged", "engine_paged_reset"}
    b = eng.max_slots
    lowered = eng._compiled["decode_paged"].lower(
        eng.serve_params, eng.kv_pool, jnp.asarray(eng.kv_alloc.table),
        jnp.zeros((b, 1), jnp.int32), jnp.full((b,), -1, jnp.int32))
    assert lowered.as_text().startswith("module @jit_engine_decode_paged ")
    eng.close()


@pytest.mark.parametrize("knobs", [{"paged_kv": False}, {"speculate": 2},
                                   {"overlap": True}],
                         ids=["slot_cache", "speculative", "pipelined"])
def test_other_paths_write_iteration_prefill_decode(smoke, tmp_path, knobs):
    cfg, params = smoke
    eng = AdaptiveServingEngine(cfg, params, config=EngineConfig(
        max_slots=2, max_len=32, page_size=PAGE, **knobs))
    eng.apply_target(QoSTarget(min_tokens_per_s=float("inf")))
    eng.submit(np.arange(1, 6), max_new_tokens=3)
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.step()
    finally:
        jax.profiler.stop_trace()
    names = {s[0] for s in _host_spans(tmp_path)}
    assert {"engine.iteration", "engine.prefill", "engine.decode"} <= names
    assert eng.metrics["decode_s"] > 0 and eng.metrics["decode_rows"] > 0
    assert all(f.__name__.startswith("engine_")
               for f in eng._compiled.values())
    eng.close()
