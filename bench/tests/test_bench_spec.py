"""Cells are found by name, and a later change adds a configuration, a
traffic mix or a per-layer metric by adding files; the entry point
refuses to run without a chip or without the program."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import tiny
from benchlib import spec

BENCHMARK = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_cell_resolves_to_its_files():
    for w in BENCHMARK["workloads"]:
        cell = spec.resolve(w["name"])
        assert cell.chips == 1
        assert cell.reference().served_gaps
        assert cell.arch().make_params and cell.kernels()
        assert cell.end_to_end and cell.per_layer
        for m in cell.per_layer:
            assert callable(cell.reader(m["name"]))
        buckets = cell.config["prefill_buckets"][cell.traffic_name]
        from benchlib.serve import prefill_buckets
        assert len(prefill_buckets(cell.config, cell.traffic)) == buckets


def test_benchmark_json_keeps_the_contract_shape():
    b = BENCHMARK
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and all(0.01 <= m["bound"] <= 0.25
                                    for m in e2e.values())
    cells = {w["name"] for w in b["workloads"]}
    for m in b["per_layer"]:
        assert m["moves"] in e2e and set(m.get("workloads", cells)) <= cells
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for c in b["configs"]:
        assert (spec.ROOT / c["file"]).is_file()
        assert c["file"].startswith(b["paths"][0] + "/")


#: a second architecture: Mixtral's, with every call the harness makes
#: recorded
TWIN_ARCH = '''
from benchlib import spec

_MIXTRAL = spec.load_module(spec.BENCH / "archs" / "mixtral.py")
CALLS = []


def _recorded(name):
    def call(*args, **kw):
        CALLS.append(name)
        return getattr(_MIXTRAL, name)(*args, **kw)
    return call


for _name in ("full_shapes", "make_params", "program_config",
              "expert_pairs", "device_parts", "prefill_flops",
              "decode_flops"):
    globals()[_name] = _recorded(_name)
'''

#: a kernel counter for XLA's fusions, by the bytes of their result
FUSION_COUNTER = '''
import re

KERNELS = ("fusion",)


def shapes(text):
    m = re.search(r" = [a-z0-9]+\\[([0-9,]*)\\]", text)
    return tuple(int(x) for x in m.group(1).split(",") if x) if m else None


def flops(name, shape, c):
    return 0.0


def nbytes(name, shape, c):
    n = 2.0
    for d in shape:
        n *= d
    return n


def least_s(name, shape, pk, c):
    return nbytes(name, shape, c) / pk["hbm_bytes_per_s"]
'''

FUSION_READER = '''
def read(ctx):
    k = ctx["trace"]["kernels"].get("fusion")
    return 100.0 * k["least_s"] / k["device_s"] if k else None
'''


def test_a_new_config_traffic_and_metric_are_files_only(tmp_path):
    """Registers throwaway files in a temporary root and resolves them by
    the names a BENCHMARK.json gives them: a configuration of a second
    architecture, whose file set-up and the window call, and a kernel
    counter with its reader, which the trace reduction uses on a window
    recorded on the chip."""
    root = tiny.make_root(tmp_path)
    for d in ("metrics", "archs", "kernels"):
        (root / "bench" / d).mkdir()
    (root / "bench" / "metrics" / "answer_ms.py").write_text(
        "def read(ctx):\n    return 42.0 if ctx else None\n")
    (root / "bench" / "archs" / "twin.py").write_text(TWIN_ARCH)
    (root / "bench" / "kernels" / "fusion.py").write_text(FUSION_COUNTER)
    (root / "bench" / "metrics" / "fusion_roofline.py").write_text(
        FUSION_READER)
    conf = root / "bench" / "configs" / "tiny.json"
    conf.write_text(json.dumps(dict(json.loads(conf.read_text()),
                                    arch="twin")))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"] = [{"name": "answer_ms", "unit": "ms",
                           "better": "lower", "source": "program_span",
                           "layer": "test", "moves": "ttft_p95_ms",
                           "workloads": ["tiny.closed"]}]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.resolve("tiny.closed", root=root, bench=bench,
                        bench_dir=root / "bench")
    assert cell.config["hidden_size"] == tiny.tiny_config()["hidden_size"]
    assert cell.traffic["loop"] == "closed"
    assert [m["name"] for m in cell.per_layer] == ["answer_ms"]
    assert cell.reader("answer_ms")({"x": 1}) == 42.0
    with pytest.raises(KeyError):
        spec.resolve("tiny.absent", root=root, bench=bench,
                     bench_dir=root / "bench")

    from benchlib import roofline, serve
    from benchlib import trace as TR
    calls = cell.arch().CALLS
    setup = serve.Setup(cell, 2**33 + 5)
    try:
        assert calls == ["program_config", "full_shapes", "make_params",
                         "expert_pairs"]
        assert setup.engine.current_plan.bits.size == 16
        parts = setup.device_bytes()
        win = serve.run_window(setup, 0.3)
    finally:
        setup.close()
    assert set(parts) == {"train_copy_device", "train_copy_host", "banks",
                          "kv_pool"} and parts["train_copy_host"] == 0
    assert {"device_parts", "prefill_flops", "decode_flops"} <= set(calls)
    assert win.prefill_flops > 0 and win.decode_flops > 0

    import jax
    pd = jax.profiler.ProfileData.from_file(
        str(spec.BENCH / "tests" / "data" / "chat_window.xplane.pb"))
    red = TR.reduce(pd, roofline.peaks("TPU v5 lite"), cell.config,
                    cell.kernels())
    assert set(red["kernels"]) == {"fusion"}
    k = red["kernels"]["fusion"]
    assert k["calls"] > k["unshaped"] and k["bytes"] > 0
    assert 0 < cell.reader("fusion_roofline")({"trace": red}) < 100


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         BENCHMARK["workloads"][0]["name"], "--seed", str(2**33 + 1),
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_without_a_tpu():
    p = _run(spec.ROOT)
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_run_refuses_without_the_program(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert "{" not in p.stdout
