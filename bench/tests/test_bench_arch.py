"""Architecture files (``bench/archs/``) and what the harness asks of them:
the Mixtral file reads as the harness did before it had architecture
files (values pinned from that harness), the layout guard holds, set-up
reckons the train copy by where each leaf lives, and the engine-counter
readers read nothing where the program lacks the counter."""
from __future__ import annotations

import dataclasses
import hashlib
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
from benchlib import serve, spec

BENCHMARK = spec.load_benchmark()
CONFIGS = {c["name"]: spec.ROOT / c["file"] for c in BENCHMARK["configs"]}

#: sha256 of the test-size tree from seed 2**40 + 3 (``_digest``), and at
#: published widths the program's configuration, the pinned point and the
#: operations of a 512-token prompt and of a token decoded at context
#: 1024, all as the harness read them before architecture files
TREE_DIGEST = "64c5638db42d7380c438fa0a4e70f1ba352c1035377c71d4970de2864f9be69d"
PROGRAM = (2, 4096, 14336, 32000, 32, 8, 128, None, 1000000.0, 8, 2, 14336,
           4.0, (16, 8, 4), 64)
PINNED = {"mixtral-8x7b-mixed": (12, 16, (4, 4, 8), 0, float(1 << 50)),
          "mixtral-8x7b-q4": (16, 16, (0, 0, 16), 0, float(1 << 50))}
PREFILL_512 = 812086460416.0
DECODE_1024 = 1872887808.0


def _arch(c: dict):
    return spec.load_module(spec.BENCH / "archs" / f"{c['arch']}.py")


def _digest(tree) -> str:
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, leaf in sorted(leaves,
                             key=lambda kv: jax.tree_util.keystr(kv[0])):
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)}|{a.shape}|{a.dtype}|"
                 .encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_tree_is_bit_identical_to_the_harness_before(name):
    c = tiny.tiny_config(CONFIGS[name])
    assert _digest(_arch(c).make_params(c, 2**40 + 3)) == TREE_DIGEST


@dataclasses.dataclass
class _Qos:
    device_bytes: float = 0.0


@dataclasses.dataclass
class _Point:
    num_q_experts: int = 0
    resident_experts: int = 0
    counts_per_rung: tuple = ()
    peer_experts: int = 3
    qos: _Qos = dataclasses.field(default_factory=_Qos)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_program_config_point_and_operations_as_before(name):
    c = json.loads(CONFIGS[name].read_text())
    arch = _arch(c)
    cfg = serve.program_config(arch, c)
    a, m = cfg.attention, cfg.moe
    assert (cfg.num_layers, cfg.d_model, cfg.d_ff, cfg.vocab_size,
            a.num_heads, a.num_kv_heads, a.head_dim, a.sliding_window,
            a.rope_theta, m.num_experts, m.top_k, m.d_ff_expert,
            m.capacity_factor, cfg.mop.ladder, cfg.mop.group_size) == PROGRAM
    engine = types.SimpleNamespace(
        frontier=types.SimpleNamespace(points=[_Point()]))
    p = serve.pinned_point(engine, c, arch.expert_pairs(c))
    assert (p.num_q_experts, p.resident_experts, p.counts_per_rung,
            p.peer_experts, p.qos.device_bytes) == PINNED[name]
    assert arch.prefill_flops(c, 512) == PREFILL_512
    assert arch.decode_flops(c, 1024) == DECODE_1024


def test_layout_guard_refuses_another_tree():
    c = tiny.tiny_config()
    arch = _arch(c)
    other = types.SimpleNamespace(
        program_config=arch.program_config,
        full_shapes=lambda c: dict(arch.full_shapes(c),
                                   **{"layers/moe/w_up": (1, 1)}))
    with pytest.raises(RuntimeError, match="parameter layout"):
        serve.program_config(other, c)


def test_train_copy_is_reckoned_where_each_leaf_lives():
    dev = jax.devices()[0]
    host = jax.sharding.SingleDeviceSharding(dev, memory_kind="pinned_host")
    fake = types.SimpleNamespace(
        params={"a": jnp.ones(4, jnp.float32),
                "b": {"c": jax.device_put(jnp.ones(8, jnp.float32), host),
                      "d": np.ones(2, np.float64)}},
        engine=None,
        arch=types.SimpleNamespace(device_parts=lambda engine: {
            "banks": {"x": jnp.ones(3, jnp.bfloat16)},
            "kv_pool": jnp.ones(5, jnp.int8)}))
    assert serve.Setup.device_bytes(fake) == {
        "train_copy_device": 16, "train_copy_host": 32 + 16, "banks": 6,
        "kv_pool": 5}


COUNTED = {"iterations": 10.0, "decode_rows": 30.0, "prefill_tokens": 60.0,
           "prefill_padded_tokens": 80.0, "sample_s": 0.02,
           "kv_release_s": 0.005}
TRACE = {"window_s": 2.0, "busy_s": 1.5, "engine_spans": 7,
         "idle_by_span": {"engine.decode.wait": 0.1,
                          "engine.prefill.wait": 0.02, "bench.wait": 0.3},
         "modules": {"jit_engine_decode_paged": {"device_s": 0.5,
                                                 "calls": 10}}}


@pytest.mark.parametrize("name,value", [
    ("batch_occupancy", 100.0 * 30 / (10 * 4)),
    ("prefill_pad_share", 25.0),
    ("sample_ms_per_step", 2.0),
    ("kv_release_ms_per_step", 0.5),
    ("decode_device_ms", 50.0),
    ("device_idle_in_wait_share", 6.0)])
def test_engine_counter_readers(name, value):
    """Each reads its counter, span or program, and nothing where the
    program does not write it."""
    read = spec.load_module(spec.BENCH / "metrics" / f"{name}.py").read
    ctx = {"win": types.SimpleNamespace(counters=dict(COUNTED)),
           "trace": TRACE, "traffic": {"slots": 4}}
    assert read(ctx) == pytest.approx(value, rel=1e-12)
    missing = dict(COUNTED, **{k: None for k in COUNTED
                               if k != "iterations"})
    bare = dict(TRACE, engine_spans=0, idle_by_span={"bench.wait": 0.4},
                modules={"jit__unknown": {"device_s": 1.0, "calls": 3}})
    assert read({"win": types.SimpleNamespace(counters=missing),
                 "trace": bare, "traffic": {"slots": 4}}) is None
