"""Compile the Pallas kernels, and the served decode step, for a described
TPU v5e at Mixtral-8x7B's expert widths — no chip attached.

The TPU compiler refuses what interpret mode accepts: misaligned block
shapes, 8-bit vector arithmetic, kernels over the VMEM limit. Each case
must lower to a ``tpu_custom_call``. The topology is described inside a
fixture (never at import): only one process may hold the TPU library, so
describing it while modules are collected would break the other test
workers.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.quantization import QTensor
from repro.kernels import ops

D_MODEL, D_FF, GROUP, EXPERTS = 4096, 14336, 64, 8


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A described-chip compile is written to the persistent cache but
    cannot be read back without the chip; keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_persistent_cache):
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


def _q_args(sharding, lead, k, n, bits):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(lead + shape, dtype, sharding=sharding)
    q = s((k // 2, n), jnp.uint8) if bits == 4 else s((k, n), jnp.int8)
    return q, s((k // GROUP, n), jnp.bfloat16)


def _assert_kernel(fn, *args, name):
    text = jax.jit(fn).lower(*args).compile().as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert any(name in ln for ln in calls), f"no {name} tpu_custom_call"


#: (K, N) of the up/gate and down projections of one Mixtral expert
PROJ = {"up": (D_MODEL, D_FF), "down": (D_FF, D_MODEL)}
#: decode (M=8 rows per expert group) and prefill (M=128)
ROWS = [8, 128]
#: rows per expert group of a 64-row decode batch
DECODE_BATCH_ROWS = 64


def _assert_grouped_q_kernel(sharding, bits, proj, m):
    k, n = PROJ[proj]
    q, sc = _q_args(sharding, (EXPERTS,), k, n, bits)
    x = jax.ShapeDtypeStruct((EXPERTS, m, k), jnp.bfloat16,
                             sharding=sharding)

    def fn(x, q, sc):
        return ops.grouped_q_matmul(
            x, QTensor(q=q, scales=sc, bits=bits, group_size=GROUP),
            interpret=False)
    _assert_kernel(fn, x, q, sc, name=f"grouped_q{bits}_matmul")


@pytest.mark.parametrize("m", ROWS)
@pytest.mark.parametrize("proj", sorted(PROJ))
@pytest.mark.parametrize("bits", [4, 8])
def test_grouped_q_kernel_compiles(one_chip, bits, proj, m):
    _assert_grouped_q_kernel(one_chip, bits, proj, m)


@pytest.mark.parametrize("proj", sorted(PROJ))
def test_grouped_q4_kernel_compiles_at_decode_batch_rows(one_chip, proj):
    """The int4 kernel at the 64 rows per expert group of a 64-row decode
    batch (top-2 of 8, capacity factor 4): x reordered to the tile's
    [even K | odd K] order and the nibble halves joined along sublanes."""
    _assert_grouped_q_kernel(one_chip, 4, proj, DECODE_BATCH_ROWS)


@pytest.mark.parametrize("m", ROWS)
@pytest.mark.parametrize("proj", sorted(PROJ))
def test_grouped_bf16_kernel_compiles(one_chip, proj, m):
    k, n = PROJ[proj]
    x = jax.ShapeDtypeStruct((EXPERTS, m, k), jnp.bfloat16,
                             sharding=one_chip)
    w = jax.ShapeDtypeStruct((EXPERTS, k, n), jnp.bfloat16,
                             sharding=one_chip)
    _assert_kernel(functools.partial(ops.grouped_bf16_matmul,
                                     interpret=False),
                   x, w, name="grouped_bf16_matmul")


@pytest.mark.parametrize("m", ROWS)
@pytest.mark.parametrize("proj", sorted(PROJ))
@pytest.mark.parametrize("bits", [4, 8])
def test_per_expert_q_kernel_compiles(one_chip, bits, proj, m):
    k, n = PROJ[proj]
    q, sc = _q_args(one_chip, (), k, n, bits)
    x = jax.ShapeDtypeStruct((m, k), jnp.bfloat16, sharding=one_chip)

    def fn(x, q, sc):
        return ops.q_matmul(
            x, QTensor(q=q, scales=sc, bits=bits, group_size=GROUP),
            interpret=False)
    _assert_kernel(fn, x, q, sc, name=f"q{bits}_matmul")


def test_served_decode_step_compiles_with_every_rung(topo, monkeypatch):
    """The engine's paged decode step, two Mixtral layers at published
    widths on a three-rung plan, compiles for one chip and runs one
    kernel per rung. The kernels' backend check would see this
    process's CPU, so the test pins compiled (not interpreted) mode."""
    import dataclasses

    from repro.configs import get_config
    from repro.core.cost_model import HardwareModel
    from repro.core.planner import AdaptivePlanner
    from repro.models.model import (abstract_params, apply_precision_plan,
                                    build_model, init_paged_cache)

    monkeypatch.setattr(ops, "_interpret",
                        lambda i: False if i is None else i)
    cfg = get_config("mixtral-8x7b")
    cfg = cfg.replace(num_layers=2, mop=dataclasses.replace(
        cfg.mop, ladder=(16, 8, 4)))
    plan = AdaptivePlanner(cfg, hw=HardwareModel()).replan(
        1e12, "quality", counts={8: 6, 4: 6}, batch_size=4)[0].plan
    assert set(np.unique(plan.bits)) == {4, 8, 16}
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1),
                ("data", "model"))
    repl = NamedSharding(mesh, P())

    def placed(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=repl)
    params = jax.tree_util.tree_map(placed, jax.eval_shape(
        lambda p: apply_precision_plan(p, cfg, plan), abstract_params(cfg)))
    pool, meta = init_paged_cache(cfg, 4, 32, abstract=True)
    pool = jax.tree_util.tree_map(placed, pool)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=repl)
    model = build_model(cfg, mesh, use_kernel=True)
    step = functools.partial(model.paged_decode_step_routed,
                             window=meta.window)
    text = jax.jit(step).lower(
        params, pool, i32(4, meta.chunks_per_slot), i32(4, 1), i32(4)
    ).compile().as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    for name in ("grouped_q4_matmul", "grouped_q8_matmul",
                 "grouped_bf16_matmul"):
        assert any(name in ln for ln in calls), name


#: (K, N) of DeepSeek-V2-Lite's expert projections: width 1408 = 11 x 128,
#: which the default tiles do not divide; 48 rows per expert group (the
#: dropless buffer of a 48-row decode batch)
LITE_PROJ = {"up": (2048, 1408), "down": (1408, 2048)}
LITE_EXPERTS, LITE_ROWS = 16, 48


@pytest.mark.parametrize("proj", sorted(LITE_PROJ))
@pytest.mark.parametrize("bits", [4, 8, 16])
def test_grouped_kernels_compile_at_width_1408(one_chip, bits, proj):
    k, n = LITE_PROJ[proj]
    x = jax.ShapeDtypeStruct((LITE_EXPERTS, LITE_ROWS, k), jnp.bfloat16,
                             sharding=one_chip)
    if bits == 16:
        w = jax.ShapeDtypeStruct((LITE_EXPERTS, k, n), jnp.bfloat16,
                                 sharding=one_chip)
        _assert_kernel(functools.partial(ops.grouped_bf16_matmul,
                                         interpret=False),
                       x, w, name="grouped_bf16_matmul")
        return
    q, sc = _q_args(one_chip, (LITE_EXPERTS,), k, n, bits)

    def fn(x, q, sc):
        return ops.grouped_q_matmul(
            x, QTensor(q=q, scales=sc, bits=bits, group_size=GROUP),
            interpret=False)
    _assert_kernel(fn, x, q, sc, name=f"grouped_q{bits}_matmul")


def test_served_latent_decode_step_compiles(topo, monkeypatch):
    """The engine's paged decode step for DeepSeek-V2-Lite at published
    widths (a dense layer and four expert layers, latent attention over
    the latent pool) on a three-rung plan compiles for one chip, runs one
    kernel per rung, and holds no more than two layers' page views in
    temporaries: each layer reads its own pages in place."""
    import dataclasses

    from repro.configs import get_config
    from repro.core.cost_model import HardwareModel
    from repro.core.planner import AdaptivePlanner
    from repro.models.model import (abstract_params, apply_precision_plan,
                                    build_model, init_paged_cache)

    monkeypatch.setattr(ops, "_interpret",
                        lambda i: False if i is None else i)
    cfg = get_config("deepseek-v2-lite")
    cfg = cfg.replace(num_layers=5, mop=dataclasses.replace(
        cfg.mop, ladder=(16, 8, 4)), moe=dataclasses.replace(
            cfg.moe, capacity_factor=64 / 6))
    plan = AdaptivePlanner(cfg, hw=HardwareModel()).replan(
        1e12, "quality", counts={8: 64, 4: 128}, batch_size=8)[0].plan
    assert set(np.unique(plan.bits)) == {4, 8, 16}
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1),
                ("data", "model"))
    repl = NamedSharding(mesh, P())

    def placed(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=repl)
    params = jax.tree_util.tree_map(placed, jax.eval_shape(
        lambda p: apply_precision_plan(p, cfg, plan), abstract_params(cfg)))
    slots, window = 8, 1024
    pool, meta = init_paged_cache(cfg, slots, window, page_size=256,
                                  abstract=True)
    pool = jax.tree_util.tree_map(placed, pool)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=repl)
    model = build_model(cfg, mesh, use_kernel=True)
    step = functools.partial(model.paged_decode_step_routed,
                             window=meta.window)
    compiled = jax.jit(step).lower(
        params, pool, i32(slots, meta.chunks_per_slot), i32(slots, 1),
        i32(slots)).compile()
    calls = [ln for ln in compiled.as_text().splitlines()
             if "tpu_custom_call" in ln]
    for name in ("grouped_q4_matmul", "grouped_q8_matmul",
                 "grouped_bf16_matmul"):
        assert any(name in ln for ln in calls), name
    view = slots * window * cfg.attention.kv_values_per_position * 2
    assert compiled.memory_analysis().temp_size_in_bytes < \
        2 * view + (64 << 20)
