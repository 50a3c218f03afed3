"""The trace reduction, on a short window of the chat cell recorded on
one TPU v5e, the grouped kernels' counter and the model's operation
counts."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from benchlib import roofline, spec
from benchlib import trace as TR

TRACE = Path(__file__).parent / "data" / "chat_window.xplane.pb"
CONFIG = json.loads((spec.BENCH / "configs" / "mixtral-8x7b-mixed.json")
                    .read_text())
GROUPED = spec.load_module(spec.BENCH / "kernels" / "grouped_matmul.py")
MIXTRAL = spec.load_module(spec.BENCH / "archs" / "mixtral.py")
Q4 = ("%grouped_q4_matmul.1 = bf16[2,32,14336]{2,1,0:T(8,128)(2,1)S(1)} "
      "custom-call(bf16[2,32,4096]{2,1,0:T(8,128)(2,1)} %x.1, "
      "u8[2,2048,14336]{2,1,0:T(8,128)(4,1)} %q4_q.1, "
      "bf16[2,64,14336]{2,1,0:T(8,128)(2,1)} %q4_scales.1), "
      'custom_call_target="tpu_custom_call"')


@pytest.fixture(scope="module")
def reduced():
    import jax
    pd = jax.profiler.ProfileData.from_file(str(TRACE))
    return TR.reduce(pd, roofline.peaks("TPU v5 lite"), CONFIG,
                     spec.kernel_counters())


def test_busy_time_lies_inside_the_window(reduced):
    assert reduced["devices"] == 1
    assert 0 < reduced["busy_s"] <= reduced["window_s"]


def test_every_rung_kernel_is_found_with_its_shapes(reduced):
    assert set(reduced["kernels"]) == set(GROUPED.KERNELS)
    for k in reduced["kernels"].values():
        assert k["calls"] > 0 and k["unshaped"] == 0
        assert 0 < k["least_s"] <= k["device_s"]


def test_breakdown_is_short_and_names_leaf_ops(reduced):
    ops = reduced["breakdown"]["device_ops"]
    gaps = reduced["breakdown"]["idle_gaps"]
    assert 0 < len(ops) <= 10 and len(gaps) <= 10
    assert not any(n.startswith("%while") for n, _ in ops)
    assert all(g[0] in TR.HOST_SPANS + (TR.OUTSIDE,) for g in gaps)


def test_kernel_names_and_shapes_from_hlo_text():
    pattern = TR.kernel_pattern(spec.kernel_counters())
    assert TR.kernel_of(Q4, pattern) == "grouped_q4_matmul"
    assert TR.kernel_of("%fusion.3 = bf16[2] fusion(grouped_q4_matmul.1)",
                        pattern) is None
    assert GROUPED.shapes(Q4) == (2, 32, 4096, 14336)


def test_union_of_overlapping_ops():
    total, pieces = TR.union_length([(0, 10), (5, 12), (20, 25), (21, 22)])
    assert total == 17 and pieces == [[0, 12], [20, 25]]


def test_kernel_call_arithmetic():
    k, shape = "grouped_q4_matmul", (2, 32, 4096, 14336)
    assert GROUPED.flops(k, shape, CONFIG) == 2 * 2 * 32 * 4096 * 14336
    weights = 2 * 4096 * 14336 // 2 + 2 * 64 * 14336 * 2
    acts = 2 * 2 * 32 * 4096 + 2 * 2 * 32 * 14336
    nbytes = GROUPED.nbytes(k, shape, CONFIG)
    assert nbytes == weights + acts
    pk = roofline.peaks("TPU v5 lite")
    assert GROUPED.least_s(k, shape, pk, CONFIG) == \
        pytest.approx(nbytes / 819e9)
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


def test_model_operations_count_each_token_once():
    c = {"hidden_size": 8, "intermediate_size": 16, "num_attention_heads": 2,
         "num_key_value_heads": 1, "head_dim": 4, "num_local_experts": 4,
         "num_experts_per_tok": 2, "num_hidden_layers": 3, "vocab_size": 10}
    one = MIXTRAL.layer_flops(c, 0)
    assert MIXTRAL.prefill_flops(c, 1) == pytest.approx(
        3 * (one + 4 * 2 * 4) + 2 * 8 * 10)
    assert MIXTRAL.decode_flops(c, 5) == pytest.approx(
        3 * MIXTRAL.layer_flops(c, 5) + 2 * 8 * 10)
