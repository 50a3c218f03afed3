"""Grouped multi-expert kernel parity vs the per-expert loop
(DESIGN.md §13).

The grouped kernel fuses a whole precision bank into ONE pallas_call with
the expert group as the leading grid axis; the contract is that it is
BIT-IDENTICAL to looping ``q_matmul`` over experts (the spelling it
replaces) for the quantized rungs, and allclose vs the einsum reference
for the bf16 bank (f32 VMEM accumulation vs XLA's reduction order).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.quantization import QTensor, quantize
from repro.kernels.ops import (
    grouped_bf16_matmul, grouped_q_matmul, q_expert_matmul, q_matmul,
)


def make_bank(e, c, k, n, bits, group, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((e, c, k)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((e, k, n)) / np.sqrt(k),
                    jnp.float32)
    return x, quantize(w, bits, group)


def loop_ref(x, qt):
    """The per-expert spelling the grouped kernel replaces — shares
    q_matmul's tile-selection logic, which is what makes the grouped
    path's bit-identity a meaningful (and testable) contract."""
    outs = [q_matmul(x[e], QTensor(q=qt.q[e], scales=qt.scales[e],
                                   bits=qt.bits, group_size=qt.group_size))
            for e in range(x.shape[0])]
    return jnp.stack(outs)


def assert_bit_equal(got, want):
    assert got.dtype == want.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got.view(jnp.uint16)),
                                  np.asarray(want.view(jnp.uint16)))


class TestGroupedQuantParity:
    #: (experts_in_group, capacity, K, N, group_size) — capacity sweeps
    #: unaligned M tiles; K/N=192 fall back to whole-dim tiles (ops._tile);
    #: group_size=32 exercises a non-default scale granularity
    CASES = [
        (1, 8, 128, 128, 64),
        (3, 5, 128, 256, 64),
        (8, 16, 256, 128, 64),
        (4, 8, 192, 192, 64),
        (2, 20, 128, 128, 32),
        (6, 1, 128, 128, 64),
    ]

    @pytest.mark.parametrize("bits", [4, 8])
    @pytest.mark.parametrize("e,c,k,n,group", CASES)
    def test_bit_exact_vs_expert_loop(self, e, c, k, n, group, bits):
        x, qt = make_bank(e, c, k, n, bits, group)
        got = grouped_q_matmul(x, qt)
        assert got.shape == (e, c, n)
        assert_bit_equal(got, loop_ref(x, qt))

    @pytest.mark.parametrize("bits", [4, 8])
    def test_dispatch_spellings_agree(self, bits):
        """q_expert_matmul(grouped=True) == the legacy vmap spelling
        (grouped=False), bit for bit — the A/B the benchmark times."""
        x, qt = make_bank(4, 8, 128, 128, bits, 64)
        assert_bit_equal(q_expert_matmul(x, qt, grouped=True),
                         q_expert_matmul(x, qt, grouped=False))

    def test_bf16_grouped_allclose_einsum(self):
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.standard_normal((5, 8, 128)), jnp.bfloat16)
        w = jnp.asarray(rng.standard_normal((5, 128, 256)) / np.sqrt(128),
                        jnp.bfloat16)
        got = grouped_bf16_matmul(x, w)
        ref = jnp.einsum("ecd,edf->ecf", x.astype(jnp.float32),
                         w.astype(jnp.float32))
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(ref), rtol=5e-2, atol=5e-2)

    @given(st.integers(2, 6), st.integers(1, 12), st.sampled_from([4, 8]),
           st.integers(0, 5))
    @settings(max_examples=8, deadline=None)
    def test_empty_group_contributes_exact_zeros(self, e, c, bits, which):
        """An expert with no routed tokens (all-zero activation rows — how
        the capacity-grouped layout encodes an empty group) must produce
        EXACT zeros: 0 @ dequant(W) has no rounding path."""
        which = which % e
        x, qt = make_bank(e, c, 128, 128, bits, 64, seed=e * 100 + c)
        x = x.at[which].set(0)
        out = grouped_q_matmul(x, qt)
        np.testing.assert_array_equal(
            np.asarray(out[which], np.float32),
            np.zeros((c, 128), np.float32))

    def test_shape_validation(self):
        x, qt = make_bank(4, 8, 128, 128, 4, 64)
        bad = QTensor(q=qt.q[:3], scales=qt.scales[:3], bits=4,
                      group_size=64)
        with pytest.raises((ValueError, TypeError)):
            grouped_q_matmul(x, bad)
