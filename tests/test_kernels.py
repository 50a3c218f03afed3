"""Per-kernel allclose vs the pure-jnp oracle (interpret mode on CPU).

Sweeps shapes/dtypes per the deliverable-(c) requirement and adds
hypothesis property tests on tiling invariance.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.quantization import QTensor, quantize
from repro.kernels import (
    expert_matmul_ref, q_expert_matmul, q_matmul, quantized_matmul_ref,
)
from repro.kernels.grouped_matmul import grouped_quantized_matmul
from repro.kernels.q4_matmul import quantized_matmul


def make_case(m, k, n, bits, group, seed=0, xdtype=jnp.bfloat16):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((m, k)), xdtype)
    w = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    return x, quantize(w, bits, group)


def assert_matches_oracle(out, x, qt, rtol=5e-2, atol=5e-2):
    ref = quantized_matmul_ref(x, qt.q, qt.scales, bits=qt.bits,
                               group_size=qt.group_size,
                               out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=rtol, atol=atol * float(
                                   jnp.abs(ref).max()))


class TestQ4MatmulKernel:
    @pytest.mark.parametrize("m,k,n", [
        (128, 128, 256), (128, 256, 256), (256, 512, 512), (128, 1024, 256),
    ])
    @pytest.mark.parametrize("bits", [4, 8])
    def test_shape_sweep(self, m, k, n, bits):
        x, qt = make_case(m, k, n, bits, 64)
        out = quantized_matmul(x, qt.q, qt.scales, bits=bits, group_size=64,
                               interpret=True)
        assert out.shape == (m, n) and out.dtype == jnp.bfloat16
        assert_matches_oracle(out, x, qt)

    @pytest.mark.parametrize("xdtype", [jnp.bfloat16, jnp.float32])
    @pytest.mark.parametrize("odtype", [jnp.bfloat16, jnp.float32])
    def test_dtype_sweep(self, xdtype, odtype):
        x, qt = make_case(128, 256, 256, 4, 64, xdtype=xdtype)
        out = quantized_matmul(x, qt.q, qt.scales, bits=4, group_size=64,
                               out_dtype=odtype, interpret=True)
        assert out.dtype == odtype
        assert_matches_oracle(out, x, qt)

    @pytest.mark.parametrize("group", [32, 64, 128])
    def test_group_sweep(self, group):
        x, qt = make_case(128, 256, 256, 4, group)
        out = quantized_matmul(x, qt.q, qt.scales, bits=4, group_size=group,
                               interpret=True)
        assert_matches_oracle(out, x, qt)

    @pytest.mark.parametrize("bm,bn,bk", [
        (128, 128, 128), (64, 256, 64), (128, 256, 256), (32, 128, 128),
    ])
    def test_tile_sweep(self, bm, bn, bk):
        x, qt = make_case(128, 512, 256, 4, 32)
        out = quantized_matmul(x, qt.q, qt.scales, bits=4, group_size=32,
                               block_m=bm, block_n=bn, block_k=bk,
                               interpret=True)
        assert_matches_oracle(out, x, qt)

    @given(mi=st.integers(1, 3), ki=st.integers(1, 4), ni=st.integers(1, 2),
           bits=st.sampled_from([4, 8]), seed=st.integers(0, 99))
    @settings(max_examples=12, deadline=None)
    def test_property_tiling_invariance(self, mi, ki, ni, bits, seed):
        """Output is independent of the tiling decomposition."""
        m, k, n = 64 * mi, 128 * ki, 128 * ni
        x, qt = make_case(m, k, n, bits, 32, seed)
        outs = [
            np.asarray(quantized_matmul(
                x, qt.q, qt.scales, bits=bits, group_size=32,
                block_m=bm, block_n=bn, block_k=bk, out_dtype=jnp.float32,
                interpret=True))
            for (bm, bn, bk) in ((64, 128, 128), (m, n, 32), (32, 128, 64))]
        np.testing.assert_allclose(outs[0], outs[1], rtol=2e-5, atol=1e-3)
        np.testing.assert_allclose(outs[0], outs[2], rtol=2e-5, atol=1e-3)
        assert_matches_oracle(outs[0], x, qt)

    def test_error_on_bad_scales(self):
        x, qt = make_case(128, 256, 128, 4, 64)
        with pytest.raises(ValueError):
            quantized_matmul(x, qt.q, qt.scales[:1], bits=4, group_size=64,
                             interpret=True)


class TestParityPropertyGrid:
    """Property-style parity vs the pure-jnp oracle across a SEEDED grid
    of shapes, group sizes and int4/int8 — including edge tiles where
    M/N/K are NOT multiples of the default (128, 256, 128) blocks, so
    the clamp/divisor tile-selection paths and the M-padding wrapper are
    exercised, not just the aligned fast path (interpret mode on CPU)."""

    @given(m=st.integers(1, 200), ki=st.integers(1, 5),
           ni=st.integers(1, 6), bits=st.sampled_from([4, 8]),
           group=st.sampled_from([32, 64]), seed=st.integers(0, 9999))
    @settings(max_examples=20, deadline=None)
    def test_wrapper_parity_any_shape(self, m, ki, ni, bits, group, seed):
        """q_matmul == oracle for arbitrary M (padded inside the
        wrapper) and K/N that are multiples of the group size but NOT of
        the default blocks (the wrapper shrinks tiles to divisors)."""
        k, n = group * ki, 32 * ni
        x, qt = make_case(m, k, n, bits, group, seed)
        out = q_matmul(x, qt, out_dtype=jnp.float32, interpret=True)
        assert out.shape == (m, n)
        assert_matches_oracle(out, x, qt)

    @given(mi=st.integers(1, 4), ki=st.integers(1, 4), ni=st.integers(1, 4),
           bits=st.sampled_from([4, 8]), seed=st.integers(0, 9999))
    @settings(max_examples=12, deadline=None)
    def test_kernel_parity_odd_explicit_tiles(self, mi, ki, ni, bits,
                                              seed):
        """The raw kernel with deliberately odd (non-default,
        non-square) tile choices: 3 tiles per axis of sizes that never
        equal the defaults. Output must not depend on the tiling."""
        m, k, n = 32 * mi, 96 * ki, 96 * ni
        x, qt = make_case(m, k, n, bits, 32, seed)
        out = quantized_matmul(
            x, qt.q, qt.scales, bits=bits, group_size=32,
            block_m=32, block_n=96, block_k=96,
            out_dtype=jnp.float32, interpret=True)
        assert_matches_oracle(out, x, qt)

    def test_edge_tile_clamp_below_default_blocks(self):
        """Dims smaller than every default block (M=8 < 128, N=64 < 256,
        K=64 < 128): the kernel clamps each block to the dim."""
        x, qt = make_case(8, 64, 64, 4, 32)
        out = quantized_matmul(x, qt.q, qt.scales, bits=4, group_size=32,
                               out_dtype=jnp.float32, interpret=True)
        assert out.shape == (8, 64)
        assert_matches_oracle(out, x, qt)

    @pytest.mark.parametrize("bits", [4, 8])
    def test_wrapper_parity_prime_ish_edge_case(self, bits):
        """A deliberately awkward single case: M prime, K=160 and N=96
        not multiples of any default block (the divisor search lands on
        32-multiples)."""
        x, qt = make_case(37, 160, 96, bits, 32, seed=7)
        out = q_matmul(x, qt, out_dtype=jnp.float32, interpret=True)
        assert out.shape == (37, 96)
        assert_matches_oracle(out, x, qt)

    @pytest.mark.parametrize("group", [32, 64, 128])
    def test_q8_group_sweep(self, group):
        """int8 across all group sizes (the q4 group sweep's twin — the
        q8 bank is first-class on the precision ladder)."""
        x, qt = make_case(128, 256, 256, 8, group)
        out = quantized_matmul(x, qt.q, qt.scales, bits=8, group_size=group,
                               interpret=True)
        assert_matches_oracle(out, x, qt)

    def test_q8_edge_tile_clamp_below_default_blocks(self):
        """int8 with dims smaller than every default block (M=8 < 128,
        N=64 < 256, K=64 < 128): the clamp path, not just the aligned
        fast path."""
        x, qt = make_case(8, 64, 64, 8, 32)
        out = quantized_matmul(x, qt.q, qt.scales, bits=8, group_size=32,
                               out_dtype=jnp.float32, interpret=True)
        assert out.shape == (8, 64)
        assert_matches_oracle(out, x, qt)

    def test_q8_odd_explicit_tiles(self):
        """int8 with deliberately odd non-default tiles (96-multiples):
        exercises the q8 kernel body off the (128, 256, 128) defaults."""
        x, qt = make_case(64, 192, 192, 8, 32, seed=11)
        out = quantized_matmul(x, qt.q, qt.scales, bits=8, group_size=32,
                               block_m=32, block_n=96, block_k=96,
                               out_dtype=jnp.float32, interpret=True)
        assert_matches_oracle(out, x, qt)

    @given(e=st.integers(1, 3), c=st.sampled_from([8, 40]),
           bits=st.sampled_from([4, 8]), seed=st.integers(0, 999))
    @settings(max_examples=6, deadline=None)
    def test_expert_batched_parity_edge_tiles(self, e, c, bits, seed):
        """The vmapped expert path on non-default tile shapes."""
        rng = np.random.default_rng(seed)
        k, n = 96, 96
        x = jnp.asarray(rng.standard_normal((e, c, k)), jnp.bfloat16)
        w = jnp.asarray(rng.standard_normal((e, k, n)), jnp.float32)
        qt = quantize(w, bits, 32)
        out = q_expert_matmul(x, qt, interpret=True)
        ref = expert_matmul_ref(x, qt.q, qt.scales, bits=bits,
                                group_size=32, out_dtype=jnp.float32)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            rtol=5e-2, atol=5e-2 * float(jnp.abs(ref).max()))


class TestOpsWrappers:
    @pytest.mark.parametrize("bits", [4, 8])
    @pytest.mark.parametrize("m", [1, 7, 128, 200])
    def test_q_matmul_pads_m(self, m, bits):
        """Decode calls with tiny M must work (padding inside the wrapper),
        on both quantized rungs."""
        x, qt = make_case(m, 256, 256, bits, 64)
        out = q_matmul(x, qt, interpret=True)
        assert out.shape == (m, 256)
        assert_matches_oracle(out, x, qt)

    def test_q_expert_matmul_matches_batched_oracle(self):
        rng = np.random.default_rng(3)
        e, c, k, n = 4, 64, 128, 256
        x = jnp.asarray(rng.standard_normal((e, c, k)), jnp.bfloat16)
        w = jnp.asarray(rng.standard_normal((e, k, n)), jnp.float32)
        qt = quantize(w, 4, 64)
        out = q_expert_matmul(x, qt, block_m=64, interpret=True)
        ref = expert_matmul_ref(x, qt.q, qt.scales, bits=4, group_size=64,
                                out_dtype=jnp.float32)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            rtol=5e-2, atol=5e-2 * float(jnp.abs(ref).max()))

    def test_grad_does_not_exist(self):
        """Quantized weights are serving-only: no grad path expected."""
        x, qt = make_case(64, 128, 128, 4, 64)
        with pytest.raises(Exception):
            jax.grad(lambda x: q_matmul(x, qt, interpret=True).sum())(x)


class TestKernelNumerics:
    def test_exact_on_integer_friendly_scales(self):
        """With scales=1 and integer x the kernel result is exact."""
        k, n, m = 128, 128, 32
        rng = np.random.default_rng(0)
        q = rng.integers(-8, 8, (k, n)).astype(np.int8)
        from repro.core.quantization import pack_int4
        packed = pack_int4(jnp.asarray(q))
        scales = jnp.ones((k // 64, n), jnp.float32)
        x = jnp.asarray(rng.integers(-4, 5, (m, k)), jnp.float32)
        out = quantized_matmul(x, packed, scales, bits=4, group_size=64,
                               block_m=32, block_n=128, block_k=128,
                               out_dtype=jnp.float32, interpret=True)
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(x, np.float32) @ q.astype(np.float32))

    @pytest.mark.parametrize("block_k", [128, 512])
    @pytest.mark.parametrize("group", [32, 64, 128])
    @pytest.mark.parametrize("grouped", [False, True])
    def test_q4_tight_parity_distinct_scales(self, grouped, group, block_k):
        """int4 against float32 dequantize-then-matmul at rtol 1e-5, with a
        different scale for every (group, column) and K over two BK tiles:
        a wrong in-tile K order or a wrong scale row for either nibble
        half is far outside this tolerance (the oracle tests' 5e-2 is
        not)."""
        from repro.core.quantization import pack_int4
        g, m, k, n = 2, 16, 2 * block_k, 128
        rng = np.random.default_rng(group + block_k)
        q = rng.integers(-8, 8, (g, k, n)).astype(np.int8)
        scales = (0.5 + rng.permutation(g * (k // group) * n)
                  .reshape(g, k // group, n) / (g * (k // group) * n)
                  ).astype(np.float32)
        x = jnp.asarray(rng.standard_normal((g, m, k)), jnp.bfloat16)
        packed = pack_int4(jnp.asarray(q))
        w = (q.astype(np.float32).reshape(g, k // group, group, n)
             * scales[:, :, None, :]).reshape(g, k, n)
        ref = np.einsum("gmk,gkn->gmn", np.asarray(x, np.float64), w)
        kw = dict(bits=4, group_size=group, block_m=m, block_n=n,
                  block_k=block_k, out_dtype=jnp.float32, interpret=True)
        if grouped:
            out = grouped_quantized_matmul(x, packed, jnp.asarray(scales),
                                           **kw)
        else:
            out = jnp.stack([quantized_matmul(x[e], packed[e],
                                              jnp.asarray(scales[e]), **kw)
                             for e in range(g)])
        np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5,
                                   atol=1e-5 * np.abs(ref).max())
