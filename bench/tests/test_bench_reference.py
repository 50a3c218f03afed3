"""The benchmark's own weights and quantization agree with what the
program is handed and does, so the reference computes the model the
program serves."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
from benchlib import spec

A = spec.load_module(spec.BENCH / "archs" / "mixtral.py")


def test_a_slice_is_the_tree_s_slice():
    c = tiny.tiny_config()
    p = A.make_params(c, 2**40 + 3)
    for name, ix in (("layers/moe/w_down", (1, 5)),
                     ("layers/attn/wk", (1, 0)), ("lm_head/table", (0, 0))):
        node = p
        for k in name.split("/"):
            node = node[k]
        axes = dict((n, a) for n, _, a in A.layout(c))[name]
        want = node[ix[0], ix[1]] if axes == "LE" else (
            node[ix[0]] if axes == "L" else node)
        got = A.leaf_slice(c, 2**40 + 3, name, *ix)
        assert np.array_equal(np.asarray(want), np.asarray(got))


def test_the_tree_has_the_program_s_layout():
    from benchlib.serve import program_config
    c = tiny.tiny_config()
    cfg = program_config(A, c)
    assert dict((n, tuple(s)) for n, s in cfg.param_shapes()) == \
        A.full_shapes(c)


@pytest.mark.parametrize("bits", [4, 8])
def test_reference_quantization_is_the_configured_scheme(bits):
    from repro.core.quantization import quantize, unpack_int4
    ref = spec.load_module(spec.BENCH / "references" / "mixtral.py")
    w = (jax.random.normal(jax.random.key(bits), (256, 96)) * 0.05
         ).astype(jnp.bfloat16)
    ours = np.asarray(ref.quant_dequant(w, bits, 64))
    qt = quantize(w, bits, 64)
    codes = np.asarray(unpack_int4(qt.q) if bits == 4 else qt.q, np.float32)
    step = np.repeat(np.asarray(qt.scales, np.float32), 64, axis=0)
    theirs = codes * step
    # the same codes and scales, but for weights that lie exactly halfway
    # between two codes, which a compiled reciprocal may round either way
    off = ours != theirs
    assert off.mean() < 2e-3
    np.testing.assert_allclose(np.abs(ours - theirs)[off], step[off],
                               rtol=1e-6)
