"""jit'd public wrappers around the Pallas kernels.

These handle padding to tile boundaries, the QTensor container, batching
over experts (vmap adds a leading grid dimension to the pallas_call), and
the execution mode: on a TPU the kernels compile to Mosaic; on the CPU
backend (tests) they run in Pallas interpret mode; any other backend has
no kernel path and raises. The mode is decided when a wrapper is traced,
never when this module is imported.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.quantization import QTensor
from repro.kernels import grouped_matmul as _gk
from repro.kernels import q4_matmul as _k


def _interpret(interpret: Optional[bool]) -> bool:
    """Resolve ``interpret=None`` from the default backend: compiled on a
    TPU, interpreted on the CPU backend. Any other backend raises rather
    than quietly running the kernel body in the Python interpreter."""
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"no Pallas kernel path on backend {backend!r}")


def serve_through_kernels() -> bool:
    """The serving default for ``use_kernel``: the compiled kernels on a
    TPU, the jnp dequant reference on the CPU backend (tests); any other
    backend raises, as :func:`_interpret` does."""
    return not _interpret(None)


def _tile(dim: int, cap: int, quantum: int) -> int:
    """The one tile chooser: the largest multiple of ``quantum`` that
    divides ``dim`` and is <= max(cap, quantum), else the whole ``dim``.
    Mosaic requires every block's last two dims to be multiples of
    (8, 128) or to span the array; callers pass the quantum that makes
    each of their BlockSpecs legal."""
    best = dim
    b = quantum
    while b <= min(max(cap, quantum), dim):
        if dim % b == 0:
            best = b
        b += quantum
    return best


def _q_tiles(qt: QTensor, block_n: int, block_k: int):
    """(block_n, block_k) for a quantized weight. K tiles hold whole
    multiples of 8 scale rows (``block_k // group_size``), which also
    makes them multiples of 128 lanes for the activations; N tiles are
    multiples of 128 lanes. Shared by the per-expert and grouped paths so
    both pick identical tiles (their bit-exact parity depends on it)."""
    k, n = qt.shape[-2:]
    return (_tile(n, block_n, 128),
            _tile(k, block_k, math.lcm(128, 8 * qt.group_size)))


def _pad_to(x: jax.Array, mult: int, axis: int) -> jax.Array:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    cfg = [(0, 0)] * x.ndim
    cfg[axis] = (0, pad)
    return jnp.pad(x, cfg)


def _with_padded_m(call, x: jax.Array, *, block_m: int, m_axis: int):
    """Centralized padded-M wrapper (decode batches are small and rarely
    tile-aligned). Picks the effective M tile, zero-pads ``x`` along
    ``m_axis`` to it, runs ``call(x_padded, block_m_eff)`` and slices the
    result back to the true M. Shared by the per-expert and grouped paths
    so both see identical tile choices (a parity requirement)."""
    m = x.shape[m_axis]
    block_m_eff = min(block_m, _round_up(m, 8))
    xp = _pad_to(x, block_m_eff, m_axis)
    out = call(xp, block_m_eff)
    return jax.lax.slice_in_dim(out, 0, m, axis=m_axis)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "out_dtype", "interpret"))
def q_matmul(x: jax.Array, qt: QTensor, *, block_m: int = 128,
             block_n: int = 256, block_k: int = 512,
             out_dtype=jnp.bfloat16,
             interpret: Optional[bool] = None) -> jax.Array:
    """``x @ dequant(qt)`` — (M, K) x Q(K, N) -> (M, N).

    M is padded to the tile size (decode batches are small); K and N get
    the largest legal tile that divides them (``_q_tiles``).
    """
    interpret = _interpret(interpret)
    block_n, block_k = _q_tiles(qt, block_n, block_k)
    return _with_padded_m(
        lambda xp, bm: _k.quantized_matmul(
            xp, qt.q, qt.scales, bits=qt.bits, group_size=qt.group_size,
            block_m=bm, block_n=block_n, block_k=block_k,
            out_dtype=out_dtype, interpret=interpret),
        x, block_m=block_m, m_axis=0)


def q_expert_matmul(x: jax.Array, qt: QTensor, *, block_m: int = 128,
                    block_n: int = 256, block_k: int = 512,
                    out_dtype=jnp.bfloat16,
                    interpret: Optional[bool] = None,
                    grouped: bool = True) -> jax.Array:
    """Batched experts: (E, C, K) x Q(E, K, N) -> (E, C, N).

    ``grouped=True`` (default) fuses the whole bank into ONE kernel launch
    with the expert-group as the leading grid axis (DESIGN.md §13) —
    decode FFN cost stops scaling with expert count. ``grouped=False``
    keeps the legacy per-expert spelling (vmap over pallas_call); it is
    bit-identical to the grouped path and retained as the A/B baseline for
    ``benchmarks/kernel_bench.py``.
    """
    if grouped:
        return grouped_q_matmul(
            x, qt, block_m=block_m, block_n=block_n, block_k=block_k,
            out_dtype=out_dtype, interpret=interpret)
    fn = functools.partial(
        q_matmul, block_m=block_m, block_n=block_n, block_k=block_k,
        out_dtype=out_dtype, interpret=interpret)
    return jax.vmap(lambda xe, qe, se: fn(
        xe, QTensor(q=qe, scales=se, bits=qt.bits, group_size=qt.group_size))
    )(x, qt.q, qt.scales)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "out_dtype", "interpret"))
def grouped_q_matmul(x: jax.Array, qt: QTensor, *, block_m: int = 128,
                     block_n: int = 256, block_k: int = 512,
                     out_dtype=jnp.bfloat16,
                     interpret: Optional[bool] = None) -> jax.Array:
    """One-launch grouped ``(E, C, K) x Q(E, K, N) -> (E, C, N)``. Tile
    selection mirrors :func:`q_matmul` exactly so the grouped result is
    bit-identical to the per-expert loop."""
    interpret = _interpret(interpret)
    block_n, block_k = _q_tiles(qt, block_n, block_k)
    return _with_padded_m(
        lambda xp, bm: _gk.grouped_quantized_matmul(
            xp, qt.q, qt.scales, bits=qt.bits, group_size=qt.group_size,
            block_m=bm, block_n=block_n, block_k=block_k,
            out_dtype=out_dtype, interpret=interpret),
        x, block_m=block_m, m_axis=1)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "out_dtype", "interpret"))
def grouped_bf16_matmul(x: jax.Array, w: jax.Array, *, block_m: int = 128,
                        block_n: int = 256, block_k: int = 512,
                        out_dtype=jnp.bfloat16,
                        interpret: Optional[bool] = None) -> jax.Array:
    """One-launch grouped bf16 ``(E, C, K) x (E, K, N) -> (E, C, N)`` —
    the f16 bank's grouped path (no dequant; f32 VMEM accumulation, so
    parity with the einsum reference is allclose, not bitwise)."""
    interpret = _interpret(interpret)
    _, k, n = w.shape
    block_n, block_k = _tile(n, block_n, 128), _tile(k, block_k, 128)
    return _with_padded_m(
        lambda xp, bm: _gk.grouped_bf16_matmul(
            xp, w, block_m=bm, block_n=block_n, block_k=block_k,
            out_dtype=out_dtype, interpret=interpret),
        x, block_m=block_m, m_axis=1)


def _round_up(v: int, m: int) -> int:
    return ((v + m - 1) // m) * m
