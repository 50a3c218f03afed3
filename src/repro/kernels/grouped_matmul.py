"""Grouped multi-expert matmul Pallas kernels: ONE launch per ladder rung
(DESIGN.md §13).

The per-expert spelling (``ops.q_matmul`` under ``vmap``, or a Python loop
of per-expert calls) dispatches one kernel instance per expert, so decode
FFN launch + dequant overhead scales with the bank's expert count — the
wrong scaling for kimi-scale configs (384 experts). These kernels fuse all
of a rung's experts into a single ``pallas_call`` with the expert-group as
the leading grid axis:

    grid = (G, C/BM, N/BN, K/BK)

where ``G`` is the number of experts in the rung's bank and ``C`` the
capacity-bounded tokens-per-expert buffer the MoE dispatch packs
(``mixed_moe._dispatch_local``). Each grid step indexes that group's packed
weights/scales through its BlockSpec and dequantizes **in VMEM** right
before the MXU dot with the per-expert kernel's own ``dequant_tile``
(``q4_matmul``) — per-tile arithmetic is identical, so the grouped q4/q8
results are bit-exact against the per-expert loop (tested). The bf16 bank
gets the same grouped layout without the dequant (f32 accumulation, so
parity with the jnp einsum is allclose, not bitwise).

An expert with zero routed tokens occupies an all-zero slice of the packed
activation buffer; its tiles compute ``0 @ dequant(W) == 0`` exactly, so
empty groups contribute exact zeros (tested) — no host-side compaction is
needed to keep the launch count at one.

K stays the innermost (fastest) grid axis so the revolving f32 accumulator
tile stays resident in VMEM scratch while weight tiles stream; the group
axis is outermost and fully parallel. VMEM per step is the same as the
per-expert kernel (leading block of 1 on the group axis).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.q4_matmul import dequant_tile


def _grouped_q_kernel(x_ref, wq_ref, sc_ref, o_ref, acc_ref, *, nk: int,
                      group_size: int, block_k: int, bits: int):
    """One (BM, BN) output tile of one expert group; K streamed over grid
    axis 3. All refs carry a leading group-block of 1."""
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w_f = dequant_tile(wq_ref[0], sc_ref[0], bits=bits,
                       group_size=group_size, block_k=block_k)
    acc_ref[...] += jax.lax.dot(
        x_ref[0].astype(jnp.float32), w_f,
        preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _flush():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def _grouped_bf16_kernel(x_ref, w_ref, o_ref, acc_ref, *, nk: int):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot(
        x_ref[0], w_ref[0], preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _flush():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def grouped_quantized_matmul(
    x: jax.Array,            # (G, C, K) bf16/f32
    wq: jax.Array,           # int4: (G, K//2, N) uint8 | int8: (G, K, N)
    scales: jax.Array,       # (G, K//group_size, N)
    *,
    bits: int = 4,
    group_size: int = 64,
    block_m: int = 128,
    block_n: int = 256,
    block_k: int = 512,
    out_dtype=jnp.bfloat16,
    interpret: bool = False,
) -> jax.Array:
    """``einsum('gck,gkn->gcn', x, dequant(wq, scales))`` in ONE launch.

    Shape requirements match :func:`~repro.kernels.q4_matmul.
    quantized_matmul` per group: BM|C, BN|N, BK|K, group_size|BK. Callers
    pad via :mod:`repro.kernels.ops`.
    """
    g, c, kdim = x.shape
    if bits == 4:
        n = wq.shape[2]
        k_w = wq.shape[1] * 2
    elif bits == 8:
        _, k_w, n = wq.shape
    else:
        raise ValueError(f"bits must be 4 or 8, got {bits}")
    if wq.shape[0] != g or scales.shape[0] != g:
        raise ValueError(f"group mismatch: x {g} vs w {wq.shape[0]} "
                         f"vs scales {scales.shape[0]}")
    if k_w != kdim:
        raise ValueError(f"K mismatch: x {kdim} vs w {k_w}")
    if scales.shape[1:] != (kdim // group_size, n):
        raise ValueError(
            f"scales {scales.shape[1:]} != {(kdim // group_size, n)}")
    block_m = min(block_m, c)
    block_n = min(block_n, n)
    block_k = min(block_k, kdim)
    if c % block_m or n % block_n or kdim % block_k:
        raise ValueError(f"blocks must divide dims: "
                         f"{(c, n, kdim)} vs {(block_m, block_n, block_k)}")
    if block_k % group_size:
        raise ValueError(f"group_size {group_size} must divide BK {block_k}")

    grid = (g, c // block_m, n // block_n, kdim // block_k)
    w_rows = block_k // 2 if bits == 4 else block_k

    return pl.pallas_call(
        functools.partial(_grouped_q_kernel, nk=grid[3],
                          group_size=group_size, block_k=block_k,
                          bits=bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_m, block_k),
                         lambda e, i, j, k: (e, i, k)),
            pl.BlockSpec((1, w_rows, block_n),
                         lambda e, i, j, k: (e, k, j)),
            pl.BlockSpec((1, block_k // group_size, block_n),
                         lambda e, i, j, k: (e, k, j)),
        ],
        out_specs=pl.BlockSpec((1, block_m, block_n),
                               lambda e, i, j, k: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((g, c, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name=f"grouped_q{bits}_matmul",
    )(x, wq, scales)


def grouped_bf16_matmul(
    x: jax.Array,            # (G, C, K)
    w: jax.Array,            # (G, K, N)
    *,
    block_m: int = 128,
    block_n: int = 256,
    block_k: int = 512,
    out_dtype=jnp.bfloat16,
    interpret: bool = False,
) -> jax.Array:
    """``einsum('gck,gkn->gcn', x, w)`` in one launch — the f16 bank's
    grouped path (f32 accumulation in VMEM scratch)."""
    g, c, kdim = x.shape
    gw, k_w, n = w.shape
    if gw != g or k_w != kdim:
        raise ValueError(f"shape mismatch: x {x.shape} vs w {w.shape}")
    block_m = min(block_m, c)
    block_n = min(block_n, n)
    block_k = min(block_k, kdim)
    if c % block_m or n % block_n or kdim % block_k:
        raise ValueError(f"blocks must divide dims: "
                         f"{(c, n, kdim)} vs {(block_m, block_n, block_k)}")
    grid = (g, c // block_m, n // block_n, kdim // block_k)
    return pl.pallas_call(
        functools.partial(_grouped_bf16_kernel, nk=grid[3]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_m, block_k),
                         lambda e, i, j, k: (e, i, k)),
            pl.BlockSpec((1, block_k, block_n),
                         lambda e, i, j, k: (e, k, j)),
        ],
        out_specs=pl.BlockSpec((1, block_m, block_n),
                               lambda e, i, j, k: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((g, c, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="grouped_bf16_matmul",
    )(x, w)
