"""Fused dequant-matmul Pallas TPU kernel: x @ dequant(Wq) for int4/int8
group-wise quantized weights (the MoP compute hot spot).

Design (DESIGN.md §2, hardware adaptation):
  * Weights are stored packed (int4: 2 nibbles/byte along K; int8: raw) with
    per-(group, column) bf16 scales, group_size | BK. The kernel unpacks and
    scales *inside VMEM* right before the MXU dot, so HBM traffic for a
    4-bit expert is ~4x lower than bf16 — this turns the paper's observed
    4-bit *slowdown* (PyTorch/bnb dequant-to-global-memory) into a speedup
    in the memory-bound decode regime.
  * Grid (M/BM, N/BN, K/BK), revolving f32 accumulator in VMEM scratch;
    K is the innermost (fastest) grid axis so the accumulator tile stays
    resident while weight tiles stream through.
  * Default tiles (BM, BN, BK) = (128, 256, 512), chosen by
    ``ops._tile``: every block's last two dims are multiples of (8, 128)
    or span the array, which Mosaic requires — for group 64 that means
    BK/G >= 8 scale rows. VMEM per step = x(128x512xbf16 = 128 KiB) +
    w(256x256 packed = 64 KiB) + scales(8x256xbf16 = 4 KiB) + acc
    (128x256xf32 = 128 KiB) + the dequantized f32 tile (512 KiB), well
    under v5e's scoped VMEM even double-buffered.
  * ``dot(int8-ish bf16 values)`` uses preferred_element_type=f32 so the MXU
    accumulates in f32.
  * int4 K order: a dequantized int4 tile holds the low nibbles' rows (even
    K) above the high nibbles' rows (odd K), ``[even | odd]`` within each
    BK block, because interleaving them back into K order costs a sublane
    shuffle per tile. ``quantized_matmul`` reorders x's K to match before
    the ``pallas_call`` (``even_odd_k``); the packing is unchanged.

The pure-jnp oracle lives in ``repro.kernels.ref``; jit'd public wrappers in
``repro.kernels.ops``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def dequant_tile(wq, sc, *, bits: int, group_size: int):
    """Unpack + scale one (BK, BN) weight tile in VMEM, in f32.

    int4 bytes hold K indices (2b, 2b+1) as (low, high) nibbles. They are
    widened to int32 before any mask, shift or subtract: the TPU vector
    unit has no 8-bit integer ALU, so Mosaic refuses int8 arithmetic.

    The int4 tile comes out in K order ``[even K | odd K]``: the low
    nibbles' (BK/2, BN) block, then the high nibbles'. Each half is
    converted and scaled on its own, byte row ``b`` by scale row
    ``2b // group_size``, and the halves are joined along sublanes, which
    costs nothing when aligned. Interleaving them back into K order would
    shuffle sublanes on every tile; instead the caller hands the kernel x
    with its K reordered to match (:func:`even_odd_k`)."""
    sc = sc.astype(jnp.float32)                        # (BK/G, BN)
    if bits == 4:
        w = wq.astype(jnp.int32)                       # (BK//2, BN)
        halves = [_scale(half, sc, group_size // 2)
                  for half in ((w & 0xF) - 8, (w >> 4) - 8)]
        return jnp.concatenate(halves, axis=0)
    return _scale(wq, sc, group_size)                  # (BK, BN) int8


def _scale(w_int, sc, group_rows: int):
    """f32 ``w_int`` with row ``r`` scaled by ``sc[r // group_rows]``."""
    rows = w_int.shape[0]
    w_f = w_int.astype(jnp.float32).reshape(
        rows // group_rows, group_rows, -1) * sc[:, None, :]
    return w_f.reshape(rows, -1)


def even_odd_k(x: jax.Array, block_k: int) -> jax.Array:
    """Reorder x's last axis (K) to the int4 tile's order: within each BK
    block, the even K indices, then the odd ones (:func:`dequant_tile`).
    One pass over x per call, outside the kernel."""
    *lead, kdim = x.shape
    return x.reshape(*lead, kdim // block_k, block_k // 2, 2).swapaxes(
        -1, -2).reshape(*lead, kdim)


def _q_kernel(x_ref, wq_ref, sc_ref, o_ref, acc_ref, *, nk: int,
              group_size: int, bits: int):
    """One (BM, BN) output tile; K streamed over grid axis 2."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w_f = dequant_tile(wq_ref[...], sc_ref[...], bits=bits,
                       group_size=group_size)
    acc_ref[...] += jax.lax.dot(
        x_ref[...].astype(jnp.float32), w_f,
        preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def quantized_matmul(
    x: jax.Array,            # (M, K) bf16/f32
    wq: jax.Array,           # int4: (K//2, N) uint8 | int8: (K, N) int8
    scales: jax.Array,       # (K//G, N)
    *,
    bits: int = 4,
    group_size: int = 64,
    block_m: int = 128,
    block_n: int = 256,
    block_k: int = 512,
    out_dtype=jnp.bfloat16,
    interpret: bool = False,
) -> jax.Array:
    """``x @ dequant(wq, scales)`` with in-VMEM dequantization.

    Shape requirements: BM|M, BN|N, BK|K, group_size|BK. Callers pad via
    :mod:`repro.kernels.ops`.
    """
    m, kdim = x.shape
    if bits == 4:
        n = wq.shape[1]
        k_w = wq.shape[0] * 2
    elif bits == 8:
        k_w, n = wq.shape
    else:
        raise ValueError(f"bits must be 4 or 8, got {bits}")
    if k_w != kdim:
        raise ValueError(f"K mismatch: x {kdim} vs w {k_w}")
    if scales.shape != (kdim // group_size, n):
        raise ValueError(f"scales {scales.shape} != {(kdim//group_size, n)}")
    block_m = min(block_m, m)
    block_n = min(block_n, n)
    block_k = min(block_k, kdim)
    if m % block_m or n % block_n or kdim % block_k:
        raise ValueError(f"blocks must divide dims: "
                         f"{(m, n, kdim)} vs {(block_m, block_n, block_k)}")
    if block_k % group_size:
        raise ValueError(f"group_size {group_size} must divide BK {block_k}")

    grid = (m // block_m, n // block_n, kdim // block_k)
    w_rows = block_k // 2 if bits == 4 else block_k
    if bits == 4:
        x = even_odd_k(x, block_k)

    return pl.pallas_call(
        functools.partial(_q_kernel, nk=grid[2], group_size=group_size,
                          bits=bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((w_rows, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec((block_k // group_size, block_n),
                         lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=f"q{bits}_matmul",
    )(x, wq, scales)
