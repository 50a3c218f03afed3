"""Counter for the grouped expert matmuls of every rung, one call
``(G, M, K) x (G, K, N) -> (G, M, N)`` each: G experts, M rows, K in,
N out.

A kernel counter is a file ``bench/kernels/<name>.py`` with ``KERNELS``,
the Pallas names it matches (an instruction named ``<kernel>`` or
``<kernel>.<n>``); ``shapes(hlo_text)``, a call's shapes from its HLO
text, or ``None``; and, for one call of kernel ``name`` with those
shapes under configuration file ``c``, ``flops``, ``nbytes`` and
``least_s`` given the chip's peaks.
"""
from __future__ import annotations

import re
from typing import Dict, Optional, Tuple

#: Pallas kernel name -> bits of its weights
KERNELS = {"grouped_q4_matmul": 4, "grouped_q8_matmul": 8,
           "grouped_bf16_matmul": 16}

_SHAPE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def shapes(text: str) -> Optional[Tuple[int, int, int, int]]:
    """(G, M, K, N) of a grouped call from its HLO text: the result is
    ``[G, M, N]`` and the first operand the activations ``[G, M, K]``."""
    dims = [[int(x) for x in d.split(",") if x] for _, d in
            _SHAPE.findall(text)]
    dims = [d for d in dims if len(d) == 3]
    if len(dims) < 2:
        return None
    (g, m, n), (g2, m2, k) = dims[0], dims[1]
    if (g, m) != (g2, m2):
        return None
    return g, m, k, n


def flops(name: str, shape, c: dict) -> float:
    g, m, k, n = shape
    return 2.0 * g * m * k * n


def nbytes(name: str, shape, c: dict) -> float:
    """Each operand read once and the output written once: bf16
    activations in and out; weights at their rung, with one bf16 scale
    per ``group_size`` weights (the configuration's plan) for the
    quantized rungs."""
    g, m, k, n = shape
    bits = KERNELS[name]
    w = g * k * n * bits / 8
    if bits < 16:
        w += g * (k // c["plan"]["group_size"]) * n * 2
    return float(2 * g * m * k + w + 2 * g * m * n)


def least_s(name: str, shape, pk: Dict[str, float], c: dict) -> float:
    """The roofline bound: the larger of operations over the bf16 peak
    (the activations are bf16) and bytes over HBM bandwidth."""
    return max(flops(name, shape, c) / pk["bf16_flops"],
               nbytes(name, shape, c) / pk["hbm_bytes_per_s"])
