"""Peaks of each chip the benchmark may run on, and the operation and
byte counts of the model and of its kernels, from shapes alone.

Peaks: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
393 TOP/s int8, 16 GB of HBM at 819 GB/s per chip.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}

#: Pallas kernel name -> bits of its weights
KERNEL_BITS = {"grouped_q4_matmul": 4, "grouped_q8_matmul": 8,
               "grouped_bf16_matmul": 16}


def peaks(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}")
    return PEAKS[device_kind]


# -- model operations per token -------------------------------------------

def layer_flops(c: dict, context: int) -> float:
    """One token through one decoder layer at ``context`` attended keys:
    q/k/v/o projections, scores and values, router, and the
    ``num_experts_per_tok`` experts it is routed to."""
    d, f = c["hidden_size"], c["intermediate_size"]
    h, hkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    proj = 2 * d * (h * hd + 2 * hkv * hd) + 2 * h * hd * d
    attn = 4 * h * hd * context
    router = 2 * d * c["num_local_experts"]
    experts = c["num_experts_per_tok"] * 2 * 3 * d * f
    return float(proj + attn + router + experts)


def unembed_flops(c: dict) -> float:
    return 2.0 * c["hidden_size"] * c["vocab_size"]


def prefill_flops(c: dict, prompt_len: int) -> float:
    """A prompt of ``prompt_len`` tokens: every position attends to
    itself and the positions before it; logits for the last one only."""
    s = int(prompt_len)
    per_layer = s * layer_flops(c, 0) + 4 * c["num_attention_heads"] \
        * c["head_dim"] * s * (s + 1) / 2
    return c["num_hidden_layers"] * per_layer + unembed_flops(c)


def decode_flops(c: dict, context: int) -> float:
    """One decoded token that attends to ``context`` positions (itself
    included), with its logits."""
    return c["num_hidden_layers"] * layer_flops(c, context) \
        + unembed_flops(c)


# -- kernels -----------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class KernelCall:
    """One grouped call ``(G, M, K) x (G, K, N) -> (G, M, N)``."""
    name: str
    groups: int
    m: int
    k: int
    n: int
    group_size: int = 64

    @property
    def bits(self) -> int:
        return KERNEL_BITS[self.name]

    def flops(self) -> float:
        return 2.0 * self.groups * self.m * self.k * self.n

    def bytes(self) -> float:
        """Each operand read once and the output written once: bf16
        activations in and out; weights at their rung, with one bf16
        scale per ``group_size`` weights for the quantized rungs."""
        g, m, k, n = self.groups, self.m, self.k, self.n
        w = g * k * n * self.bits / 8
        if self.bits < 16:
            w += g * (k // self.group_size) * n * 2
        return float(2 * g * m * k + w + 2 * g * m * n)

    def least_s(self, pk: Dict[str, float]) -> float:
        """The roofline bound: the larger of operations over the bf16
        peak (the activations are bf16) and bytes over HBM bandwidth."""
        return max(self.flops() / pk["bf16_flops"],
                   self.bytes() / pk["hbm_bytes_per_s"])
