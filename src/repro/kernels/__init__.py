"""Pallas TPU kernels for the MoP compute hot spots.

q4_matmul — fused in-VMEM dequant + MXU matmul for int4/int8 group-quantized
weights (kernel body), with ops.py as the jit'd public wrapper and ref.py as
the pure-jnp oracle. Checked against the oracle in interpret mode on the CPU,
compiled for a described TPU v5e in tests/test_tpu_compile.py, and run
compiled (Mosaic) on a TPU.
"""
from repro.kernels.ops import q_expert_matmul, q_matmul  # noqa: F401
from repro.kernels.ref import expert_matmul_ref, quantized_matmul_ref  # noqa: F401
