"""Pallas TPU kernels (hand-written hot ops the XLA autofuser can't shape).

Current kernels:
- ``quantize.quantize_int8_stochastic`` / ``dequantize_int8`` — fused
  block-scaled stochastic int8 gradient quantization for the FedSGD
  compression path.
- ``noise.apply_gaussian_noise`` — the DP noise of secure aggregation's
  finalize step, scale-and-add in one pass.

Every eager kernel invocation is recorded into the process-global
``pallas_kernel_seconds`` histogram (``timing.py``).
"""

from .quantize import (
    dequantize_int8,
    qsgd_int8,
    quantize_int8_reference,
    quantize_int8_stochastic,
)
from .timing import PALLAS_KERNEL_TIME, kernel_time_summary

__all__ = [
    "dequantize_int8",
    "kernel_time_summary",
    "PALLAS_KERNEL_TIME",
    "qsgd_int8",
    "quantize_int8_reference",
    "quantize_int8_stochastic",
]
