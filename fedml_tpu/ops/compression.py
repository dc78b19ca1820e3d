"""Gradient/model compression operators (FedSGD path).

Parity with the reference's ``ml/utils/compression.py``: ``TopKCompressor:21``,
``EFTopKCompressor:139`` (error-feedback residuals), ``QuantizationCompressor:175``
(naive level quantization), ``QSGDCompressor:210`` (norm-scaled stochastic
quantization).  The reference compresses per-tensor with torch ops on the host;
here each operator is a pure JAX function over the flat parameter vector so it
fuses into the round program, and EF residuals are explicit state (threaded as
the client state of the FedSGD algorithm) rather than a stateful object.

Note: on-device "compression" keeps dense shapes (a masked vector), which is
the right semantics for simulation — the statistical effect is identical,
while the wire-level sparse encoding lives in ``comm.wire`` for real
cross-silo transport.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def top_k_mask(vec: jax.Array, ratio: float) -> jax.Array:
    """Keep the k = ceil(ratio * n) largest-|.| entries; zero the rest."""
    n = vec.shape[0]
    k = max(1, int(ratio * n))
    thresh = jax.lax.top_k(jnp.abs(vec), k)[0][-1]
    return jnp.where(jnp.abs(vec) >= thresh, vec, 0.0)


def ef_top_k(vec: jax.Array, residual: jax.Array, ratio: float):
    """Error-feedback TopK (EFTopKCompressor:139): add residual, compress,
    keep what was dropped as the next residual."""
    corrected = vec + residual
    compressed = top_k_mask(corrected, ratio)
    new_residual = corrected - compressed
    return compressed, new_residual


def quantize_naive(vec: jax.Array, levels: int = 256) -> jax.Array:
    """Uniform quantization to ``levels`` steps of the per-vector range
    (QuantizationCompressor semantics)."""
    vmax = jnp.max(jnp.abs(vec)) + 1e-12
    step = 2.0 * vmax / (levels - 1)
    return jnp.round(vec / step) * step


def qsgd(vec: jax.Array, key: jax.Array, levels: int = 256) -> jax.Array:
    """QSGD stochastic quantization (QSGDCompressor:210): scale by the l2
    norm, stochastically round to ``levels`` buckets — unbiased."""
    norm = jnp.linalg.norm(vec) + 1e-12
    scaled = jnp.abs(vec) / norm * levels
    floor = jnp.floor(scaled)
    prob = scaled - floor
    rnd = jax.random.uniform(key, vec.shape)
    q = floor + (rnd < prob).astype(vec.dtype)
    return jnp.sign(vec) * q * norm / levels


def qsgd_int8_fused(vec: jax.Array, key: jax.Array, interpret=None) -> jax.Array:
    """Block-scaled stochastic int8 quantize+dequantize via the Pallas TPU
    kernel (``ops/pallas/quantize.py``) — the fused fast path for the QSGD
    semantics (one HBM read + int8 write instead of materialized f32
    intermediates).  ``interpret``: see ``ops/pallas/backend.py``."""
    from .pallas import qsgd_int8

    return qsgd_int8(vec, key, interpret=interpret)


def compress(name: str, vec: jax.Array, *, key: Optional[jax.Array] = None,
             residual: Optional[jax.Array] = None, ratio: float = 0.01,
             quantize_level: int = 8):
    """Dispatch matching reference ``compression`` config values
    (``no | topk | eftopk | quantize | qsgd``), plus ``qsgd_int8`` — the
    Pallas-fused block-scaled int8 fast path.  Returns (vec, new_residual)."""
    if name in ("no", "", None):
        return vec, residual
    if name == "topk":
        return top_k_mask(vec, ratio), residual
    if name == "eftopk":
        return ef_top_k(vec, residual, ratio)
    if name == "quantize":
        return quantize_naive(vec, 2 ** quantize_level), residual
    if name == "qsgd":
        return qsgd(vec, key, 2 ** quantize_level), residual
    if name == "qsgd_int8":
        return qsgd_int8_fused(vec, key), residual
    raise ValueError(f"unknown compression {name!r}")
