"""Pallas TPU kernels: fused stochastic int8 quantization for gradient
compression.

The FedSGD compression path (``ops/compression.py``, reference
``ml/utils/compression.py:175-260``) quantizes flat update vectors every
round; at cross-silo scale that is the bandwidth-critical op.  The fused
kernel keeps each block in VMEM through scale -> stochastic round -> int8
cast (one HBM read + one ~4x-smaller write), instead of XLA materializing
the f32 intermediates between ops.

Layout: the flat vector is reshaped to (blocks, 8, 128) — the f32 min tile —
with one grid step per block and a per-block scale (block-wise scaling is
also statistically tighter than one global scale).  The uniform noise for
stochastic rounding is an explicit input (generated with the caller's jax
PRNG key): this keeps the kernel deterministic given its inputs, bitwise
reproducible across interpret (CPU CI) and compiled (TPU) modes, and
testable against the pure-jnp reference below.

E[dequantize(quantize(x))] = x  (floor(x/s + u) with u ~ U[0,1) is unbiased).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import resolve_interpret
from .timing import observe_eager

_SUB, _LANE = 8, 128  # f32 min tile
_BLOCK = _SUB * _LANE


def _quantize_kernel(x_ref, noise_ref, values_ref, scale_ref):
    # scale_ref sees the WHOLE (blocks, 1) scale array in SMEM (per-block
    # (1,1) tiles violate the TPU (8,128) tiling constraint); each grid step
    # writes only its own element
    x = x_ref[:]
    amax = jnp.max(jnp.abs(x))
    scale = amax / 127.0 + 1e-12
    scale_ref[pl.program_id(0), 0] = scale
    scaled = x / scale                      # in [-127, 127]
    q = jnp.floor(scaled + noise_ref[:])    # stochastic round (unbiased)
    values_ref[:] = jnp.clip(q, -127.0, 127.0).astype(jnp.int8)


def _dequantize_kernel(values_ref, scale_ref, out_ref):
    out_ref[:] = values_ref[:].astype(jnp.float32) * scale_ref[pl.program_id(0), 0]


def _pad_blocks(vec: jax.Array):
    n = vec.shape[0]
    pad = (-n) % _BLOCK
    x = jnp.pad(vec, (0, pad)).reshape(-1, _SUB, _LANE)
    return x, n


def quantize_int8_stochastic(vec: jax.Array, key: jax.Array, interpret=None):
    """flat f32 vector -> (int8 values (blocks, 8, 128), f32 scales (blocks,),
    original length).  ``interpret=True`` runs the same kernel through the
    pallas interpreter (CPU CI); ``None`` derives it from the backend."""
    return observe_eager(
        "quantize_int8_stochastic",
        partial(_quantize_impl, interpret=resolve_interpret(interpret)),
        vec, key,
    )


def _quantize_impl(vec: jax.Array, key: jax.Array, *, interpret: bool):
    x, n = _pad_blocks(vec.astype(jnp.float32))
    noise = jax.random.uniform(key, x.shape, jnp.float32)
    blocks = x.shape[0]
    values, scales = pl.pallas_call(
        _quantize_kernel,
        grid=(blocks,),
        in_specs=[
            pl.BlockSpec((1, _SUB, _LANE), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, _SUB, _LANE), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, _SUB, _LANE), lambda i: (i, 0, 0)),
            pl.BlockSpec((blocks, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((blocks, _SUB, _LANE), jnp.int8),
            jax.ShapeDtypeStruct((blocks, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x, noise)
    return values, scales[:, 0], n


def dequantize_int8(values: jax.Array, scales: jax.Array, length: int,
                    interpret=None) -> jax.Array:
    return observe_eager(
        "dequantize_int8",
        partial(_dequantize_impl, length=length,
                interpret=resolve_interpret(interpret)),
        values, scales,
    )


def _dequantize_impl(values: jax.Array, scales: jax.Array, *, length: int,
                     interpret: bool) -> jax.Array:
    blocks = values.shape[0]
    out = pl.pallas_call(
        _dequantize_kernel,
        grid=(blocks,),
        in_specs=[
            pl.BlockSpec((1, _SUB, _LANE), lambda i: (i, 0, 0)),
            pl.BlockSpec((blocks, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, _SUB, _LANE), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((blocks, _SUB, _LANE), jnp.float32),
        interpret=interpret,
    )(values, scales[:, None])
    return out.reshape(-1)[:length]


# -- pure-jnp reference (the conformance oracle for the kernel) --------------

def quantize_int8_reference(vec: jax.Array, key: jax.Array):
    x, n = _pad_blocks(vec.astype(jnp.float32))
    noise = jax.random.uniform(key, x.shape, jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=(1, 2), keepdims=True)
    scale = amax / 127.0 + 1e-12
    q = jnp.clip(jnp.floor(x / scale + noise), -127.0, 127.0).astype(jnp.int8)
    return q, scale[:, 0, 0], n


def qsgd_int8(vec: jax.Array, key: jax.Array, interpret=None) -> jax.Array:
    """Quantize + dequantize round trip — the simulation-path compressor
    (dense-in/dense-out like ops/compression.qsgd, but int8 block-scaled and
    kernel-fused)."""
    values, scales, n = quantize_int8_stochastic(vec, key, interpret=interpret)
    return dequantize_int8(values, scales, n, interpret=interpret)
