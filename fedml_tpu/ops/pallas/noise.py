"""Pallas TPU kernel: fused DP noise application for secure-aggregation
finalize.

The streaming SecAgg path (ISSUE 15) adds central-DP noise EXACTLY ONCE, at
finalize, to the unmasked aggregate — never per client, never per fold.  The
fused kernel keeps each block VMEM-resident through the scale-and-add
(one HBM read of the aggregate + one of the noise, one write), instead of
XLA materializing the scaled-noise intermediate.

Same discipline as ``quantize.py``: the normal noise is an EXPLICIT input
generated with the caller's jax PRNG key — the kernel stays deterministic
given its inputs, bitwise reproducible across interpret (CPU CI) and
compiled (TPU) modes, and testable against the pure-jnp reference below.
(TPU Pallas does have an in-kernel PRNG — ``pltpu.prng_random_bits`` — but
an in-kernel stream cannot be replayed by the interpret-mode oracle, and DP
accounting wants the noise draw auditable from the round key.)
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .backend import resolve_interpret
from .timing import observe_eager

_SUB, _LANE = 8, 128  # f32 min tile
_BLOCK = _SUB * _LANE


def _noise_kernel(x_ref, noise_ref, sigma_ref, out_ref):
    # sigma rides SMEM as a (1, 1) scalar; mul-then-add mirrors the
    # reference op-for-op so interpret mode is bitwise the jnp oracle
    out_ref[:] = x_ref[:] + noise_ref[:] * sigma_ref[0, 0]


def _pad_blocks(vec: jax.Array):
    n = vec.shape[0]
    pad = (-n) % _BLOCK
    x = jnp.pad(vec, (0, pad)).reshape(-1, _SUB, _LANE)
    return x, n


def apply_gaussian_noise(vec: jax.Array, key: jax.Array, sigma: float,
                         interpret=None) -> jax.Array:
    """flat f32 vector + N(0, sigma^2) noise in one fused VMEM pass.
    ``interpret=True`` runs the same kernel through the pallas interpreter
    (CPU CI); ``None`` derives it from the backend."""
    return observe_eager(
        "apply_gaussian_noise",
        partial(_noise_impl, interpret=resolve_interpret(interpret)),
        vec, key, jnp.float32(sigma),
    )


def _noise_impl(vec: jax.Array, key: jax.Array, sigma: jax.Array, *,
                interpret: bool) -> jax.Array:
    from jax.experimental.pallas import tpu as pltpu

    x, n = _pad_blocks(vec.astype(jnp.float32))
    noise = jax.random.normal(key, x.shape, jnp.float32)
    blocks = x.shape[0]
    out = pl.pallas_call(
        _noise_kernel,
        grid=(blocks,),
        in_specs=[
            pl.BlockSpec((1, _SUB, _LANE), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, _SUB, _LANE), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, _SUB, _LANE), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((blocks, _SUB, _LANE), jnp.float32),
        interpret=interpret,
    )(x, noise, sigma.reshape(1, 1))
    return out.reshape(-1)[:n]


# -- pure-jnp reference (the conformance oracle for the kernel) --------------

def apply_gaussian_noise_reference(vec: jax.Array, key: jax.Array,
                                   sigma: float) -> jax.Array:
    x, n = _pad_blocks(vec.astype(jnp.float32))
    noise = jax.random.normal(key, x.shape, jnp.float32)
    out = x + noise * jnp.float32(sigma)
    return out.reshape(-1)[:n]
