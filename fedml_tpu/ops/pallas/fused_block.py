"""Pallas TPU kernel: fused BasicBlock epilogue (BN apply + residual + ReLU).

The round-4 trace (PERF.md "Per-op attribution") pins 33 ms of the 284 ms
flagship FedAvg round on *second-pass loop fusions*: after each conv, XLA
materializes the BN scale/shift application, the residual/downsample add and
the ReLU as separate HBM traversals of the full activation tensor.  This
kernel fuses that epilogue into ONE pass that keeps the block's activations
in VMEM — one HBM read of the conv output (+ residual), one write of the
activated result — the same "intermediates stay on-chip" discipline as
``quantize.py`` and the FlashAttention lineage (PAPERS.md).

Scope note: the batch mean/var *statistics* are NOT recomputed here — the
trace shows XLA already fuses those reductions into the producing conv
(``convert_reduce`` inside the conv fusions).  The caller folds
(gamma, beta, mean, var) into a per-channel affine ``scale``/``shift``
(``models/resnet.FusedBasicBlock``) and this kernel applies it.  Gradients
w.r.t. ``scale``/``shift`` chain back through mean/var into the conv output
via ordinary autodiff outside the kernel, so train-mode BN semantics are
exact.

Layout: activations are flattened and reshaped to ``(blocks, 16, 128)``
(16 sublanes covers the bf16 min tile; f32's 8 divides it).  Because every
CIFAR-ResNet channel count C ∈ {16, 32, 64} divides the 128-lane vector
width, a flat element's channel is ``lane % C`` — so the per-channel affine
rides a single (1, 1, 128) lane vector (``scale`` tiled 128/C times) and the
backward pass accumulates d(scale)/d(shift) into one (1, 16, 128) VMEM tile
across grid steps, folded to (C,) outside the kernel.  Channels that do not
divide 128 are refused (``ValueError``) — never silently computed by the
reference.

The backward pass is also a single fused traversal.  The ReLU mask is not
stored separately: the forward *output* is saved (XLA aliases it — it is the
layer's activation and already lives in HBM for the bwd convs) and the mask
is recovered as ``out > 0``, which is exactly ``jax.nn.relu``'s subgradient
convention (zero at the kink).

``interpret=True`` runs the identical kernels through the Pallas interpreter
for CPU CI; when ``interpret`` is not given, it is derived from the active
backend (``backend.resolve_interpret``: compiled on TPU, interpreted
elsewhere), like every kernel in this package.  Parity oracle: ``fused_block_reference``
— jitted kernel vs jitted reference is f32-bitwise (the parity tests in
``tests/test_pallas.py`` assert it).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .backend import resolve_interpret
from .timing import observe_eager

_SUB, _LANE = 16, 128  # sublane x lane block; 16 covers the bf16 min tile
_BLOCK = _SUB * _LANE


def _check_channels(channels: int) -> None:
    """The lane-vector layout needs C | 128 (module docstring).  Anything
    else is refused: quietly computing the reference instead would let a
    run report the fused path while executing none of it."""
    if channels > _LANE or _LANE % channels:
        raise ValueError(
            f"fused epilogue needs a channel count that divides {_LANE} "
            f"(CIFAR-ResNet 16/32/64); got {channels} — use the unfused block "
            "or fused_block_reference")


def _to_blocks(a: jax.Array):
    n = a.size
    pad = (-n) % _BLOCK
    return jnp.pad(a.reshape(-1), (0, pad)).reshape(-1, _SUB, _LANE), n


def _lane_vec(v: jax.Array) -> jax.Array:
    """(C,) per-channel vector -> (1, 1, 128) lane vector.  With C | 128 a
    flat NHWC element's channel is ``lane % C``, so tiling 128/C copies makes
    the lane vector line up with every (16, 128) block."""
    return jnp.tile(v.astype(jnp.float32), _LANE // v.shape[-1]).reshape(1, 1, _LANE)


def _block_spec(index_map):
    return pl.BlockSpec((1, _SUB, _LANE), index_map)


def _lane_spec():
    return pl.BlockSpec((1, 1, _LANE), lambda i: (0, 0, 0))


# -- forward kernels ---------------------------------------------------------

def _fwd_res_kernel(y_ref, s_ref, b_ref, r_ref, out_ref):
    y = y_ref[...].astype(jnp.float32)
    z = y * s_ref[...] + b_ref[...] + r_ref[...].astype(jnp.float32)
    out_ref[...] = jnp.maximum(z, 0.0).astype(out_ref.dtype)


def _fwd_kernel(y_ref, s_ref, b_ref, out_ref):
    y = y_ref[...].astype(jnp.float32)
    z = y * s_ref[...] + b_ref[...]
    out_ref[...] = jnp.maximum(z, 0.0).astype(out_ref.dtype)


def _fwd_call(y, scale, shift, residual, interpret: bool):
    yb, n = _to_blocks(y)
    blocks = yb.shape[0]
    operands = [yb, _lane_vec(scale), _lane_vec(shift)]
    in_specs = [_block_spec(lambda i: (i, 0, 0)), _lane_spec(), _lane_spec()]
    kernel = _fwd_kernel
    if residual is not None:
        rb, _ = _to_blocks(residual)
        operands.append(rb)
        in_specs.append(_block_spec(lambda i: (i, 0, 0)))
        kernel = _fwd_res_kernel
    out = pl.pallas_call(
        kernel,
        grid=(blocks,),
        in_specs=in_specs,
        out_specs=_block_spec(lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(yb.shape, y.dtype),
        interpret=interpret,
    )(*operands)
    return out.reshape(-1)[:n].reshape(y.shape)


# -- backward kernels --------------------------------------------------------
#
# Accumulator outputs map every grid step onto the SAME (1, 16, 128) tile
# (TPU grids run sequentially; step 0 zero-initializes).  Padded tail
# elements contribute nothing: the cotangent g is zero-padded, so
# g * mask * (...) vanishes there.  The ReLU mask compares in f32: the v5e
# vector unit has no bf16 compare (Mosaic: "Target does not support this
# comparison" on arith.cmpf over bf16), and the upcast is exact.

def _bwd_res_kernel(g_ref, y_ref, s_ref, out_ref, dy_ref, dr_ref, ds_ref, db_ref):
    g = g_ref[...].astype(jnp.float32)
    mask = (out_ref[...].astype(jnp.float32) > 0).astype(jnp.float32)
    gm = g * mask
    dy_ref[...] = (gm * s_ref[...]).astype(dy_ref.dtype)
    dr_ref[...] = gm.astype(dr_ref.dtype)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        ds_ref[...] = jnp.zeros_like(ds_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    ds_ref[...] += gm * y_ref[...].astype(jnp.float32)
    db_ref[...] += gm


def _bwd_kernel(g_ref, y_ref, s_ref, out_ref, dy_ref, ds_ref, db_ref):
    g = g_ref[...].astype(jnp.float32)
    mask = (out_ref[...].astype(jnp.float32) > 0).astype(jnp.float32)
    gm = g * mask
    dy_ref[...] = (gm * s_ref[...]).astype(dy_ref.dtype)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        ds_ref[...] = jnp.zeros_like(ds_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    ds_ref[...] += gm * y_ref[...].astype(jnp.float32)
    db_ref[...] += gm


def _fold_lanes(acc: jax.Array, channels: int) -> jax.Array:
    """(1, 16, 128) f32 accumulator -> (C,): sum sublanes and the 128/C lane
    repeats (lane = k*C + c holds channel c)."""
    return acc.reshape(_SUB, _LANE // channels, channels).sum(axis=(0, 1))


def _bwd_call(g, y, scale, out, with_residual: bool, interpret: bool):
    channels = scale.shape[-1]
    gb, n = _to_blocks(g)
    yb, _ = _to_blocks(y)
    ob, _ = _to_blocks(out)
    blocks = gb.shape[0]
    elem = _block_spec(lambda i: (i, 0, 0))
    acc = _block_spec(lambda i: (0, 0, 0))
    acc_shape = jax.ShapeDtypeStruct((1, _SUB, _LANE), jnp.float32)
    if with_residual:
        dy, dr, ds, db = pl.pallas_call(
            _bwd_res_kernel,
            grid=(blocks,),
            in_specs=[elem, elem, _lane_spec(), elem],
            out_specs=[elem, elem, acc, acc],
            out_shape=[
                jax.ShapeDtypeStruct(gb.shape, y.dtype),
                jax.ShapeDtypeStruct(gb.shape, y.dtype),
                acc_shape,
                acc_shape,
            ],
            interpret=interpret,
        )(gb, yb, _lane_vec(scale), ob)
    else:
        dy, ds, db = pl.pallas_call(
            _bwd_kernel,
            grid=(blocks,),
            in_specs=[elem, elem, _lane_spec(), elem],
            out_specs=[elem, acc, acc],
            out_shape=[jax.ShapeDtypeStruct(gb.shape, y.dtype), acc_shape, acc_shape],
            interpret=interpret,
        )(gb, yb, _lane_vec(scale), ob)
        dr = None
    unblock = lambda a: a.reshape(-1)[:n].reshape(y.shape)
    dy = unblock(dy)
    dr = unblock(dr) if dr is not None else None
    return dy, _fold_lanes(ds, channels), _fold_lanes(db, channels), dr


# -- custom_vjp wiring -------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fused_res(interpret, y, scale, shift, residual):
    return _fwd_call(y, scale, shift, residual, interpret)


def _fused_res_fwd(interpret, y, scale, shift, residual):
    out = _fwd_call(y, scale, shift, residual, interpret)
    # residuals: the conv output y (needed for d scale), the folded scale and
    # the OUTPUT (whose sign is the relu mask) — all arrays XLA already
    # materializes, so nothing extra is written for the backward pass.  The
    # size-0 sentinels carry shift/residual dtypes (cotangent dtypes must
    # match primals exactly).
    return out, (y, scale, jnp.zeros((), shift.dtype), jnp.zeros((), residual.dtype), out)


def _fused_res_bwd(interpret, res, g):
    y, scale, shift0, r0, out = res
    dy, ds, db, dr = observe_eager(
        "fused_bn_residual_relu_bwd",
        partial(_bwd_call, with_residual=True, interpret=interpret),
        g, y, scale, out,
    )
    return dy, ds.astype(scale.dtype), db.astype(shift0.dtype), dr.astype(r0.dtype)


_fused_res.defvjp(_fused_res_fwd, _fused_res_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fused(interpret, y, scale, shift):
    return _fwd_call(y, scale, shift, None, interpret)


def _fused_fwd(interpret, y, scale, shift):
    out = _fwd_call(y, scale, shift, None, interpret)
    return out, (y, scale, jnp.zeros((), shift.dtype), out)


def _fused_bwd(interpret, res, g):
    y, scale, shift0, out = res
    dy, ds, db, _ = observe_eager(
        "fused_bn_relu_bwd",
        partial(_bwd_call, with_residual=False, interpret=interpret),
        g, y, scale, out,
    )
    return dy, ds.astype(scale.dtype), db.astype(shift0.dtype)


_fused.defvjp(_fused_fwd, _fused_bwd)


# -- public API --------------------------------------------------------------

def fused_bn_relu(y: jax.Array, scale: jax.Array, shift: jax.Array,
                  *, interpret=None) -> jax.Array:
    """``relu(y * scale + shift)`` with per-channel (last-axis) affine, as one
    fused VMEM-resident pass; differentiable (fused backward)."""
    _check_channels(y.shape[-1])
    return observe_eager(
        "fused_bn_relu", partial(_fused, resolve_interpret(interpret)),
        y, scale, shift,
    )


def fused_bn_residual_relu(y: jax.Array, scale: jax.Array, shift: jax.Array,
                           residual: jax.Array, *, interpret=None) -> jax.Array:
    """``relu(y * scale + shift + residual)`` — the full BasicBlock epilogue
    (BN apply, shortcut add, activation) as one fused pass; differentiable."""
    _check_channels(y.shape[-1])
    return observe_eager(
        "fused_bn_residual_relu", partial(_fused_res, resolve_interpret(interpret)),
        y, scale, shift, residual,
    )


# -- pure-jnp reference (the conformance oracle for the kernels) -------------

def fused_block_reference(y: jax.Array, scale: jax.Array, shift: jax.Array,
                          residual=None) -> jax.Array:
    z = y.astype(jnp.float32) * scale.astype(jnp.float32) + shift.astype(jnp.float32)
    if residual is not None:
        z = z + residual.astype(jnp.float32)
    return jnp.maximum(z, 0.0).astype(y.dtype)
