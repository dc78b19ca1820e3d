"""CIFAR ResNets (resnet20/32/44/56) in flax.linen.

Capability parity with the reference's torch CIFAR ResNet family
(``model/cv/resnet.py`` — resnet20/32/44/56, 3 stages of widths 16/32/64,
option-A identity shortcuts) and its BN-free GroupNorm variant
(``model/cv/resnet_gn.py``), which the reference carries precisely because
BatchNorm statistics are ill-posed under federated averaging (SURVEY.md §7
hard part 3).

TPU notes: NHWC layout (XLA-native), bf16-friendly conv/matmul, static shapes
throughout.  BatchNorm running stats live in the ``batch_stats`` collection and
are treated as part of the federated state (averaged with the same weights as
parameters, matching FedAvg-on-state_dict in the reference, which averages BN
buffers too — ``fedavg_api.py:144-159`` iterates all state_dict keys).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..ops.pallas.fused_block import fused_bn_relu, fused_bn_residual_relu


class BasicBlock(nn.Module):
    filters: int
    stride: int = 1
    norm: str = "batch"
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = True):
        residual = x
        y = nn.Conv(self.filters, (3, 3), strides=(self.stride, self.stride), padding="SAME", use_bias=False, dtype=self.dtype)(x)
        y = _norm_layer(self.norm, train, self.dtype)(y)
        y = nn.relu(y)
        y = nn.Conv(self.filters, (3, 3), padding="SAME", use_bias=False, dtype=self.dtype)(y)
        y = _norm_layer(self.norm, train, self.dtype)(y)
        if residual.shape != y.shape:
            # Option-A shortcut (parameter-free, as in the reference's
            # LambdaLayer pad shortcut): stride-subsample + zero-pad channels.
            residual = residual[:, :: self.stride, :: self.stride, :]
            pad = self.filters - residual.shape[-1]
            residual = jnp.pad(residual, ((0, 0), (0, 0), (0, 0), (pad // 2, pad - pad // 2)))
        return nn.relu(y + residual)


def _norm_layer(norm: str, train: bool, dtype=jnp.float32):
    if norm == "group":
        return nn.GroupNorm(num_groups=2, dtype=dtype)
    return nn.BatchNorm(use_running_average=not train, momentum=0.9, epsilon=1e-5, dtype=dtype)


class _FusedBNScaleShift(nn.Module):
    """BatchNorm stats/params with ``nn.BatchNorm``'s exact variable layout
    (params ``scale``/``bias``, batch_stats ``mean``/``var``, f32, momentum
    0.9, eps 1e-5, fast variance), returning the folded per-channel affine
    ``(scale, shift)`` with ``normalized = x * scale + shift`` instead of
    normalizing — the application itself is the fused Pallas epilogue's job.

    Instantiated with an explicit ``name="BatchNorm_k"`` so the fused model's
    variable tree is IDENTICAL (names, shapes, init values) to the unfused
    one: checkpoints, FedAvg state averaging, and the A/B bench all interop.

    Gradients flow through mean/var into the conv output exactly as in
    ``nn.BatchNorm`` — the folding is plain jnp, so autodiff chains the
    kernel's d(scale)/d(shift) cotangents back through rsqrt and the batch
    reductions (which XLA fuses into the producing conv; see PERF.md).
    """

    use_running_average: bool
    momentum: float = 0.9
    epsilon: float = 1e-5

    @nn.compact
    def __call__(self, x):
        features = x.shape[-1]
        ra_mean = self.variable(
            "batch_stats", "mean", lambda s: jnp.zeros(s, jnp.float32), (features,)
        )
        ra_var = self.variable(
            "batch_stats", "var", lambda s: jnp.ones(s, jnp.float32), (features,)
        )
        gamma = self.param("scale", nn.initializers.ones, (features,), jnp.float32)
        beta = self.param("bias", nn.initializers.zeros, (features,), jnp.float32)
        if self.use_running_average:
            mean, var = ra_mean.value, ra_var.value
        else:
            xf = x.astype(jnp.float32)
            axes = tuple(range(x.ndim - 1))
            mean = jnp.mean(xf, axes)
            # fast variance (flax _compute_stats): E[x^2] - E[x]^2, clipped
            var = jnp.maximum(0.0, jnp.mean(jnp.square(xf), axes) - jnp.square(mean))
            if not self.is_initializing():
                ra_mean.value = self.momentum * ra_mean.value + (1.0 - self.momentum) * mean
                ra_var.value = self.momentum * ra_var.value + (1.0 - self.momentum) * var
        scale = gamma * jax.lax.rsqrt(var + self.epsilon)
        return scale, beta - mean * scale


class FusedBasicBlock(nn.Module):
    """``BasicBlock`` with both conv epilogues (BN apply, shortcut add, ReLU)
    executed by the fused Pallas kernel (``ops/pallas/fused_block.py``) —
    one VMEM-resident HBM pass each instead of XLA's separate loop fusions.
    Same parameter/state tree as ``BasicBlock`` (child modules carry the
    auto-generated names of the unfused variant).  BatchNorm only."""

    filters: int
    stride: int = 1
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = True):
        residual = x
        y = nn.Conv(self.filters, (3, 3), strides=(self.stride, self.stride), padding="SAME", use_bias=False, dtype=self.dtype)(x)
        s1, b1 = _FusedBNScaleShift(use_running_average=not train, name="BatchNorm_0")(y)
        y = fused_bn_relu(y, s1, b1)
        y = nn.Conv(self.filters, (3, 3), padding="SAME", use_bias=False, dtype=self.dtype)(y)
        s2, b2 = _FusedBNScaleShift(use_running_average=not train, name="BatchNorm_1")(y)
        if residual.shape != y.shape:
            residual = residual[:, :: self.stride, :: self.stride, :]
            pad = self.filters - residual.shape[-1]
            residual = jnp.pad(residual, ((0, 0), (0, 0), (0, 0), (pad // 2, pad - pad // 2)))
        return fused_bn_residual_relu(y, s2, b2, residual)


class CifarResNet(nn.Module):
    """3-stage CIFAR ResNet; depth = 6n+2.

    ``fused=True`` (the ``hp/extra.fused_blocks`` recipe flag) routes every
    conv epilogue — stem BN+ReLU and both BasicBlock epilogues — through the
    fused Pallas kernel; BatchNorm only (any other ``norm`` raises).  The
    variable tree is identical to
    the unfused model (explicit child names), so the two are checkpoint- and
    aggregation-compatible.  The default (``fused=False``) path is untouched.
    """

    num_blocks: int  # n per stage
    num_classes: int = 10
    norm: str = "batch"
    dtype: Any = jnp.float32
    fused: bool = False

    @nn.compact
    def __call__(self, x, train: bool = True):
        fused = self.fused
        if fused and self.norm != "batch":
            raise ValueError(
                f"fused=True folds BatchNorm statistics into the kernel's "
                f"affine; norm={self.norm!r} has no fused path")
        x = x.astype(self.dtype)
        x = nn.Conv(16, (3, 3), padding="SAME", use_bias=False, dtype=self.dtype)(x)
        if fused:
            s, b = _FusedBNScaleShift(use_running_average=not train, name="BatchNorm_0")(x)
            x = fused_bn_relu(x, s, b)
        else:
            x = _norm_layer(self.norm, train, self.dtype)(x)
            x = nn.relu(x)
        idx = 0
        for stage, filters in enumerate((16, 32, 64)):
            for block in range(self.num_blocks):
                stride = 2 if (stage > 0 and block == 0) else 1
                if fused:
                    # explicit name keeps the tree identical to the unfused
                    # model's auto-numbered BasicBlock_{idx}
                    x = FusedBasicBlock(filters, stride, self.dtype,
                                        name=f"BasicBlock_{idx}")(x, train=train)
                else:
                    x = BasicBlock(filters, stride, self.norm, self.dtype)(x, train=train)
                idx += 1
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=self.dtype)(x)
        return x


def resnet20(num_classes: int = 10, norm: str = "batch", dtype=jnp.float32, fused: bool = False) -> CifarResNet:
    return CifarResNet(num_blocks=3, num_classes=num_classes, norm=norm, dtype=dtype, fused=fused)


def resnet32(num_classes: int = 10, norm: str = "batch", dtype=jnp.float32, fused: bool = False) -> CifarResNet:
    return CifarResNet(num_blocks=5, num_classes=num_classes, norm=norm, dtype=dtype, fused=fused)


def resnet44(num_classes: int = 10, norm: str = "batch", dtype=jnp.float32, fused: bool = False) -> CifarResNet:
    return CifarResNet(num_blocks=7, num_classes=num_classes, norm=norm, dtype=dtype, fused=fused)


def resnet56(num_classes: int = 10, norm: str = "batch", dtype=jnp.float32, fused: bool = False) -> CifarResNet:
    return CifarResNet(num_blocks=9, num_classes=num_classes, norm=norm, dtype=dtype, fused=fused)


class SplitResNet56Client(nn.Module):
    """Client half of the split resnet56 (reference ``model/cv/resnet56/``:
    client owns conv stem + first stage; server owns the rest).  Used by
    FedGKT / SplitNN (P7/P8)."""

    norm: str = "batch"

    @nn.compact
    def __call__(self, x, train: bool = True):
        x = nn.Conv(16, (3, 3), padding="SAME", use_bias=False)(x)
        x = _norm_layer(self.norm, train)(x)
        x = nn.relu(x)
        for block in range(9):
            x = BasicBlock(16, 1, self.norm)(x, train=train)
        return x  # feature map handed to the server half


class SplitResNet56Server(nn.Module):
    num_classes: int = 10
    norm: str = "batch"

    @nn.compact
    def __call__(self, x, train: bool = True):
        for stage, filters in enumerate((32, 64)):
            for block in range(9):
                stride = 2 if block == 0 else 1
                x = BasicBlock(filters, stride, self.norm)(x, train=train)
        x = jnp.mean(x, axis=(1, 2))
        return nn.Dense(self.num_classes)(x)
