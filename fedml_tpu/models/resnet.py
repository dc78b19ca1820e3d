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

import jax.numpy as jnp
from flax import linen as nn


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


class CifarResNet(nn.Module):
    """3-stage CIFAR ResNet; depth = 6n+2."""

    num_blocks: int  # n per stage
    num_classes: int = 10
    norm: str = "batch"
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = True):
        x = x.astype(self.dtype)
        x = nn.Conv(16, (3, 3), padding="SAME", use_bias=False, dtype=self.dtype)(x)
        x = _norm_layer(self.norm, train, self.dtype)(x)
        x = nn.relu(x)
        for stage, filters in enumerate((16, 32, 64)):
            for block in range(self.num_blocks):
                stride = 2 if (stage > 0 and block == 0) else 1
                x = BasicBlock(filters, stride, self.norm, self.dtype)(x, train=train)
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=self.dtype)(x)
        return x


def resnet20(num_classes: int = 10, norm: str = "batch", dtype=jnp.float32) -> CifarResNet:
    return CifarResNet(num_blocks=3, num_classes=num_classes, norm=norm, dtype=dtype)


def resnet32(num_classes: int = 10, norm: str = "batch", dtype=jnp.float32) -> CifarResNet:
    return CifarResNet(num_blocks=5, num_classes=num_classes, norm=norm, dtype=dtype)


def resnet44(num_classes: int = 10, norm: str = "batch", dtype=jnp.float32) -> CifarResNet:
    return CifarResNet(num_blocks=7, num_classes=num_classes, norm=norm, dtype=dtype)


def resnet56(num_classes: int = 10, norm: str = "batch", dtype=jnp.float32) -> CifarResNet:
    return CifarResNet(num_blocks=9, num_classes=num_classes, norm=norm, dtype=dtype)


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
