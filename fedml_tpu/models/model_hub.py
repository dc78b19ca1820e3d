"""Dataset-keyed model factory.

Parity with the reference's ``model/model_hub.py:19`` (``create(args, output_dim)``):
dispatch on ``(args.model, args.dataset)`` to a model instance.  Returns a
flax.linen Module; parameter init happens in the trainer frame so the factory
stays cheap and side-effect free.
"""

from __future__ import annotations

from typing import Any

import jax.numpy as jnp

from ..arguments import Config
from ..core.flags import cfg_extra
from . import cnn_zoo, resnet, rnn, simple


def create(cfg: Config, output_dim: int) -> Any:
    name = cfg.model.lower()
    norm = getattr(cfg, "norm", "batch")
    # compute dtype threads into the conv/matmul path (params stay f32);
    # without this the whole CNN zoo silently runs f32 on the MXU's slow path
    dtype = jnp.bfloat16 if cfg.compute_dtype == "bfloat16" else jnp.float32
    if name in ("lr", "logistic_regression"):
        return simple.LogisticRegression(num_classes=output_dim)
    if name in ("cnn", "cnn_dropout"):
        only_digits = cfg.dataset in ("mnist", "fashionmnist")
        return simple.FedAvgCNN(num_classes=output_dim, only_digits=only_digits)
    if name in ("simple-cnn", "cifar_cnn", "cnn_web"):
        return simple.CifarCNN(num_classes=output_dim)
    if name == "mlp":
        # extra.mlp_hidden widens the hidden layer (comm-compression benches
        # need leaves past the qsgd8 block size); default matches upstream
        return simple.MLP(num_classes=output_dim,
                          hidden=int(cfg_extra(cfg, "mlp_hidden")))
    if name == "resnet20":
        return resnet.resnet20(output_dim, norm, dtype)
    if name == "resnet32":
        return resnet.resnet32(output_dim, norm, dtype)
    if name == "resnet44":
        return resnet.resnet44(output_dim, norm, dtype)
    if name == "resnet56":
        return resnet.resnet56(output_dim, norm, dtype)
    if name in ("resnet18_gn", "resnet_gn"):
        # BN-free escape hatch (reference model/cv/resnet_gn.py)
        return resnet.resnet20(output_dim, "group", dtype)
    if name in ("rnn", "char_lstm", "rnn_originalfedavg"):
        return rnn.CharLSTM(vocab_size=output_dim)
    if name in ("rnn_stackoverflow", "word_lstm"):
        return rnn.WordLSTM(vocab_size=output_dim)
    # CNN zoo breadth (reference model_hub.py:66-73 + model/cv/vgg.py);
    # small_input picks the CIFAR stride-1 stem for small images — derived
    # from the dataset's spec shape (public accessor applies the loader's
    # name normalization) so the knowledge lives in ONE place
    from ..data.loader import dataset_spec

    spec = dataset_spec(cfg.dataset)
    small = spec is not None and len(spec[0]) == 3 and spec[0][0] <= 36
    if name == "mobilenet":
        return cnn_zoo.MobileNetV1(num_classes=output_dim, norm=norm, dtype=dtype, small_input=small)
    if name in ("mobilenet_v3", "mobilenetv3"):
        return cnn_zoo.MobileNetV3Small(num_classes=output_dim, norm=norm, dtype=dtype, small_input=small)
    if name in ("efficientnet", "efficientnet_b0"):
        return cnn_zoo.EfficientNetB0(num_classes=output_dim, norm=norm, dtype=dtype, small_input=small)
    if name in ("vgg11", "vgg"):
        return cnn_zoo.VGG(num_classes=output_dim, depth=11, norm=norm, dtype=dtype)
    if name == "vgg16":
        return cnn_zoo.VGG(num_classes=output_dim, depth=16, norm=norm, dtype=dtype)
    raise ValueError(f"unknown model {cfg.model!r} (dataset {cfg.dataset!r})")
