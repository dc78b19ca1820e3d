"""Analytical FLOPs accounting + device peak lookup for MFU reporting.

MFU = (model FLOPs per second) / (chip peak FLOPs): the *nominal* FLOPs of the
training computation (fwd + bwd = 3x fwd for matmul-dominated nets), NOT the
executed FLOPs — rematerialization recompute does not count as useful work.
This is the PaLM-appendix convention the scaling literature uses (executed
FLOPs from XLA's cost model would over-credit remat recompute).

The reference has no MFU accounting anywhere (its perf story is wall-clock CI
budgets, SURVEY.md §6); BASELINE.md sets >=35% MFU as the target, so the
accounting itself is a new obligation of the TPU build.
"""

from __future__ import annotations

from typing import Optional

#: Peak dense bf16 FLOP/s of ONE chip, keyed by the ``device_kind`` string
#: JAX reports (compared case-insensitively, whole string).  Source: Google
#: Cloud TPU documentation, per-generation system-architecture pages ("TPU
#: v5e": 197 TFLOP/s bf16 per chip; v2 45, v3 123, v4 275, v5p 459, v6e
#: 918).  JAX names v5e/v6e "TPU v5 lite"/"TPU v6 lite" and v5p "TPU v5";
#: the marketing names are listed beside them.  The one table for every MFU
#: in the repo: a TPU that is not in it is an error, never a default.
PEAK_BF16_FLOPS_BY_KIND = {
    "tpu v2": 45e12,
    "tpu v3": 123e12,
    "tpu v4": 275e12,
    "tpu v5 lite": 197e12,
    "tpu v5e": 197e12,
    "tpu v5": 459e12,
    "tpu v5p": 459e12,
    "tpu v6 lite": 918e12,
    "tpu v6e": 918e12,
}


def device_peak_flops(device=None) -> Optional[float]:
    """Peak bf16 FLOP/s of one chip.  ``None`` off-TPU (a CPU has no peak
    here and so no MFU); a TPU whose ``device_kind`` is not in the table
    raises — add the kind with its source rather than guess."""
    if device is None:
        import jax

        device = jax.devices()[0]
    if device.platform != "tpu":
        return None
    kind = str(device.device_kind)
    try:
        return PEAK_BF16_FLOPS_BY_KIND[kind.lower()]
    except KeyError:
        raise ValueError(
            f"no peak FLOP/s on record for TPU device_kind {kind!r}; add it "
            f"to ops/flops.py PEAK_BF16_FLOPS_BY_KIND with its source "
            f"(known: {sorted(PEAK_BF16_FLOPS_BY_KIND)})") from None


def local_peak_flops() -> Optional[float]:
    """Aggregate peak over every device this process sees (``None`` off-TPU)
    — the MFU denominator for a program that spans the local mesh."""
    import jax

    per_chip = device_peak_flops()
    return None if per_chip is None else per_chip * jax.device_count()


def transformer_train_flops_per_token(
    n_params: int, n_embed_params: int, n_layers: int, d_model: int, seq_len: int
) -> float:
    """Nominal train FLOPs per token: 6*(matmul params) + attention term.

    ``n_embed_params`` (the gather-only embedding table) is excluded from the
    6N term; the lm_head projection participates in matmuls and stays in.
    The attention score/value matmuls add 12 * L * s * d (fwd 4*s*d per layer,
    x3 for fwd+bwd; counted un-halved since the dense kernel computes the full
    s^2 score matrix).
    """
    return 6.0 * (n_params - n_embed_params) + 12.0 * n_layers * seq_len * d_model


def resnet20_cifar_train_flops_per_sample() -> float:
    """ResNet-20 CIFAR-10 at 32x32: ~40.8M MACs fwd => 81.7 MFLOPs fwd,
    x3 for fwd+bwd.  (Conv MACs from the standard He et al. arch: 3 stages x
    3 blocks x 2 convs at 16/32/64 channels + stem + fc.)"""
    fwd = 81.7e6
    return 3.0 * fwd
