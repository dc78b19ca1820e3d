"""LoRA — low-rank adaptation as a pure parameter transform.

Parity with the reference's PEFT integration (``train/llm/configurations.py``
``ModelArguments`` LoRA r/alpha/dropout/target fields :181-188; FedLLM
exchanges only the PEFT state dict).  Here LoRA is functional: adapters are a
separate pytree ``{path: {"a": (in, r), "b": (r, out)}}`` and

    merged = base + (alpha / r) * reshape(a @ b)

is differentiable w.r.t. the adapters, so ``jax.grad`` of
``loss(merge(base, lora))`` trains ONLY the adapters with the base frozen —
no model surgery, works for any flax model.  The federated payload is the
adapter tree alone (the whole point of FedLLM: exchange K entries of rank-r
factors, not 7B weights).

``merge`` builds a full-size copy of every target and its gradient.  The
transformer's own projections also take the adapters on the activation side,
``x W + (x a) b`` (``as_collection`` lays the tree out as the flax collection
``lora`` that ``models/transformer.py:_project`` reads): the same function of
``(base, lora)``, with no merged copy and no gradient of a frozen kernel.
"""

from __future__ import annotations

import re
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from flax import traverse_util

DEFAULT_TARGETS = r".*attn/w[qkvo]/kernel"
#: the five projections of a latent-attention mixer (``MLAttention``)
MLA_TARGETS = r".*attn/w(q_a|q_b|kv_a|kv_b|o)/kernel"
#: the two projections of a Mamba-2 mixer (``Mamba``): ``in_proj`` (d_model ->
#: z | xBC | dt) and ``out_proj`` (inner -> d_model), both plain (in, out) kernels
MAMBA_TARGETS = r".*attn/(in|out)_proj/kernel"
#: kernels that contract all but their LAST dim ((heads, head_dim, d_model),
#: the head_dim being the values' own under latent attention); every other
#: kernel contracts its first
_FAN_IN_ALL_BUT_LAST = r".*attn/wo/kernel"
#: the held experts' stacked kernels (experts, in, out): one factor pair
#: would read the number of experts as the fan-in
_STACKED = r".*moe/experts/w_(gate|up|down)"
#: a sparse-attention mixer's indexer (``models/transformer.Indexer``): it
#: only chooses keys, no gradient passes through the choice, so an adapter on
#: it would never move; no target takes it
_FROZEN = r".*attn/indexer/.*"


def _fan_in(path: str, shape) -> int:
    lead = shape[:-1] if re.fullmatch(_FAN_IN_ALL_BUT_LAST, path) else shape[:1]
    return int(np.prod(lead))


def _match_paths(params, targets: str):
    out = []

    def visit(path, leaf):
        ps = "/".join(str(getattr(p, "key", p)) for p in path)
        if re.fullmatch(targets, ps) and leaf.ndim >= 2 and not re.fullmatch(_FROZEN, ps):
            out.append((ps, leaf.shape, leaf.dtype))

    jax.tree_util.tree_map_with_path(visit, params)
    return out


def init_lora(params, rank: int, key: jax.Array, targets: str = DEFAULT_TARGETS,
              dtype=jnp.float32) -> dict:
    """Adapter tree keyed by 'path/with/slashes' -> {a, b}."""
    lora = {}
    for i, (path, shape, _) in enumerate(_match_paths(params, targets)):
        if re.fullmatch(_STACKED, path):
            raise ValueError(
                f"LoRA targets {targets!r} match {path!r}, a stack of expert kernels {tuple(shape)}: "
                "an adapter pair per expert is not implemented, and one pair over the stack "
                "would take the number of experts for the fan-in")
        d_in = _fan_in(path, shape)
        d_out = int(np.prod(shape)) // d_in
        ka = jax.random.fold_in(key, 2 * i)
        lora[path] = {
            "a": jax.random.normal(ka, (d_in, rank), dtype) * (1.0 / max(1, d_in)) ** 0.5,
            "b": jnp.zeros((rank, d_out), dtype),  # zero init: merge starts as identity
        }
    if not lora:
        raise ValueError(f"no parameters matched LoRA targets {targets!r}")
    return lora


def merge(base_params, lora: dict, alpha: float = 16.0, rank: Optional[int] = None):
    """base + (alpha/r) * a@b, reshaped to each target's shape.  Pure and
    differentiable in ``lora``."""
    if rank is None:
        rank = next(iter(lora.values()))["a"].shape[1]
    scale = alpha / rank

    def update(path, leaf):
        ps = "/".join(str(getattr(p, "key", p)) for p in path)
        ab = lora.get(ps)
        if ab is None:
            return leaf
        delta = (ab["a"] @ ab["b"]).reshape(leaf.shape) * scale
        return leaf + delta.astype(leaf.dtype)

    return jax.tree_util.tree_map_with_path(update, base_params)


def as_collection(lora: dict, alpha: float = 16.0, rank: Optional[int] = None) -> dict:
    """The adapter tree as the flax variable collection ``lora``:
    ``{"layer_0/attn/wq/kernel": {a, b}}`` becomes ``{"layer_0": {"attn":
    {"wq": {"a": a, "b": b * alpha / r}}}}``, which the transformer's
    projections add on the activation side.  Differentiable in ``lora``."""
    if rank is None:
        rank = next(iter(lora.values()))["a"].shape[1]
    scale, flat = alpha / rank, {}
    for path, ab in lora.items():
        module, _, leaf = path.rpartition("/")
        if leaf != "kernel":
            raise ValueError(f"the activation-side path adapts kernels only, not {path!r}")
        flat[module] = {"a": ab["a"], "b": ab["b"] * scale}
    return traverse_util.unflatten_dict(flat, sep="/")


def lora_size(lora: dict) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(lora))
