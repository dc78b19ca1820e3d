"""Parameter/activation sharding rules (GSPMD).

The reference scales LLM training with DeepSpeed ZeRO-3 param sharding
(``train/llm/distributed.py:52-68``, ``ds_z3_bf16_config.json`` — SURVEY.md
§2.14 P6).  On TPU the same thing is a set of ``PartitionSpec`` rules: fully
sharding parameters over the ``data`` axis IS ZeRO-3 (GSPMD inserts the
gather/scatter), and a ``model`` axis adds Megatron-style tensor parallelism
the reference never had.

Rules are (path-regex -> PartitionSpec) pairs matched against flattened
parameter paths, the idiom used by t5x/maxtext-style trainers.
"""

from __future__ import annotations

import re
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import AXIS_DATA, AXIS_MODEL, AXIS_SEQ

# (regex over 'layer_0/attn/wq/kernel'-style paths, spec builder)
# Specs assume kernels are (in, out) or (in, heads, head_dim).
TRANSFORMER_RULES = [
    # attention projections: shard heads/out over model axis, in over data (zero3)
    (r".*attn/w[qkv]/kernel", lambda dp, tp: P(dp, tp, None)),
    (r".*attn/wo/kernel", lambda dp, tp: P(tp, None, dp)),
    # a mixer's output gate (d_model -> heads x head_dim), laid out like wq
    (r".*attn/wg/kernel", lambda dp, tp: P(dp, tp, None)),
    # latent attention: the down-projections to the two latents shard their
    # input over data only (a latent is whole on every model shard); the
    # up-projections from them shard heads over model, like wq
    (r".*attn/w(q|kv)_a/kernel", lambda dp, tp: P(dp, None)),
    (r".*attn/w(q|kv)_b/kernel", lambda dp, tp: P(dp, tp, None)),
    # a Mamba-2 mixer: in_proj (d_model -> z | xBC | dt) like an mlp's up,
    # out_proj (inner -> d_model) like its down; the convolution's taps and
    # the per-head scalars are small and replicated
    (r".*attn/in_proj/kernel", lambda dp, tp: P(dp, tp)),
    (r".*attn/out_proj/kernel", lambda dp, tp: P(tp, dp)),
    (r".*attn/(conv_kernel|conv_bias|A_log|D|dt_bias)", lambda dp, tp: P()),
    # a KDA mixer's three short convolutions and its low-rank gates (the
    # decay's pair, beta's, the output gate's pair and its bias): small and
    # replicated; its wq wk wv wo are laid out as attention's
    (r".*attn/conv_[qkv]", lambda dp, tp: P()),
    (r".*attn/(wf_a|wf_b|wbeta|wg_a|wg_b)/(kernel|bias)", lambda dp, tp: P()),
    # a sparse-attention mixer's indexer: its query heads (d_model -> heads x
    # width), its one key and its weights shard their input over data only
    # (the choice sums over the heads on every shard); the key's LayerNorm is
    # small and replicated
    (r".*attn/indexer/(wq|wk|weights)/kernel", lambda dp, tp: P(dp, None, None)),
    (r".*attn/indexer/k_norm/(scale|bias)", lambda dp, tp: P()),
    # mlp: gate/up shard out over model; down shards in over model; an expert
    # layer's shared experts (moe/shared) are an mlp
    (r".*(mlp|moe/shared)/w_(gate|up)/kernel", lambda dp, tp: P(dp, tp)),
    (r".*(mlp|moe/shared)/w_down/kernel", lambda dp, tp: P(tp, dp)),
    # the held experts' stacked kernels (experts, in, out): each expert laid
    # out like an mlp; the leading axis is kept whole until a mesh has an
    # expert axis to spread it over.  The router is small and replicated.
    (r".*moe/experts/w_(gate|up)", lambda dp, tp: P(None, dp, tp)),
    (r".*moe/experts/w_down", lambda dp, tp: P(None, tp, dp)),
    (r".*moe/router/(kernel|e_score_correction_bias)", lambda dp, tp: P()),
    # the MTP module's (2 d_model -> d_model) projection, like an mlp's down
    (r".*mtp/proj/kernel", lambda dp, tp: P(tp, dp)),
    # embeddings / head: vocab over model axis
    (r".*embed/embedding", lambda dp, tp: P(tp, dp)),
    (r".*lm_head/kernel", lambda dp, tp: P(dp, tp)),
    # norms replicated
    (r".*norm.*/scale", lambda dp, tp: P()),
]


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def partition_specs(params, rules=TRANSFORMER_RULES, dp_axis: Optional[str] = AXIS_DATA,
                    tp_axis: Optional[str] = AXIS_MODEL, mesh: Optional[Mesh] = None):
    """Pytree of PartitionSpecs for ``params`` by first-matching rule.

    Axes absent from ``mesh`` (or of size 1) degrade to None in the spec, so
    the same rules serve pure-DP, pure-TP, and hybrid meshes.
    """
    def axis_or_none(name):
        if name is None or mesh is None:
            return name
        return name if (name in mesh.shape and mesh.shape[name] > 1) else None

    dp = axis_or_none(dp_axis)
    tp = axis_or_none(tp_axis)

    def spec_for(path, leaf):
        ps = _path_str(path)
        for pattern, builder in rules:
            if re.fullmatch(pattern, ps):
                spec = builder(dp, tp)
                # trim/extend to leaf rank
                entries = list(spec)[: leaf.ndim]
                entries += [None] * (leaf.ndim - len(entries))
                # drop shardings that don't divide the dim evenly
                entries = [
                    e if e is not None and leaf.shape[i] % (mesh.shape[e] if mesh else 1) == 0 else (e if e is None else None)
                    for i, e in enumerate(entries)
                ]
                return P(*entries)
        return P()  # replicate by default

    return jax.tree_util.tree_map_with_path(spec_for, params)


def named_shardings(params, mesh: Mesh, **kw):
    specs = partition_specs(params, mesh=mesh, **kw)
    return jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), specs,
                                  is_leaf=lambda x: isinstance(x, P))


def shard_params(params, mesh: Mesh, **kw):
    sh = named_shardings(params, mesh, **kw)
    return jax.tree_util.tree_map(jax.device_put, params, sh)


def batch_sharding(mesh: Mesh, dp_axis: str = AXIS_DATA, seq_axis: Optional[str] = None):
    """(batch, seq, ...) activation sharding: batch over dp, seq over sp."""
    dp = dp_axis if dp_axis in mesh.shape and mesh.shape[dp_axis] > 1 else None
    sp = seq_axis if seq_axis and seq_axis in mesh.shape and mesh.shape[seq_axis] > 1 else None
    return NamedSharding(mesh, P(dp, sp))
