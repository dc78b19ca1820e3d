"""Per-leaf compression codecs for model-update payloads (wire v2).

The compression operators the simulation path already owns
(``ops/compression.py``: QSGD, top-k with error feedback, the fused int8
Pallas kernel) were wired into nothing on the distributed path — cross-silo
clients shipped full-f32 pytrees every round.  This module turns them into
wire codecs: :func:`compress_pytree` maps a pytree of (delta) arrays to a
pytree where large float leaves become :class:`~fedml_tpu.comm.wire.
CompressedLeaf` segments (``qsgd8`` via ``ops/pallas/quantize.py``'s
block-scaled stochastic int8, ``topk`` as sparse indices+values with the
``ef_top_k`` error-feedback residual carried by the caller across rounds),
and small or non-float leaves ride raw — quantizing a 64-element BatchNorm
bias into a padded 1024-element block would *expand* it.

Decompression lives in ``comm.wire`` (numpy-only, so a server can fold
arriving updates without touching jax), keeping the format polyglot.

Payload accounting lands in the process-global registry:
``fedml_comm_payload_bytes_total`` / ``fedml_comm_payload_raw_bytes_total``
(wire vs dense-equivalent bytes, by codec) and the last observed
``fedml_comm_compression_ratio``.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from ..obs import registry as obsreg
from . import wire

PAYLOAD_BYTES = obsreg.REGISTRY.counter(
    "fedml_comm_payload_bytes_total",
    "Model-update payload bytes as encoded on the wire, by codec.",
    labels=("codec",),
)
PAYLOAD_RAW_BYTES = obsreg.REGISTRY.counter(
    "fedml_comm_payload_raw_bytes_total",
    "Dense-equivalent bytes of the same model-update payloads, by codec.",
    labels=("codec",),
)
COMPRESSION_RATIO = obsreg.REGISTRY.gauge(
    "fedml_comm_compression_ratio",
    "Last observed dense/wire payload ratio, by codec.",
    labels=("codec",),
)

#: codecs a payload leaf may carry (``raw`` is the identity)
CODECS = ("raw", "qsgd8", "topk")

#: secure-aggregation upload forms (ISSUE 15): masked field vectors on the
#: minimal ring dtype.  ``secagg_dense`` = fixed-point over the M31 field
#: (u32 wire); ``secagg_qsgd8`` = the quantize-then-mask composition (int8
#: grid in a cohort-sized ring).  Accounted through the same payload
#: counters so bytes/round trajectories cover the trusted path too.
MASKED_CODECS = ("secagg_dense", "secagg_qsgd8")

#: leaves below this element count stay raw: the qsgd8 block padding (1024
#: elements) would expand them, and their bytes are noise at model scale
DEFAULT_MIN_COMPRESS_ELEMS = 1024

#: per-tree floor for LOW-RANK exchanged trees (LoRA adapter factors): the
#: smallest leaf size at which qsgd8 cannot expand.  A leaf of n f32 elements
#: is 4n raw bytes and ceil(n/1024)*(1024 + 4) compressed bytes, so for
#: n <= 1024 compression shrinks iff n > 257 — 260 adds a small margin.
#: Trainers whose whole payload is rank-r factors (``LoRASiloTrainer``)
#: declare this as their ``comm_compress_min_elems`` so adapter leaves ride
#: the compressed wire where the model-scale default would leave them raw.
LOW_RANK_MIN_COMPRESS_ELEMS = 260


def codec_from_config(cfg) -> Optional[str]:
    """``extra.comm_compression`` -> validated codec name, or None when
    compression is off (unset / ``no`` / ``off`` / ``raw``)."""
    from ..core.flags import cfg_extra

    name = str(cfg_extra(cfg, "comm_compression") or "").strip().lower()
    if name in ("", "no", "off", "none", "raw"):
        return None
    if name not in CODECS:
        raise ValueError(f"unknown comm_compression {name!r}; known: {CODECS[1:]}")
    return name


def _compress_vec(codec: str, vec, leaf_key, residual, ratio: float):
    """One flat f32 vector -> (segments, meta, new_residual).  jax-side: the
    qsgd8 path runs the fused Pallas kernel (interpret mode off-TPU)."""
    import jax
    import jax.numpy as jnp

    if codec == "qsgd8":
        from ..ops.pallas import quantize as q

        values, scales, n = q.quantize_int8_stochastic(vec, leaf_key)
        segments = (np.asarray(scales, dtype="<f4"),
                    np.asarray(values, np.int8).reshape(-1))
        return segments, {"blocks": int(scales.shape[0]), "length": int(n)}, residual
    if codec == "topk":
        # ef_top_k semantics (ops/compression.py) in sparse wire form: add
        # the carried residual, keep the k largest-|.| entries as explicit
        # (index, value) pairs, keep everything dropped as the next residual
        corrected = vec if residual is None else vec + jnp.asarray(residual, jnp.float32)
        k = max(1, int(ratio * corrected.shape[0]))
        _, idx = jax.lax.top_k(jnp.abs(corrected), k)
        vals = corrected[idx]
        new_residual = np.asarray(corrected.at[idx].set(0.0))
        segments = (np.asarray(idx, dtype="<i4"), np.asarray(vals, dtype="<f4"))
        return segments, {"size": int(corrected.shape[0]), "k": int(k)}, new_residual
    raise ValueError(f"unknown codec {codec!r}")


def compress_pytree(tree, codec: Optional[str], *, key=None, residuals=None,
                    ratio: float = 0.01,
                    min_elems: int = DEFAULT_MIN_COMPRESS_ELEMS):
    """Compress the large float leaves of ``tree`` with ``codec``.

    Returns ``(compressed_tree, new_residuals, stats)``.  ``residuals`` /
    ``new_residuals`` are leaf-aligned lists (jax flatten order) carrying the
    top-k error-feedback state across rounds; qsgd8 is unbiased and carries
    none.  ``stats`` = {"raw_bytes", "wire_bytes", "ratio"}.  ``min_elems``
    is the per-tree floor: callers whose whole tree is low-rank (LoRA
    adapters) pass :data:`LOW_RANK_MIN_COMPRESS_ELEMS` instead of the
    model-scale default.
    """
    import jax
    import jax.numpy as jnp

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if codec is None:
        return tree, residuals, {"raw_bytes": sum(np.asarray(l).nbytes for l in leaves),
                                 "wire_bytes": sum(np.asarray(l).nbytes for l in leaves),
                                 "ratio": 1.0}
    if key is None:
        key = jax.random.PRNGKey(0)
    new_residuals: list = [None] * len(leaves)
    out_leaves: list = []
    raw_bytes = 0
    wire_bytes = 0
    for i, leaf in enumerate(leaves):
        a = np.asarray(leaf)
        raw_bytes += a.nbytes
        if a.dtype.kind != "f" or a.size < min_elems:
            out_leaves.append(a)
            wire_bytes += a.nbytes
            continue
        vec = jnp.asarray(a.reshape(-1), jnp.float32)
        prev = residuals[i] if residuals is not None else None
        segments, meta, new_residuals[i] = _compress_vec(
            codec, vec, jax.random.fold_in(key, i), prev, ratio
        )
        cl = wire.CompressedLeaf(codec, a.dtype.str, a.shape, meta, segments)
        out_leaves.append(cl)
        wire_bytes += cl.nbytes
    PAYLOAD_BYTES.inc(wire_bytes, codec=codec)
    PAYLOAD_RAW_BYTES.inc(raw_bytes, codec=codec)
    ratio_out = raw_bytes / max(wire_bytes, 1)
    COMPRESSION_RATIO.set(ratio_out, codec=codec)
    return (jax.tree_util.tree_unflatten(treedef, out_leaves), new_residuals,
            {"raw_bytes": int(raw_bytes), "wire_bytes": int(wire_bytes),
             "ratio": float(ratio_out)})


def note_masked_payload(codec: str, wire_bytes: int, raw_bytes: int) -> None:
    """Account one secure-aggregation upload (``codec`` from
    :data:`MASKED_CODECS`): ``wire_bytes`` = the packed masked vector as
    shipped, ``raw_bytes`` = the dense f32 equivalent."""
    PAYLOAD_BYTES.inc(int(wire_bytes), codec=codec)
    PAYLOAD_RAW_BYTES.inc(int(raw_bytes), codec=codec)
    COMPRESSION_RATIO.set(raw_bytes / max(wire_bytes, 1), codec=codec)


def payload_counters() -> dict:
    """Snapshot of the payload accounting (for BENCH json / tests)."""
    out = {}
    for codec in CODECS[1:] + MASKED_CODECS:
        wire_b = PAYLOAD_BYTES.value(codec=codec)
        raw_b = PAYLOAD_RAW_BYTES.value(codec=codec)
        if wire_b or raw_b:
            out[codec] = {"wire_bytes": int(wire_b), "raw_bytes": int(raw_b),
                          "ratio": round(raw_b / max(wire_b, 1.0), 3)}
    return out
