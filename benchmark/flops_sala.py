"""Operations and bytes that a MiniCPM-SALA adapter fine-tuning step REQUIRES,
from shapes (the companion of ``flops.py`` for configuration
``minicpm_sala_d4``).

The base is frozen, so a projection requires its forward product and the
gradient to its input (4 FLOPs a parameter and token), never the gradient of
its kernel; layer 0's q, k, v and gate projections feed only frozen things on
their input side and require the forward alone.  The adapters' own products
are counted in full.  A lightning layer is counted by its recurrence
(``k_t^T v_t`` into the state and ``q_t S_t``: 4 d^2 a head and token), not by
the chunked form that computes it; a sparse layer by the keys it KEEPS (scores
and values over the kept tokens at or before each query) plus the scores of
the compressed keys that the selection itself needs, never by the masked
scores a dense pass also computes.  Recomputed (remat) work is never counted.
Bytes are the least an algorithm moves: each operand and result once, bf16.
"""

from __future__ import annotations

import re

import numpy as np

from flops import BF16, _matmul


def _mixers(c: dict) -> list[str]:
    return list(c["mixer_types"])[: c["num_hidden_layers"]]


def mixer_heads(c: dict, kind: str) -> tuple[int, int, int]:
    """(query heads, key/value heads, head size) of a layer of ``kind``."""
    if kind == "lightning-attn":
        return c["lightning_nh"], c["lightning_nkv"], c["lightning_head_dim"]
    return c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]


def layer_projections(c: dict, kind: str) -> dict[str, tuple[int, int]]:
    """(fan_in, fan_out) of every projection of one layer."""
    d, f = c["hidden_size"], c["intermediate_size"]
    h, kv, hd = mixer_heads(c, kind)
    return {"attn/wq": (d, h * hd), "attn/wk": (d, kv * hd), "attn/wv": (d, kv * hd),
            "attn/wg": (d, h * hd), "attn/wo": (h * hd, d),
            "mlp/w_gate": (d, f), "mlp/w_up": (d, f), "mlp/w_down": (f, d)}


def param_counts(c: dict) -> dict:
    """Matmul parameters by kind of layer, the embedding and head, and the
    base's total (norm scales included)."""
    d, v = c["hidden_size"], c["vocab_size"]
    per_kind = {k: sum(i * o for i, o in layer_projections(c, k).values())
                for k in ("minicpm4", "lightning-attn")}
    norms = 0
    for kind in _mixers(c):
        h, _, hd = mixer_heads(c, kind)
        norms += 2 * d + 2 * hd + (h * hd if kind == "lightning-attn" else 0)
    matmul = sum(per_kind[k] for k in _mixers(c)) + d * v
    return {**per_kind, "embed_and_head": 2 * v * d, "matmul": matmul,
            "total": matmul + v * d + norms + d}


def adapter_shapes(c: dict, job: dict) -> list[tuple[int, int, int]]:
    """(fan_in, rank, fan_out) of every adapter of the job."""
    out = []
    for i, kind in enumerate(_mixers(c)):
        for name, (fan_in, fan_out) in layer_projections(c, kind).items():
            if re.fullmatch(job["lora_targets"], f"layer_{i}/{name}/kernel"):
                out.append((fan_in, job["lora_rank"], fan_out))
    return out


def kept_keys(c: dict, seq_len: int) -> tuple[float, float]:
    """(kept, causal): keys one KV head of a sparse layer attends over one
    sequence, summed over its queries, and what causal attention would.  The
    count depends on how MANY blocks the selection keeps, not on which."""
    z = c["sparse_config"]
    t = np.arange(seq_len, dtype=np.int64)
    causal = float(np.sum(t + 1))
    if seq_len <= z["dense_len"]:
        return causal, causal
    bs = z["block_size"]
    first_window = np.maximum((t - z["window_size"] + 1) // bs, 0)
    # tokens at or before t in the window's blocks, and in the initial blocks
    # where the window has left them behind
    window = t + 1 - first_window * bs
    init = np.minimum(z["init_blocks"], first_window) * bs
    others = np.maximum(first_window - z["init_blocks"], 0)   # whole blocks, all in the past
    return float(np.sum(window + init + np.minimum(others, z["topk"]) * bs)), causal


def valid_compressed_keys(c: dict, seq_len: int) -> float:
    """Compressed keys that end at or before each query, summed over queries."""
    z = c["sparse_config"]
    if seq_len <= z["dense_len"]:
        return 0.0
    t = np.arange(seq_len, dtype=np.int64)
    return float(np.sum(np.maximum((t - z["kernel_size"] + 1) // z["kernel_stride"] + 1, 0)))


def mixer_work(c: dict, kind: str, batch: int, seq_len: int) -> tuple[float, float]:
    """(FLOPs, least bytes) one layer's mixer requires for forward and
    backward over a batch, projections left out: q, k, v and the output's
    gradient read, the output and three gradients written, each once."""
    h, kv, hd = mixer_heads(c, kind)
    t = batch * seq_len
    moved = float(BF16 * t * hd * (2 * (h + 2 * kv) + 2 * h))
    if kind == "lightning-attn":
        return 3.0 * 4.0 * hd * hd * h * t, moved
    kept, _ = kept_keys(c, seq_len)
    attend = 4.0 * hd * (h // kv) * kv * kept * batch          # scores + values over kept keys
    select = 2.0 * hd * h * valid_compressed_keys(c, seq_len) * batch   # forward only, no gradient
    return 3.0 * attend + select, moved


def mixers_work(c: dict, kind: str, batch: int, seq_len: int) -> list[tuple[float, float]]:
    """``mixer_work`` once for every layer of ``kind`` in one step."""
    return [mixer_work(c, kind, batch, seq_len)] * _mixers(c).count(kind)


def train_flops_per_step(c: dict, job: dict, batch: int, seq_len: int) -> float:
    t = batch * seq_len
    n = param_counts(c)
    first = layer_projections(c, _mixers(c)[0])
    no_input_grad = sum(first[k][0] * first[k][1] for k in ("attn/wq", "attn/wk", "attn/wv", "attn/wg"))
    adapters = sum(r * (i + o) for i, r, o in adapter_shapes(c, job))
    mixers = sum(mixer_work(c, k, batch, seq_len)[0] for k in _mixers(c))
    return t * (4.0 * n["matmul"] - 2.0 * no_input_grad + 6.0 * adapters) + mixers


def step_matmuls(c: dict, job: dict, batch: int, seq_len: int) -> list[tuple[float, float]]:
    """(FLOPs, least bytes) of every product one step requires: each
    projection forward and its input gradient, the adapters' six products, and
    each mixer's work as one entry."""
    t = batch * seq_len
    out: list[tuple[float, float]] = []
    for i, kind in enumerate(_mixers(c)):
        for name, (fan_in, fan_out) in layer_projections(c, kind).items():
            out.append(_matmul(t, fan_in, fan_out))
            if not (i == 0 and name in ("attn/wq", "attn/wk", "attn/wv", "attn/wg")):
                out.append(_matmul(t, fan_out, fan_in))
        out.append(mixer_work(c, kind, batch, seq_len))
    out.extend([_matmul(t, c["hidden_size"], c["vocab_size"]),
                _matmul(t, c["vocab_size"], c["hidden_size"])])
    for fan_in, r, fan_out in adapter_shapes(c, job):
        out.extend([_matmul(t, fan_in, r), _matmul(t, r, fan_out)] * 3)
    return out


def check() -> None:
    """The counts ISSUE 29 reckoned the cut with, and the step's two sums
    agreeing with each other."""
    import json
    import os

    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "configs",
                           "minicpm_sala_d4.json")) as fh:
        c = json.load(fh)
    n = param_counts(c)
    assert abs(n["minicpm4"] - 253.8e6) < 0.1e6, n
    assert abs(n["lightning-attn"] - 285.2e6) < 0.1e6, n
    assert abs(n["embed_and_head"] - 601.7e6) < 0.1e6, n
    assert abs(n["total"] - 1711.1e6) < 0.1e6, n
    job = {"lora_rank": 8, "lora_targets": r".*attn/w[qkvo]/kernel"}
    assert sum(r * (i + o) for i, r, o in adapter_shapes(c, job)) == 987136
    kept, causal = kept_keys(c, 16384)
    assert 0.3 < kept / causal < 0.7, (kept, causal)
    assert kept_keys(c, 8192)[0] == kept_keys(c, 8192)[1] == 8192 * 8193 / 2
    whole = train_flops_per_step(c, job, 1, 16384)
    parts = sum(f for f, _ in step_matmuls(c, job, 1, 16384))
    assert abs(whole - parts) / whole < 1e-9, (whole, parts)
    # 4 x 1,410.2M x 16,384 = 92.4 TFLOP of projections, about 4 of the sparse
    # layer's kept keys, 0.3 of the three recurrences (ISSUE 29's 139-185
    # counted the frozen kernels' gradients too)
    assert 95e12 < whole < 100e12, whole


if __name__ == "__main__":
    check()
    print("flops_sala ok")
