"""Operations and bytes that a Granite 4.0-H adapter fine-tuning step on packed
documents REQUIRES, from shapes (the companion of ``flops.py`` for
configuration ``granite_4_0_h_micro_d10``).

The base is frozen, so a projection requires its forward product and the
gradient to its input (4 FLOPs a parameter and token), never the gradient of
its kernel; layer 0's ``in_proj`` reads a frozen embedding's norm: forward
alone.  The adapters' own products are counted in full.  ``step_matmuls``
counts ONLY the products that are projections, SwiGLU and head (what XLA runs
as matrix-product fusions whatever implements the mixers); attention and the
scan are counted OUT of it and have work of their own, which does not depend
on what implements them:

attention  the causal pairs INSIDE documents (``doc_pairs``: a query and a
    key at or before it in its own document), scores and values, forward and
    the two gradients of each (x 3, as ``flops.py`` counts attention).  A
    kernel that computes masked tiles does more and reads low.
scan  Mamba-2's chunked form at the PUBLISHED chunk (``mamba_chunk_size``): a
    token and head, forward, ``2 x ((c + 1) / 2 x (N / heads-a-group + P) + 2 N
    P)``: the causal half of the chunk's ``C B^T`` (shared by a group's heads)
    and of its product with ``dt X``, the carried state read (``C S``) and
    fed (``X^T B``); backward twice that.  Bytes: x, B, C, dt and y once
    forward, those, dy and the four gradients backward, a state a chunk
    written and read.
convolution  bytes alone: xBC read and written forward; xBC and dy read and
    dx written backward.

Recomputed (remat) work is never counted.  Bytes are the least an algorithm
moves, each operand and result once, bf16 (dt and the states float32).
"""

from __future__ import annotations

import re

from flops import BF16, _matmul

F32 = 4


def kinds(c: dict) -> list[str]:
    return list(c["layer_types"])[: c["num_hidden_layers"]]


def mamba_sizes(c: dict) -> dict:
    h, p, g, n = c["mamba_n_heads"], c["mamba_d_head"], c["mamba_n_groups"], c["mamba_d_state"]
    inner = h * p
    return {"h": h, "p": p, "g": g, "n": n, "inner": inner, "conv": inner + 2 * g * n,
            "in": 2 * inner + 2 * g * n + h, "chunk": c["mamba_chunk_size"], "taps": c["mamba_d_conv"]}


def projections(c: dict, kind: str) -> dict[str, tuple[int, int]]:
    """(fan_in, fan_out) of a mixer's projections by kernel."""
    d = c["hidden_size"]
    if kind == "mamba":
        m = mamba_sizes(c)
        return {"attn/in_proj": (d, m["in"]), "attn/out_proj": (m["inner"], d)}
    hd = d // c["num_attention_heads"]
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    return {"attn/wq": (d, q), "attn/wk": (d, kv), "attn/wv": (d, kv), "attn/wo": (q, d)}


def swiglu(c: dict) -> list[tuple[int, int]]:
    d, f = c["hidden_size"], c["shared_intermediate_size"]
    return [(d, f), (d, f), (f, d)]


def param_counts(c: dict) -> dict:
    """Parameters of each part, of each kind of layer, of the cut and of the
    published model (norm scales included; the head is the embedding)."""
    d, v, m = c["hidden_size"], c["vocab_size"], mamba_sizes(c)
    mamba = (sum(i * o for i, o in projections(c, "mamba").values())
             + m["taps"] * m["conv"] + m["conv"] + 3 * m["h"] + m["inner"])
    attention = sum(i * o for i, o in projections(c, "attention").values())
    mlp = sum(i * o for i, o in swiglu(c))
    layer = {"mamba": mamba + mlp + 2 * d, "attention": attention + mlp + 2 * d}
    whole = lambda ks: sum(layer[k] for k in ks) + v * d + d
    return {"mamba_mixer": mamba, "attention_mixer": attention, "mlp": mlp,
            "mamba_layer": layer["mamba"], "attention_layer": layer["attention"],
            "embedding": v * d, "total": whole(kinds(c)),
            "published_total": whole(c["published"]["layer_types"])}


def adapter_shapes(c: dict, job: dict) -> list[tuple[int, int, int]]:
    """(fan_in, rank, fan_out) of every adapter of the job."""
    return [(fan_in, job["lora_rank"], fan_out)
            for i, kind in enumerate(kinds(c)) for name, (fan_in, fan_out) in projections(c, kind).items()
            if re.fullmatch(job["lora_targets"], f"layer_{i}/{name}/kernel")]


def doc_pairs(lengths) -> int:
    """Causal pairs inside documents of one row."""
    return sum(n * (n + 1) // 2 for n in lengths)


def attention_work(c: dict, batch: int, lengths) -> tuple[float, float]:
    """(FLOPs, least bytes) of one attention layer's scores and values over
    the pairs inside documents, forward and backward: q, k, v and the
    output's gradient read, the output and three gradients written, once."""
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    hd, s = c["hidden_size"] // h, sum(lengths)
    flops = 3.0 * 2.0 * (hd + hd) * batch * h * doc_pairs(lengths)
    return flops, float(BF16 * batch * s * hd * (4 * h + 4 * kv))


def scan_work(c: dict, batch: int, seq_len: int) -> tuple[float, float]:
    """(FLOPs, least bytes) of one Mamba-2 layer's selective scan, forward
    and backward (see the module's docstring)."""
    m = mamba_sizes(c)
    t, half = batch * seq_len, (min(m["chunk"], seq_len) + 1) / 2.0
    per_token_head = 2.0 * (half * (m["n"] * m["g"] / m["h"] + m["p"]) + 2.0 * m["n"] * m["p"])
    operands = t * (2 * m["inner"] * BF16 + 2 * m["g"] * m["n"] * BF16 + m["h"] * F32)
    states = batch * -(-seq_len // m["chunk"]) * m["h"] * m["p"] * m["n"] * F32
    return 3.0 * per_token_head * t * m["h"], float(3 * operands + 2 * states)


def conv_work(c: dict, batch: int, seq_len: int) -> tuple[float, float]:
    """(FLOPs, least bytes) of one layer's depthwise convolution with its
    silu, forward and backward: 2 FLOPs a tap and channel each way; bytes as
    the module's docstring counts them."""
    m, t = mamba_sizes(c), batch * seq_len
    return 3.0 * 2.0 * m["taps"] * m["conv"] * t, float(5 * t * m["conv"] * BF16)


def attention_module_work(c: dict, batch: int, lengths) -> list[tuple[float, float]]:
    """The attention MODULE whole, one layer: its four projections forward and
    the gradient to their inputs, and ``attention_work``."""
    t = batch * sum(lengths)
    work = [attention_work(c, batch, lengths)]
    for fan_in, fan_out in projections(c, "attention").values():
        work += [_matmul(t, fan_in, fan_out), _matmul(t, fan_out, fan_in)]
    return work


def step_matmuls(c: dict, job: dict, batch: int, seq_len: int) -> list[tuple[float, float]]:
    """(FLOPs, least bytes) of every product that is a projection, a SwiGLU's,
    the head's or an adapter's: NOT attention's and NOT the scan's."""
    t, d, v = batch * seq_len, c["hidden_size"], c["vocab_size"]
    out: list[tuple[float, float]] = []
    for i, kind in enumerate(kinds(c)):
        for name, (fan_in, fan_out) in projections(c, kind).items():
            out.append(_matmul(t, fan_in, fan_out))
            if not (i == 0 and name in ("attn/in_proj", "attn/wq", "attn/wk", "attn/wv")):
                out.append(_matmul(t, fan_out, fan_in))
        for fan_in, fan_out in swiglu(c):
            out += [_matmul(t, fan_in, fan_out), _matmul(t, fan_out, fan_in)]
    out += [_matmul(t, d, v), _matmul(t, v, d)]         # the tied head, once a loss
    for fan_in, r, fan_out in adapter_shapes(c, job):
        out.extend([_matmul(t, fan_in, r), _matmul(t, r, fan_out)] * 3)
    return out


def train_flops_per_step(c: dict, job: dict, batch: int, lengths) -> float:
    """The whole step: ``step_matmuls``, each attention layer's pairs inside
    documents, each Mamba layer's scan and convolution."""
    s = sum(lengths)
    total = sum(f for f, _ in step_matmuls(c, job, batch, s))
    for kind in kinds(c):
        if kind == "mamba":
            total += scan_work(c, batch, s)[0] + conv_work(c, batch, s)[0]
        else:
            total += attention_work(c, batch, lengths)[0]
    return total


def check() -> None:
    """The counts ISSUE 35 cut the configuration with."""
    import json
    import os

    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "configs", "granite_4_0_h_micro_d10.json")) as fh:
        c = json.load(fh)
    with open(os.path.join(here, "traffic", "lora_sft_32k_packed_b1.json")) as fh:
        t = json.load(fh)
    n, m = param_counts(c), mamba_sizes(c)
    assert (m["in"], m["conv"], m["inner"]) == (8512, 4352, 4096), m
    for key, want in (("mamba_mixer", 25_847_232), ("mlp", 50_331_648), ("mamba_layer", 76_182_976),
                      ("attention_mixer", 10_485_760), ("attention_layer", 60_821_504),
                      ("embedding", 205_520_896), ("published_total", 3_191_396_096),
                      ("total", 951_991_232)):
        assert n[key] == want, (key, n[key], want)
    job = {k: t["train_args"][k] for k in ("lora_rank", "lora_targets")}
    assert sum(r * (i + o) for i, r, o in adapter_shapes(c, job)) == 1_309_184
    lengths = t["doc_lengths"]
    s = t["seq_len"]
    assert sum(lengths) == s and doc_pairs(lengths) == 74_184_268
    assert abs(sum(x * x for x in lengths) / s ** 2 - 0.138) < 1e-3
    whole = train_flops_per_step(c, job, 1, lengths)
    products = sum(f for f, _ in step_matmuls(c, job, 1, s))
    # 4 FLOPs a matmul parameter and token (less layer 0's in_proj gradient), the head, the adapters
    assert 1.2e14 < products < 1.3e14, products
    scan, attn = 9 * scan_work(c, 1, s)[0], attention_work(c, 1, lengths)[0]
    assert 2.5e12 < scan < 3.2e12 and 1.7e12 < attn < 1.9e12, (scan, attn)
    assert products + scan + attn < whole < 1.01 * (products + scan + attn), whole


if __name__ == "__main__":
    check()
    print("flops_granite ok")
