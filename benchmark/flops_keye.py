"""Operations and bytes that a Keye-VL-2.0 adapter fine-tuning step REQUIRES on
one expert-parallel rank, from shapes (the companion of ``flops.py`` for
configuration ``keye_vl2_30b_a3b_d4_ep8``).

The base is frozen, so a projection requires its forward product and the
gradient to its input (4 FLOPs a parameter and token), never the gradient of
its kernel; layer 0's ``wq``, ``wk`` and ``wv`` read the norm of a frozen
embedding: forward alone.  The indexer chooses and passes no gradient: its
three projections and its scores are forward alone, the scores counted by
their products over the causal pairs (``2 x heads x width`` a pair; the
ReLU, the weights and the choice are not products).  Attention over the
chosen keys is counted by the KEPT pairs (``min(t + 1, topk)`` keys for query
``t``), scores and values forward and the two gradients of each (x 3, as
``flops.py`` counts attention); a pass that computes every causal pair and
masks the rest does more than it requires.  The held experts are counted at
their expectation under even routing (``tokens x top_k x held /
router_experts`` rows a layer), the router over all its outputs.  The
adapters' own products are counted in full.  Recomputed (remat) work is
never counted.  Bytes are the least an algorithm moves: each operand and
result once, bf16; a choice of keys as one bit a pair.
"""

from __future__ import annotations

import re

from flops import BF16, _matmul

def _sizes(c: dict) -> tuple:
    sa = c["sa_config"]
    return (c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"],
            sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"])


def attention_projections(c: dict) -> dict[str, tuple[int, int]]:
    """(fan_in, fan_out) of the four projections of one attention mixer."""
    d, h, kv, hd = _sizes(c)[:4]
    return {"attn/wq": (d, h * hd), "attn/wk": (d, kv * hd), "attn/wv": (d, kv * hd), "attn/wo": (h * hd, d)}


def indexer_projections(c: dict) -> dict[str, tuple[int, int]]:
    """(fan_in, fan_out) of the indexer's three projections: its query heads,
    its one key, its weight per head."""
    d, _, _, _, ih, ihd, _ = _sizes(c)
    return {"attn/indexer/wq": (d, ih * ihd), "attn/indexer/wk": (d, ihd), "attn/indexer/weights": (d, ih)}


def swiglu(d: int, f: int) -> list[tuple[int, int]]:
    return [(d, f), (d, f), (f, d)]


def causal_pairs(seq_len: int) -> float:
    return seq_len * (seq_len + 1) / 2


def kept_pairs(c: dict, seq_len: int) -> float:
    """Pairs (query, key) the choice keeps in one sequence: every key up to
    the ``topk``-th query, ``topk`` a query after it."""
    k = c["sa_config"]["topk"]
    return causal_pairs(seq_len) if seq_len <= k else causal_pairs(k) + (seq_len - k) * k


def param_counts(c: dict) -> dict:
    """Matmul parameters of each part and of a layer as this rank holds it,
    the base's total here (norm scales included), and the published model's."""
    d, v, fm = c["hidden_size"], c["vocab_size"], c["moe_intermediate_size"]
    attention = sum(i * o for i, o in attention_projections(c).values())
    indexer = sum(i * o for i, o in indexer_projections(c).values())
    router = d * c["router_experts"]
    expert = sum(i * o for i, o in swiglu(d, fm))
    layer = attention + indexer + router + c["num_experts"] * expert
    ihd, hd = c["sa_config"]["indexer_head_dim"], c["head_dim"]
    norms = c["num_hidden_layers"] * (2 * d + 2 * hd + 2 * ihd) + d
    matmul = c["num_hidden_layers"] * layer + d * v
    p = c["published"]
    published = (p["num_hidden_layers"] * (attention + indexer + router + p["num_experts"] * expert)
                 + 2 * p["vocab_size"] * d)
    return {"attention": attention, "indexer": indexer, "router": router, "expert": expert,
            "layer": layer, "embed_and_head": 2 * v * d, "embed_and_head_whole": 2 * p["vocab_size"] * d,
            "matmul": matmul, "total": matmul + v * d + norms, "published": published}


def adapter_shapes(c: dict, job: dict) -> list[tuple[int, int, int]]:
    """(fan_in, rank, fan_out) of every adapter of the job."""
    return [(fan_in, job["lora_rank"], fan_out)
            for i in range(c["num_hidden_layers"]) for name, (fan_in, fan_out) in attention_projections(c).items()
            if re.fullmatch(job["lora_targets"], f"layer_{i}/{name}/kernel")]


def held_rows(c: dict, tokens: int) -> float:
    """Rows the held experts of one layer see under even routing."""
    return tokens * c["num_experts_per_tok"] * c["num_experts"] / c["router_experts"]


def attention_work(c: dict, batch: int, seq_len: int) -> tuple[float, float]:
    """(FLOPs, least bytes) of one layer's attention over the chosen keys,
    forward and backward: the kept pairs' scores and values x 3; q, k, v and
    the output's gradient read, the output and three gradients written, the
    choice read, each once."""
    _, h, kv, hd = _sizes(c)[:4]
    flops = 3.0 * 2.0 * 2 * hd * h * batch * kept_pairs(c, seq_len)
    return flops, float(BF16 * batch * seq_len * hd * 4 * (h + kv) + batch * seq_len * seq_len / 8)


def index_work(c: dict, batch: int, seq_len: int) -> tuple[float, float]:
    """(FLOPs, least bytes) of one layer's index scores and choice, forward
    alone: the scores' products over the causal pairs; its queries, key and
    weights read, the choice written, once."""
    _, _, _, _, ih, ihd, _ = _sizes(c)
    flops = 2.0 * ih * ihd * batch * causal_pairs(seq_len)
    return flops, float(BF16 * batch * seq_len * (ih * ihd + ihd + ih) + batch * seq_len * seq_len / 8)


def indexer_work(c: dict, batch: int, seq_len: int) -> list[tuple[float, float]]:
    """Every product of one layer's indexer: its three projections and its scores."""
    t = batch * seq_len
    return [_matmul(t, i, o) for i, o in indexer_projections(c).values()] + [index_work(c, batch, seq_len)]


def moe_products(c: dict, tokens: int, rows: float) -> list[tuple[float, float]]:
    """(FLOPs, least bytes) of every product of one expert layer, forward and
    the gradient to its input: the router, the held experts over ``rows`` rows
    in all (spread evenly: each expert's kernels are read once a product)."""
    d, fm, held, r = c["hidden_size"], c["moe_intermediate_size"], c["num_experts"], c["router_experts"]
    out = [_matmul(tokens, d, r), _matmul(tokens, r, d)]
    for _ in range(held):
        for fan_in, fan_out in swiglu(d, fm):
            out += [_matmul(rows / held, fan_in, fan_out), _matmul(rows / held, fan_out, fan_in)]
    return out


def step_matmuls(c: dict, job: dict, batch: int, seq_len: int) -> list[tuple[float, float]]:
    """(FLOPs, least bytes) of every product one step requires."""
    t = batch * seq_len
    d, v = c["hidden_size"], c["vocab_size"]
    out: list[tuple[float, float]] = []
    for i in range(c["num_hidden_layers"]):
        for name, (fan_in, fan_out) in attention_projections(c).items():
            out.append(_matmul(t, fan_in, fan_out))
            if not (i == 0 and name != "attn/wo"):
                out.append(_matmul(t, fan_out, fan_in))
        out.append(attention_work(c, batch, seq_len))
        out += indexer_work(c, batch, seq_len)
        out += moe_products(c, t, held_rows(c, t))
    out += [_matmul(t, d, v), _matmul(t, v, d)]
    for fan_in, r, fan_out in adapter_shapes(c, job):
        out.extend([_matmul(t, fan_in, r), _matmul(t, r, fan_out)] * 3)
    return out


def train_flops_per_step(c: dict, job: dict, batch: int, seq_len: int) -> float:
    return sum(f for f, _ in step_matmuls(c, job, batch, seq_len))


def forward_flops_per_layer(c: dict, seq_len: int) -> dict:
    """One layer's forward by part, and the head, FLOPs for one sequence
    (what the cell was sized with)."""
    n = param_counts(c)
    d, h, hd = c["hidden_size"], c["num_attention_heads"], c["head_dim"]
    return {"indexer_scores": index_work(c, 1, seq_len)[0],
            "kept_attention": attention_work(c, 1, seq_len)[0] / 3.0,
            "causal_attention": 2.0 * 2 * hd * h * causal_pairs(seq_len),
            "projections": 2.0 * n["attention"] * seq_len,
            "held_experts": 2.0 * n["expert"] * held_rows(c, seq_len),
            "head": 2.0 * d * c["vocab_size"] * seq_len}


def check() -> None:
    """The counts the configuration was cut with, and the forward's parts
    the cell was sized with."""
    import json
    import os

    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "configs",
                           "keye_vl2_30b_a3b_d4_ep8.json")) as fh:
        c = json.load(fh)
    n = param_counts(c)
    for key, count in (("attention", 18_874_368), ("indexer", 2_260_992), ("router", 262_144),
                       ("expert", 4_718_592), ("embed_and_head_whole", 622_329_856)):
        assert n[key] == count, (key, n[key])
    assert abs(n["layer"] / 1e6 - 96.9) < 0.05 and abs(n["embed_and_head"] / 1e6 - 77.8) < 0.05, n
    assert abs(n["total"] / 1e6 - 465.4) < 0.5 and abs(n["published"] / 1e9 - 30.6) < 0.05, n
    job = {"lora_rank": 8, "lora_targets": r".*attn/w[qkvo]/kernel"}
    assert sum(r * (i + o) for i, r, o in adapter_shapes(c, job)) == 557_056
    assert kept_pairs(c, 32768) == 65_012_736 and causal_pairs(32768) == 536_887_296
    assert abs(100 * kept_pairs(c, 32768) / causal_pairs(32768) - 12.109) < 5e-4
    fwd = forward_flops_per_layer(c, 32768)
    for key, tera in (("indexer_scores", 1.10), ("kept_attention", 1.07), ("causal_attention", 8.8),
                      ("projections", 1.24), ("held_experts", 0.31), ("head", 2.55)):
        assert abs(fwd[key] / 1e12 - tera) < 0.006, (key, fwd[key])
    whole = train_flops_per_step(c, job, 1, 32768)
    assert 34e12 < whole < 38e12, whole


if __name__ == "__main__":
    check()
    print("flops_keye ok")
