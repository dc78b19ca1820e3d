"""Operations and bytes that an openPangu-Ultra-MoE adapter fine-tuning step
REQUIRES on one expert-parallel rank, from shapes (the companion of
``flops.py`` for configuration ``pangu_ultra_moe_d5_ep32``).

The base is frozen, so a projection requires its forward product and the
gradient to its input (4 FLOPs a parameter and token), never the gradient of
its kernel; layer 0's two down-projections (``wq_a``, ``wkv_a``) read a frozen
embedding's norm and the MTP projection's embedding half likewise: forward
alone.  The adapters' own products are counted in full.  Latent attention is
counted by the causal half of its scores (192 wide) and values (128 wide),
forward and the two gradients of each (x 3, as ``flops.py`` counts attention;
the scores a flash backward recomputes are not required work).  The held
experts are counted at their EXPECTATION under even routing: ``tokens x top_k x
held / router_experts`` rows a layer (256 of 8,192 x 8 / 256 an expert), each
through one expert's three kernels forward and back; the router over all its
outputs and the shared experts over every token.  Both head passes (the main
one and the MTP module's) are counted.  Recomputed (remat) work is never
counted.  Bytes are the least an algorithm moves: each operand and result
once, bf16; a held expert's kernels once a pass.
"""

from __future__ import annotations

import re

from flops import BF16, _matmul


def blocks(c: dict) -> list[tuple[str, bool]]:
    """(path prefix, has experts) of every block: the layers, then the MTP
    module's."""
    return ([(f"layer_{i}/", i >= c["first_k_dense_replace"]) for i in range(c["num_hidden_layers"])]
            + [("mtp/block/", True)] * c["num_nextn_predict_layers"])


def mla_projections(c: dict) -> dict[str, tuple[int, int]]:
    """(fan_in, fan_out) of the five projections of one latent-attention mixer."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return {"attn/wq_a": (d, c["q_lora_rank"]), "attn/wq_b": (c["q_lora_rank"], h * qk),
            "attn/wkv_a": (d, c["kv_lora_rank"] + c["qk_rope_head_dim"]),
            "attn/wkv_b": (c["kv_lora_rank"], h * (c["qk_nope_head_dim"] + c["v_head_dim"])),
            "attn/wo": (h * c["v_head_dim"], d)}


def swiglu(d: int, f: int) -> list[tuple[int, int]]:
    return [(d, f), (d, f), (f, d)]


def param_counts(c: dict) -> dict:
    """Matmul parameters of each part, of each kind of block, and the base's
    total (norm scales included) as this rank holds it."""
    d, v, fm = c["hidden_size"], c["vocab_size"], c["moe_intermediate_size"]
    mla = sum(i * o for i, o in mla_projections(c).values())
    expert = sum(i * o for i, o in swiglu(d, fm))
    dense = sum(i * o for i, o in swiglu(d, c["intermediate_size"]))
    router = d * c["router_experts"]
    expert_layer = mla + c["n_shared_experts"] * expert + router + c["n_routed_experts"] * expert
    mtp = (expert_layer + 2 * d * d) * c["num_nextn_predict_layers"]
    n_dense = c["first_k_dense_replace"]
    n_expert = c["num_hidden_layers"] - n_dense
    block_norms = 4 * d + c["q_lora_rank"] + c["kv_lora_rank"]
    norms = (len(blocks(c)) * block_norms + d + 3 * d * c["num_nextn_predict_layers"])
    matmul = n_dense * (mla + dense) + n_expert * expert_layer + mtp + d * v
    return {"mla": mla, "expert": expert, "dense_swiglu": dense, "router": router,
            "dense_layer": mla + dense, "expert_layer": expert_layer, "mtp": mtp,
            "embed_and_head": 2 * v * d, "matmul": matmul, "total": matmul + v * d + norms}


def adapter_shapes(c: dict, job: dict) -> list[tuple[int, int, int]]:
    """(fan_in, rank, fan_out) of every adapter of the job."""
    return [(fan_in, job["lora_rank"], fan_out)
            for prefix, _ in blocks(c) for name, (fan_in, fan_out) in mla_projections(c).items()
            if re.fullmatch(job["lora_targets"], prefix + name + "/kernel")]


def held_rows(c: dict, tokens: int) -> float:
    """Rows the held experts of one layer see under even routing."""
    return tokens * c["num_experts_per_tok"] * c["n_routed_experts"] / c["router_experts"]


def attention_work(c: dict, batch: int, seq_len: int) -> tuple[float, float]:
    """(FLOPs, least bytes) of one layer's causal scores and values, forward
    and backward: q, k, v and the output's gradient read, the output and three
    gradients written, each once."""
    h, dv = c["num_attention_heads"], c["v_head_dim"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    pairs = batch * h * seq_len * (seq_len + 1) / 2
    return 3.0 * 2.0 * (qk + dv) * pairs, float(BF16 * batch * seq_len * h * (4 * qk + 4 * dv))


def mla_work(c: dict, batch: int, seq_len: int) -> tuple[float, float]:
    """One mixer whole: its five projections forward and the gradient to
    their inputs, and ``attention_work``; bytes by product."""
    t = batch * seq_len
    work = [attention_work(c, batch, seq_len)]
    for fan_in, fan_out in mla_projections(c).values():
        work += [_matmul(t, fan_in, fan_out), _matmul(t, fan_out, fan_in)]
    return sum(f for f, _ in work), sum(b for _, b in work)


def moe_products(c: dict, tokens: int, rows: float) -> list[tuple[float, float]]:
    """(FLOPs, least bytes) of every product of one expert layer, forward and
    the gradient to its input: the router, the shared experts over every
    token, the held experts over ``rows`` rows in all (spread evenly: each
    expert's kernels are read once a product)."""
    d, fm, held = c["hidden_size"], c["moe_intermediate_size"], c["n_routed_experts"]
    out = [_matmul(tokens, d, c["router_experts"]), _matmul(tokens, c["router_experts"], d)]
    for fan_in, fan_out in swiglu(d, c["n_shared_experts"] * fm):
        out += [_matmul(tokens, fan_in, fan_out), _matmul(tokens, fan_out, fan_in)]
    for _ in range(held):
        for fan_in, fan_out in swiglu(d, fm):
            out += [_matmul(rows / held, fan_in, fan_out), _matmul(rows / held, fan_out, fan_in)]
    return out


def step_matmuls(c: dict, job: dict, batch: int, seq_len: int,
                 attention: bool = True) -> list[tuple[float, float]]:
    """(FLOPs, least bytes) of every product one step requires; without
    ``attention`` each block's ``attention_work`` is left out."""
    t = batch * seq_len
    d, v = c["hidden_size"], c["vocab_size"]
    out: list[tuple[float, float]] = []
    for prefix, experts in blocks(c):
        for name, (fan_in, fan_out) in mla_projections(c).items():
            out.append(_matmul(t, fan_in, fan_out))
            if not (prefix == "layer_0/" and name in ("attn/wq_a", "attn/wkv_a")):
                out.append(_matmul(t, fan_out, fan_in))
        if attention:
            out.append(attention_work(c, batch, seq_len))
        if experts:
            out += moe_products(c, t, held_rows(c, t))
        else:
            for fan_in, fan_out in swiglu(d, c["intermediate_size"]):
                out += [_matmul(t, fan_in, fan_out), _matmul(t, fan_out, fan_in)]
    for _ in range(c["num_nextn_predict_layers"]):
        # [N_e(E[t+1]) | N_h(h)] W_p: forward over both halves, the gradient to h's half alone
        out += [_matmul(t, 2 * d, d), _matmul(t, d, d)]
    for _ in range(1 + c["num_nextn_predict_layers"]):     # the head, once a loss
        out += [_matmul(t, d, v), _matmul(t, v, d)]
    for fan_in, r, fan_out in adapter_shapes(c, job):
        out.extend([_matmul(t, fan_in, r), _matmul(t, r, fan_out)] * 3)
    return out


def train_flops_per_step(c: dict, job: dict, batch: int, seq_len: int) -> float:
    return sum(f for f, _ in step_matmuls(c, job, batch, seq_len))


def forward_flops_per_token(c: dict, seq_len: int) -> dict:
    """The forward pass by part, FLOPs a token (what ISSUE 33 sized the cell
    with)."""
    n = param_counts(c)
    nb = len(blocks(c))
    n_expert = sum(e for _, e in blocks(c))
    return {"mla_projections": 2.0 * nb * n["mla"],
            "scores_and_values": nb * attention_work(c, 1, seq_len)[0] / 3.0 / seq_len,
            "dense_swiglu": 2.0 * c["first_k_dense_replace"] * n["dense_swiglu"],
            "heads": 2.0 * (1 + c["num_nextn_predict_layers"]) * c["hidden_size"] * c["vocab_size"],
            "shared_experts": 2.0 * n_expert * c["n_shared_experts"] * n["expert"],
            "router": 2.0 * n_expert * n["router"],
            "mtp_projection": 2.0 * c["num_nextn_predict_layers"] * 2 * c["hidden_size"] ** 2,
            "held_experts": 2.0 * n_expert * n["expert"] * held_rows(c, 1)}


def check() -> None:
    """The counts ISSUE 33 cut the configuration with, and the forward's
    parts it sized the cell with."""
    import json
    import os

    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "configs",
                           "pangu_ultra_moe_d5_ep32.json")) as fh:
        c = json.load(fh)
    n = param_counts(c)
    for key, millions in (("mla", 196.58), ("expert", 47.19), ("dense_swiglu", 424.67),
                          ("router", 1.97), ("dense_layer", 621.25), ("expert_layer", 623.25),
                          ("mtp", 741.21), ("embed_and_head", 294.91), ("total", 4150.4)):
        assert abs(n[key] / 1e6 - millions) < 0.06, (key, n[key])
    job = {"lora_rank": 8, "lora_targets": r".*attn/w(q_a|q_b|kv_a|kv_b|o)/kernel"}
    adapters = sum(r * (i + o) for i, r, o in adapter_shapes(c, job))
    assert adapters == 6 * 8 * (7680 + 1536 + 1536 + 24576 + 7680 + 576 + 512 + 32768 + 16384 + 7680), adapters
    assert abs(adapters / 1e6 - 4.84) < 0.01, adapters
    assert held_rows(c, 8192) == 2048.0
    fwd = forward_flops_per_token(c, 8192)
    for key, giga in (("mla_projections", 2.36), ("scores_and_values", 2.01), ("dense_swiglu", 0.85),
                      ("heads", 0.59), ("shared_experts", 0.47), ("mtp_projection", 0.24),
                      ("held_experts", 0.12)):
        assert abs(fwd[key] / 1e9 - giga) < 0.006, (key, fwd[key])
    assert abs(sum(fwd.values()) / 1e9 - 6.66) < 0.02, fwd
    whole = train_flops_per_step(c, job, 1, 8192)
    # twice the forward's products (their input gradients) less the three that
    # need none, three times its scores and values, the adapters in full
    assert 120e12 < whole < 128e12, whole
    f, _ = mla_work(c, 1, 8192)
    assert abs(f - (4.0 * n["mla"] * 8192 + attention_work(c, 1, 8192)[0])) / f < 1e-12


if __name__ == "__main__":
    check()
    print("flops_pangu ok")
