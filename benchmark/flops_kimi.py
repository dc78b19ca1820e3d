"""Operations and bytes that a Kimi-Linear adapter fine-tuning step REQUIRES on
one expert-parallel rank, from shapes (the companion of ``flops.py`` for
configuration ``kimi_linear_48b_a3b_d5_ep4``).

The base is frozen, so a projection requires its forward product and the
gradient to its input (4 FLOPs a parameter and token), never the gradient of
its kernel; in layer 0, which reads the norm of a frozen embedding, nothing
below the KDA mixer's q, k and v and its gates needs a gradient: those
products are forward alone (its ``wo`` and its dense SwiGLU pass gradients
on to the adapters of its q, k, v).  KDA's gated delta rule is counted in its
chunked form at ``CHUNK`` tokens (``kda_work``: per chunk and head the two
decayed pair products ``A`` and ``M`` over the causal half, the state read by
``K`` and ``Q``, the triangular solve, ``M U`` and the state's update),
forward and the two gradients (x 3); its bytes are q, k, v, g and beta read
and o written, and in the backward pass those and ``do`` read and their
gradients written, once.  Latent attention is counted by the causal half of
its scores (192 wide) and values (128 wide), forward and the two gradients
of each (x 3).  The held experts are counted at their expectation under even
routing (``tokens x top_k x held / router_experts`` rows a layer), the router
over all its outputs and the shared expert over every token.  The short
convolutions (4 taps, depthwise) are no products and are left out.  The
adapters' own products are counted in full.  Recomputed (remat) work is
never counted.  Bytes are the least an algorithm moves: each operand and
result once, bf16 (the decay and beta float32).
"""

from __future__ import annotations

import re

from flops import BF16, _matmul

#: tokens of a chunk of the chunked form that ``kda_work`` counts
#: (flash-linear-attention's KDA chunk)
CHUNK = 64
F32 = 4


def layers(c: dict) -> list[tuple[str, str, bool]]:
    """(path prefix, mixer, has experts) of every layer: the mixers from
    ``linear_attn_config`` (1-based layer numbers), the expert layers from
    ``first_k_dense_replace`` on."""
    lac = c["linear_attn_config"]
    out = []
    for i in range(c["num_hidden_layers"]):
        kind = "kda" if i + 1 in lac["kda_layers"] else "mla" if i + 1 in lac["full_attn_layers"] else None
        if kind is None:
            raise ValueError(f"layer {i + 1} is in neither kda_layers nor full_attn_layers")
        out.append((f"layer_{i}/", kind, i >= c["first_k_dense_replace"]))
    return out


def kda_sizes(c: dict) -> tuple[int, int, int]:
    """(hidden, heads, head width) of a KDA mixer (keys and values alike)."""
    lac = c["linear_attn_config"]
    return c["hidden_size"], lac["num_heads"], lac["head_dim"]


def kda_projections(c: dict) -> dict[str, tuple[int, int]]:
    """(fan_in, fan_out) of the nine products of one KDA mixer."""
    d, h, hd = kda_sizes(c)
    return {"attn/wq": (d, h * hd), "attn/wk": (d, h * hd), "attn/wv": (d, h * hd), "attn/wo": (h * hd, d),
            "attn/wf_a": (d, hd), "attn/wf_b": (hd, h * hd), "attn/wbeta": (d, h),
            "attn/wg_a": (d, hd), "attn/wg_b": (hd, h * hd)}


def mla_projections(c: dict) -> dict[str, tuple[int, int]]:
    """(fan_in, fan_out) of the four projections of one latent-attention
    mixer with a direct query (``q_lora_rank`` null)."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return {"attn/wq": (d, h * qk), "attn/wkv_a": (d, c["kv_lora_rank"] + c["qk_rope_head_dim"]),
            "attn/wkv_b": (c["kv_lora_rank"], h * (c["qk_nope_head_dim"] + c["v_head_dim"])),
            "attn/wo": (h * c["v_head_dim"], d)}


def swiglu(d: int, f: int) -> list[tuple[int, int]]:
    return [(d, f), (d, f), (f, d)]


def param_counts(c: dict) -> dict:
    """Matmul parameters of each part and of each kind of layer as this rank
    holds it, and the base's total here (norm scales, convolutions, decays
    and biases included)."""
    d, v, fm = c["hidden_size"], c["vocab_size"], c["moe_intermediate_size"]
    _, h, hd = kda_sizes(c)
    kda = sum(i * o for i, o in kda_projections(c).values())
    mla = sum(i * o for i, o in mla_projections(c).values())
    expert = sum(i * o for i, o in swiglu(d, fm))
    dense = sum(i * o for i, o in swiglu(d, c["intermediate_size"]))
    router = d * c["router_experts"]
    ffn = {False: dense, True: c["num_shared_experts"] * expert + router + c["num_experts"] * expert}
    mixer = {"kda": kda, "mla": mla}
    small = {"kda": 3 * c["linear_attn_config"]["short_conv_kernel_size"] * h * hd + h + 2 * h * hd + hd,
             "mla": c["kv_lora_rank"]}
    matmul = sum(mixer[kind] + ffn[experts] for _, kind, experts in layers(c)) + d * v
    rest = sum(small[kind] + 2 * d + (c["router_experts"] if experts else 0) for _, kind, experts in layers(c))
    return {"kda": kda, "mla": mla, "expert": expert, "dense_swiglu": dense, "router": router,
            "dense_kda_layer": kda + dense, "kda_expert_layer": kda + ffn[True],
            "mla_expert_layer": mla + ffn[True], "held_experts": c["num_experts"] * expert,
            "embed_and_head": 2 * v * d, "matmul": matmul, "total": matmul + v * d + rest + d}


def adapter_shapes(c: dict, job: dict) -> list[tuple[int, int, int]]:
    """(fan_in, rank, fan_out) of every adapter of the job."""
    out = []
    for prefix, kind, _ in layers(c):
        products = kda_projections(c) if kind == "kda" else mla_projections(c)
        out += [(fan_in, job["lora_rank"], fan_out) for name, (fan_in, fan_out) in products.items()
                if re.fullmatch(job["lora_targets"], prefix + name + "/kernel")]
    return out


def held_rows(c: dict, tokens: int) -> float:
    """Rows the held experts of one layer see under even routing."""
    return tokens * c["num_experts_per_token"] * c["num_experts"] / c["router_experts"]


def kda_work(c: dict, batch: int, seq_len: int, chunk: int = CHUNK) -> tuple[float, float]:
    """(FLOPs, least bytes) of one layer's gated delta rule in chunks of
    ``chunk``, forward and backward (the tail's padding not counted)."""
    _, h, hd = kda_sizes(c)
    n_chunks = seq_len / chunk
    # A and M over the causal half (C^2 / 2 pairs x 2 FLOPs x d each), the
    # state read by K and by Q and its update (2 C d^2 each), the solve and
    # M U (C^2 / 2 x 2 x d each)
    per_chunk = 2 * chunk * chunk * hd + 3 * 2 * chunk * hd * hd + 2 * chunk * chunk * hd
    flops = 3.0 * batch * h * n_chunks * per_chunk
    inputs = BF16 * 3 * hd + F32 * hd + F32      # q, k, v; g; beta
    per_token = (inputs + BF16 * hd) + (inputs + BF16 * hd + inputs)   # forward; backward with do and the gradients
    return flops, float(batch * seq_len * h * per_token)


def mla_attention_work(c: dict, batch: int, seq_len: int) -> tuple[float, float]:
    """(FLOPs, least bytes) of one latent-attention layer's causal scores and
    values, forward and backward: q, k, v and the output's gradient read, the
    output and three gradients written, each once."""
    h, dv = c["num_attention_heads"], c["v_head_dim"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    pairs = batch * h * seq_len * (seq_len + 1) / 2
    return 3.0 * 2.0 * (qk + dv) * pairs, float(BF16 * batch * seq_len * h * (4 * qk + 4 * dv))


def moe_products(c: dict, tokens: int, rows: float) -> list[tuple[float, float]]:
    """(FLOPs, least bytes) of every product of one expert layer, forward and
    the gradient to its input: the router, the shared expert over every
    token, the held experts over ``rows`` rows in all (spread evenly: each
    expert's kernels are read once a product)."""
    d, fm, held = c["hidden_size"], c["moe_intermediate_size"], c["num_experts"]
    out = [_matmul(tokens, d, c["router_experts"]), _matmul(tokens, c["router_experts"], d)]
    for fan_in, fan_out in swiglu(d, c["num_shared_experts"] * fm):
        out += [_matmul(tokens, fan_in, fan_out), _matmul(tokens, fan_out, fan_in)]
    for _ in range(held):
        for fan_in, fan_out in swiglu(d, fm):
            out += [_matmul(rows / held, fan_in, fan_out), _matmul(rows / held, fan_out, fan_in)]
    return out


#: layer 0's products that need no gradient to their input
FORWARD_ALONE = ("attn/wq", "attn/wk", "attn/wv", "attn/wf_a", "attn/wf_b", "attn/wbeta", "attn/wg_a",
                 "attn/wg_b")


def step_matmuls(c: dict, job: dict, batch: int, seq_len: int,
                 attention: bool = True) -> list[tuple[float, float]]:
    """(FLOPs, least bytes) of every product one step requires; without
    ``attention`` each latent-attention layer's ``mla_attention_work`` is
    left out."""
    t = batch * seq_len
    d, v = c["hidden_size"], c["vocab_size"]
    out: list[tuple[float, float]] = []
    for prefix, kind, experts in layers(c):
        products = kda_projections(c) if kind == "kda" else mla_projections(c)
        for name, (fan_in, fan_out) in products.items():
            out.append(_matmul(t, fan_in, fan_out))
            if not (prefix == "layer_0/" and name in FORWARD_ALONE):
                out.append(_matmul(t, fan_out, fan_in))
        if kind == "kda":
            out.append(kda_work(c, batch, seq_len))
        elif attention:
            out.append(mla_attention_work(c, batch, seq_len))
        if experts:
            out += moe_products(c, t, held_rows(c, t))
        else:
            for fan_in, fan_out in swiglu(d, c["intermediate_size"]):
                out += [_matmul(t, fan_in, fan_out), _matmul(t, fan_out, fan_in)]
    out += [_matmul(t, d, v), _matmul(t, v, d)]
    for fan_in, r, fan_out in adapter_shapes(c, job):
        out.extend([_matmul(t, fan_in, r), _matmul(t, r, fan_out)] * 3)
    return out


def train_flops_per_step(c: dict, job: dict, batch: int, seq_len: int) -> float:
    return sum(f for f, _ in step_matmuls(c, job, batch, seq_len))


def check() -> None:
    """The counts the configuration was cut with, and the parts the cell was
    sized with."""
    import json
    import os

    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "configs",
                           "kimi_linear_48b_a3b_d5_ep4.json")) as fh:
        c = json.load(fh)
    assert [(kind, experts) for _, kind, experts in layers(c)] == [
        ("kda", False), ("kda", True), ("kda", True), ("mla", True), ("kda", True)]
    n = param_counts(c)
    for key, millions in (("kda", 39.46), ("mla", 29.12), ("expert", 7.08), ("dense_swiglu", 63.70),
                          ("router", 0.59), ("dense_kda_layer", 103.16), ("kda_expert_layer", 500.11),
                          ("mla_expert_layer", 489.77), ("held_experts", 452.98), ("embed_and_head", 188.74)):
        assert abs(n[key] / 1e6 - millions) < 0.006, (key, n[key])
    assert abs(n["total"] / 1e6 - 2282.0) < 0.5 and abs(2 * n["total"] / 1e9 - 4.56) < 0.005, n
    job = {"lora_rank": 8, "lora_targets": r".*attn/w(q|k|v|o|kv_a|kv_b)/kernel"}
    adapters = sum(r * (i + o) for i, r, o in adapter_shapes(c, job))
    assert adapters == 4 * 8 * 4 * (2304 + 4096) + 8 * (2304 + 6144 + 2304 + 576 + 512 + 8192 + 4096 + 2304)
    assert adapters == 1_030_656
    assert held_rows(c, 16384) == 32768.0
    f, b = kda_work(c, 1, 16384)
    assert abs(f / 1e9 - 206.2) < 0.05 and abs(b / 1e9 - 2.288) < 0.001, (f, b)
    assert abs(mla_attention_work(c, 1, 16384)[0] / 3 / 1e12 - 2.75) < 0.005
    whole = train_flops_per_step(c, job, 1, 16384)
    assert 35e12 < whole < 38e12, whole


if __name__ == "__main__":
    check()
    print("flops_kimi ok")
