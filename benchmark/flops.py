"""Operations and bytes that the benchmark's models REQUIRE, from shapes.

Copied in spirit from ``fedml_tpu/ops/flops.py`` with one departure: causal
attention is counted at the half of the s x s scores that a causal model
needs, whatever the implementation computes, so ``llm.mfu`` reads the same
work under any attention kernel.  Recomputed (remat) operations, padded lanes
and masked steps are never counted.  Bytes are the least an algorithm moves:
each operand and the result once, in bf16.
"""

from __future__ import annotations

BF16 = 2


# ---------------------------------------------------------------- transformer
def transformer_param_counts(c: dict) -> dict:
    """Parameter counts of a Llama/Mistral-shaped decoder from its sizes."""
    d, f, v = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    h, kv, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f + 2 * d
    embed = v * d
    total = c["num_hidden_layers"] * layer + 2 * embed + d
    norms = (2 * c["num_hidden_layers"] + 1) * d
    return {"layer": layer, "embed": embed, "norms": norms, "total": total}


def transformer_train_flops_per_token(c: dict, seq_len: int) -> float:
    """6 x (matmul parameters: no embedding gather, no norm scale) + causal
    attention: per layer and token the forward needs s*d for the scores and
    s*d for the values (half of the full 4*s*d), times 3 for forward and
    backward."""
    n = transformer_param_counts(c)
    attn = 6.0 * c["num_hidden_layers"] * seq_len * c["num_attention_heads"] * c["head_dim"]
    return 6.0 * (n["total"] - n["embed"] - n["norms"]) + attn


def _matmul(m: int, k: int, n: int, flops_scale: float = 1.0) -> tuple[float, float]:
    """(flops, least bytes) of one (m,k)x(k,n) product in bf16."""
    return 2.0 * m * k * n * flops_scale, float(BF16 * (m * k + k * n + m * n))


def transformer_step_matmuls(c: dict, batch: int, seq_len: int,
                             attention: bool = True) -> list[tuple[float, float]]:
    """(flops, least bytes) of every matrix product one training step
    requires: each projection forward, its input gradient and its weight
    gradient; with ``attention``, each layer's ``attention_products``.  The
    embedding is a gather and has none."""
    d, f, v = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    h, kv, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    t = batch * seq_len
    out: list[tuple[float, float]] = []

    def dense(k: int, n: int) -> None:
        out.extend([_matmul(t, k, n), _matmul(t, n, k), _matmul(k, t, n)])

    for _ in range(c["num_hidden_layers"]):
        dense(d, h * hd)
        dense(d, kv * hd)
        dense(d, kv * hd)
        dense(h * hd, d)
        dense(d, f)
        dense(d, f)
        dense(f, d)
        if attention:
            out.extend(attention_products(c, batch, seq_len))
    dense(d, v)
    return out


def attention_products(c: dict, batch: int, seq_len: int) -> list[tuple[float, float]]:
    """One layer's causal attention as separate products, head by head, as
    XLA runs it: scores q k^T and values p v, forward + two gradients each,
    half the square under the causal mask, the scores written between them."""
    hd = c["head_dim"]
    return ([_matmul(seq_len, hd, seq_len, 0.5)] * 3
            + [_matmul(seq_len, seq_len, hd, 0.5)] * 3) * (batch * c["num_attention_heads"])


def attention_work(c: dict, batch: int, seq_len: int) -> tuple[float, float]:
    """(FLOPs, least bytes) of one layer's causal attention as ONE fused
    kernel computes it (``fedml_causal_attention_*``): the same FLOPs as
    ``attention_products``, but no score ever leaves the chip: q, k, v and the
    output's gradient read, the output and three gradients written, each
    once, k and v at their own KV heads."""
    h, kv, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    pairs = batch * h * seq_len * (seq_len + 1) / 2
    return 3.0 * 2.0 * (hd + hd) * pairs, float(BF16 * batch * seq_len * hd * (4 * h + 4 * kv))


def roofline_seconds(work: list[tuple[float, float]], peak_flops: float,
                     peak_bytes_per_s: float) -> float:
    """Least time the chip could take: per product the larger of operations
    over peak FLOP/s and least bytes over peak bytes/s, summed."""
    return sum(max(fl / peak_flops, by / peak_bytes_per_s) for fl, by in work)


# ------------------------------------------------------------------ ResNet-20
def resnet20_cifar_convs(channels=(16, 32, 64), blocks: int = 3, image: int = 32,
                         classes: int = 10) -> list[dict]:
    """Every convolution (and the classifier) of the CIFAR ResNet-20 of He et
    al.: 3x3 stem, three stages of ``blocks`` two-conv basic blocks, stride 2
    entering stages 2 and 3, parameter-free option-A shortcuts (subsample and
    zero-pad), so a shortcut adds no product."""
    layers = [dict(k=3, cin=3, cout=channels[0], hw_out=image, hw_in=image)]
    cin, hw = channels[0], image
    for s, cout in enumerate(channels):
        for b in range(blocks):
            stride = 2 if (s > 0 and b == 0) else 1
            hw_out = hw // stride
            layers.append(dict(k=3, cin=cin, cout=cout, hw_out=hw_out, hw_in=hw))
            layers.append(dict(k=3, cin=cout, cout=cout, hw_out=hw_out, hw_in=hw_out))
            cin, hw = cout, hw_out
    layers.append(dict(k=1, cin=cin, cout=classes, hw_out=1, hw_in=1))
    return layers


def resnet20_forward_macs_per_sample(**kw) -> float:
    return float(sum(l["k"] ** 2 * l["cin"] * l["cout"] * l["hw_out"] ** 2
                     for l in resnet20_cifar_convs(**kw)))


def resnet20_train_flops_per_sample(**kw) -> float:
    """Forward 2 x MACs, times 3 for forward and backward."""
    return 6.0 * resnet20_forward_macs_per_sample(**kw)


def resnet20_step_convs(batch: int, **kw) -> list[tuple[float, float]]:
    """(flops, least bytes) of the forward, input-gradient and
    weight-gradient product of every convolution for one batch."""
    out = []
    for l in resnet20_cifar_convs(**kw):
        fl = 2.0 * batch * l["k"] ** 2 * l["cin"] * l["cout"] * l["hw_out"] ** 2
        by = float(BF16 * (batch * l["hw_in"] ** 2 * l["cin"]
                           + l["k"] ** 2 * l["cin"] * l["cout"]
                           + batch * l["hw_out"] ** 2 * l["cout"]))
        out.extend([(fl, by)] * 3)
    return out
