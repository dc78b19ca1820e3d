"""Plain float32 reference of a Kimi-Linear adapter fine-tuning step, as ONE
expert-parallel rank computes it.

Straightforward ``jax.numpy`` at ``highest`` matmul precision, nothing imported
from the program (the helpers that are not the model's come from
``ref_sala.py``: the seed's key, RMSNorm, the float8 control's rounding, the
blockwise ``lax.map``, the batch, the schedule).  For a sequence ``x`` of (tokens, hidden):

layer  ``x = x + mixer(N1(x))``, ``x = x + FFN(N2(x))``, RMSNorms with learned
    scales; the mixer is KDA or MLA by ``linear_attn_config`` (layer numbers
    from 1); the FFN a dense SwiGLU in the first ``first_k_dense_replace``
    layers, the expert layer after; a final RMSNorm, then the untied head.
KDA  ``q, k, v = silu(conv(x W_q|k|v))`` (depthwise, causal, no bias), q and k
    L2-normed per head; ``g = -exp(A_log) softplus(x W_fa W_fb + dt_bias)``;
    ``beta = sigmoid(x W_b)``; the gated delta rule TOKEN BY TOKEN:
    ``S = Diag(exp g_t) S``, ``S = S + beta_t k_t (v_t - S^T k_t)^T``, ``o_t
    = S^T q_t / sqrt(d_k)`` (state float32); ``W_o(RMSNorm_head(o) *
    sigmoid(x W_ga W_gb + b_g))``.
MLA  ``q = x W_q`` (no low-rank path); ``[c_kv | k_r] = x W_dkv``, ``[k_n | v] =
    N_kv(c_kv) W_ukv`` per head; no RoPE (NoPE); causal softmax of ``[q_n |
    q_r] . [k_n | k_r] / sqrt(192)``; ``W_o``.
expert layer  ``s = sigmoid(x W_r)`` over ALL ``router_experts``; the
    ``num_experts_per_token`` best of ``s + b`` (``lax.top_k``: ties to the
    lower index; no gradient through the choice); ``g = routed_scaling_factor
    * s_I / (sum(s_I) + 1e-20)`` of the unbiased ``s``; ``y = SwiGLU_shared(x)
    + sum over the HELD experts e in I of g_e SwiGLU_e(x)``, each held expert
    on every token under a 0/1 mask, one expert a step of a ``lax.scan``.  What the absent experts would add is
    left out (the ``model-configs`` guide, section 4).

The frozen base is drawn in float32 from the seed and rounded to bfloat16
(what the configuration's ``precision`` states); the reference holds those
bfloat16 values and reads each kernel in float32 where it is used.
Rank-``r`` adapters enter as ``x W + (alpha / r) (x a) b``; their gradients
come from autodiff; the global-norm clip and AdamW are written out.

Departures from the published model: the cut (``reduced`` in the
configuration file: depth, the experts held, the vocabulary's slice);
everything the file lists under ``assumed``; random weights (the residual
stream's kernels scaled down, the selection biases balanced on a calibration
row: ``_mean_std``, ``balanced_biases``) and non-zero adapter factors.

To fit 16,384 tokens beside the base on a 16 GB chip each layer is wrapped in
``jax.checkpoint``, the recurrence runs ``KDA_BLOCK`` tokens to a
checkpointed step, MLA takes its queries ``QUERY_BLOCK`` at a time, and the
SwiGLUs, the experts and the head with the loss take their tokens a block at
a time.

``control="fp8"`` rounds the operands of every projection (the gates' too)
and expert product to float8_e4m3 (the router, the recurrence and attention's
scores stay in float32).  ``fault="half_batch"`` is ``ref_sala.batch_tokens``'s.
"""

from __future__ import annotations

import math
import re

import jax
import jax.numpy as jnp
import numpy as np

from flops_kimi import layers as layer_kinds   # (path prefix, mixer, has experts) of every layer
from ref_sala import (ADAM_EPS, ADAPTER_B_STD, B1, B2, F32, TOKEN_BLOCK, _fake_fp8, _in_blocks, _leaf,
                      _rms, batch_tokens, leaf_norms, lr_at, seed_key)

__all__ = ["batch_tokens", "leaf_norms", "B1"]
QUERY_BLOCK = 128   # queries the latent attention takes at a time
KDA_BLOCK = 256     # tokens of the recurrence a checkpointed step takes
BIAS_STD = 0.005    # of the router's selection bias as drawn, before it is balanced
BALANCE_STEPS = 128         # of DeepSeek-V3's bias update, ...
BALANCE_STEP = 1e-3         # ... each by its gamma
L2_EPS = 1e-6


# ------------------------------------------------------------------ the sizes
def sizes(c: dict) -> dict:
    lac = c["linear_attn_config"]
    if (c["q_lora_rank"] is not None or not c["mla_use_nope"] or c["num_expert_group"] != 1
            or c["topk_group"] != 1 or c["moe_layer_freq"] != 1 or c["num_nextn_predict_layers"]
            or c["tie_word_embeddings"] or c["moe_router_activation_func"] != "sigmoid"
            or c["num_key_value_heads"] != c["num_attention_heads"]):
        raise ValueError("the reference has a direct MLA query without positions, one expert group, an "
                         "expert layer after every leading dense one, sigmoid scores, a key per head, no "
                         "MTP module and an untied head")
    return {"d": c["hidden_size"], "f": c["intermediate_size"], "fm": c["moe_intermediate_size"],
            "v": c["vocab_size"], "h": c["num_attention_heads"], "kvl": c["kv_lora_rank"],
            "nope": c["qk_nope_head_dim"], "rot": c["qk_rope_head_dim"], "dv": c["v_head_dim"],
            "kh": lac["num_heads"], "kd": lac["head_dim"], "taps": lac["short_conv_kernel_size"],
            "eps": c["rms_norm_eps"], "routed": c["router_experts"], "held": c["num_experts"],
            "first": c["first_expert"], "k": c["num_experts_per_token"],
            "shared": c["num_shared_experts"], "scale": c["routed_scaling_factor"],
            "norm": c["moe_renormalize"]}


def leaf_shapes(c: dict) -> dict[str, tuple]:
    z = sizes(c)
    d, h, kh, kd = z["d"], z["h"], z["kh"], z["kd"]
    shapes = {"embed/embedding": (z["v"], d), "final_norm/scale": (d,), "lm_head/kernel": (d, z["v"])}
    for p, kind, experts in layer_kinds(c):
        shapes.update({p + "attn_norm/scale": (d,), p + "mlp_norm/scale": (d,)})
        a = p + "attn/"
        if kind == "kda":
            shapes.update({a + "wq/kernel": (d, kh, kd), a + "wk/kernel": (d, kh, kd),
                           a + "wv/kernel": (d, kh, kd), a + "wo/kernel": (kh, kd, d),
                           a + "conv_q": (z["taps"], kh * kd), a + "conv_k": (z["taps"], kh * kd),
                           a + "conv_v": (z["taps"], kh * kd), a + "A_log": (kh,), a + "dt_bias": (kh * kd,),
                           a + "wf_a/kernel": (d, kd), a + "wf_b/kernel": (kd, kh, kd),
                           a + "wbeta/kernel": (d, kh), a + "wg_a/kernel": (d, kd),
                           a + "wg_b/kernel": (kd, kh, kd), a + "wg_b/bias": (kh, kd),
                           a + "o_norm/scale": (kd,)})
        else:
            shapes.update({a + "wq/kernel": (d, h, z["nope"] + z["rot"]),
                           a + "wkv_a/kernel": (d, z["kvl"] + z["rot"]), a + "kv_a_norm/scale": (z["kvl"],),
                           a + "wkv_b/kernel": (z["kvl"], h, z["nope"] + z["dv"]),
                           a + "wo/kernel": (h, z["dv"], d)})
        if experts:
            fs = z["shared"] * z["fm"]
            shapes.update({
                p + "moe/router/kernel": (d, z["routed"]), p + "moe/router/e_score_correction_bias": (z["routed"],),
                p + "moe/experts/w_gate": (z["held"], d, z["fm"]),
                p + "moe/experts/w_up": (z["held"], d, z["fm"]),
                p + "moe/experts/w_down": (z["held"], z["fm"], d),
                p + "moe/shared/w_gate/kernel": (d, fs), p + "moe/shared/w_up/kernel": (d, fs),
                p + "moe/shared/w_down/kernel": (fs, d)})
        else:
            shapes.update({p + "mlp/w_gate/kernel": (d, z["f"]), p + "mlp/w_up/kernel": (d, z["f"]),
                           p + "mlp/w_down/kernel": (z["f"], d)})
    return shapes


def _fan_in(name: str, shape: tuple) -> int:
    if name.endswith("wo/kernel"):
        return shape[0] * shape[1]          # heads x head width
    if "/experts/" in name:
        return shape[1]                     # (held, in, out)
    return shape[0]


def adapter_shapes(c: dict, a: dict) -> dict[str, tuple]:
    """``{"<kernel path>/a": (fan_in, r), ".../b": (r, fan_out)}`` of the
    kernels the job's ``lora_targets`` name."""
    out = {}
    for name, shape in leaf_shapes(c).items():
        if re.fullmatch(a["lora_targets"], name):
            fan_in = _fan_in(name, shape)
            out[name + "/a"] = (fan_in, a["lora_rank"])
            out[name + "/b"] = (a["lora_rank"], int(np.prod(shape)) // fan_in)
    return out


# ---------------------------------------------------------------- the weights
#: kernels that write into the residual stream (each branch's last product)
RESIDUAL_OUT = re.compile(r".*(attn/wo/kernel|w_down/kernel|experts/w_down)")


def _mean_std(name: str, shape: tuple, c: dict) -> tuple[float, float]:
    """Every product's kernel normal / sqrt(fan_in) (a short convolution's
    fan-in is its taps), the embedding normal, norm scales 1 + 0.1 normal,
    g_b's bias 0.1 normal, the router's selection bias ``BIAS_STD`` normal
    (then balanced: ``balanced_biases``).
    One departure: the kernels that write into the residual stream
    (``RESIDUAL_OUT``) are 1 / sqrt(2 x published depth) smaller (GPT-2's
    scaled initialisation of residual branches).  Without it the mixers'
    outputs, whose silu'd q, k and v give them a direction common to every
    token (KDA's 0.65 of its norm, the flat-scored MLA's 0.96), reach each
    router as a fixed offset per expert that ``balanced_biases`` would have
    to take out alone (PERF.md section 6)."""
    if name.endswith("scale"):
        return 1.0, 0.1
    if name.endswith("e_score_correction_bias"):
        return 0.0, BIAS_STD
    if name.endswith("bias"):
        return 0.0, 0.1
    if name == "embed/embedding":
        return 0.0, 1.0
    std = 1.0 / math.sqrt(_fan_in(name, shape))
    if RESIDUAL_OUT.fullmatch(name):
        std *= residual_scale(c)
    return 0.0, std


def residual_scale(c: dict) -> float:
    """``1 / sqrt(2 x published depth)``: what a kernel (or an adapter's b
    factor) that writes into the residual stream is drawn smaller by."""
    return 1.0 / math.sqrt(2 * c["published"]["num_hidden_layers"])


def _decay_leaf(key, name: str, shape: tuple, dtype):
    """``A_log = log(A)``, A uniform in [1, 16]; ``dt_bias = softplus^-1(dt)``,
    dt log-uniform in [1e-3, 1e-1] (at least 1e-4)."""
    u = jax.random.uniform(key, shape, F32)
    if name.endswith("A_log"):
        return jnp.log(1.0 + 15.0 * u).astype(dtype)
    dt = jnp.maximum(jnp.exp(u * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3)), 1e-4)
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def drawn_weights(c: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """The frozen base as drawn from the seed (``_mean_std``, ``_decay_leaf``),
    rounded to ``dtype``, its selection biases not yet balanced."""
    shapes, key = leaf_shapes(c), seed_key(seed)
    draw = jax.jit(_leaf, static_argnums=(1, 2, 3, 4))
    decay = jax.jit(_decay_leaf, static_argnums=(1, 2, 3))
    out = {}
    for i, name in enumerate(sorted(shapes)):
        k = jax.random.fold_in(key, i)
        if name.endswith(("A_log", "dt_bias")):
            out[name] = decay(k, name, shapes[name], dtype)
        else:
            out[name] = draw(k, shapes[name], *_mean_std(name, shapes[name], c), dtype)
    return out


def init_weights(c: dict, seed: int, shardings: dict | None = None, dtype=jnp.bfloat16) -> dict:
    """The frozen base from the seed: ``drawn_weights``, the routers'
    selection biases then balanced (``balanced_biases``)."""
    out = drawn_weights(c, seed, dtype)
    out.update(balanced_biases(out, c, seed))
    return out if shardings is None else {k: jax.device_put(v, shardings[k]) for k, v in out.items()}


def balanced_biases(w: dict, c: dict, seed: int) -> dict:
    """The selection biases as DeepSeek-V3's auxiliary-loss-free balancing
    leaves them: on a calibration row of the configuration's
    ``calibration_tokens`` from the seed (no training step's; a row as long
    as the cell's, since the mixers' common direction grows with a token's
    position), layer by layer in float32, each router's bias
    as drawn takes ``BALANCE_STEPS`` updates ``b_e -= gamma sign(load_e -
    mean load)`` against the load of the choice it makes, and the layer then
    routes by the balanced bias.  A trained model's bias is where these
    updates settle; random weights leave every token's hidden state a common
    direction (the mixers' silu'd q, k and v), which each router reads as a
    fixed offset per expert, and a bias drawn at random does not take it
    out.  The forward takes the backend's default matmul precision (on a TPU
    one bfloat16 pass, as the program's own products): it only places the
    biases, and full float32 products would cost set-up several times over.
    The biases come back in the dtype they were drawn in."""
    z = sizes(c)
    tok, _ = batch_tokens(seed, 2 ** 31 - 1, 1, c["calibration_tokens"], z["v"])

    def balance(s, b):
        def one(_, b):
            _, idx = jax.lax.top_k(s + b, z["k"])
            load = jnp.zeros(s.shape[1], F32).at[idx.reshape(-1)].add(1.0)
            return b - BALANCE_STEP * jnp.sign(load - jnp.mean(load))
        return jax.lax.fori_loop(0, BALANCE_STEPS, one, b)

    def run(drawn, tok):
        w = dict(drawn)
        m = parts(w, {}, c, {"lora_alpha": 1.0, "lora_rank": 1})
        h, out = w["embed/embedding"][tok].astype(F32), {}
        for p, kind, experts in layer_kinds(c):
            h = h + (m["kda"] if kind == "kda" else m["mla"])(m["norm"](h, p + "attn_norm/scale"), p + "attn/")
            x = m["norm"](h, p + "mlp_norm/scale")
            if experts:
                name = p + "moe/router/e_score_correction_bias"
                w[name] = balance(jax.nn.sigmoid(x @ w[p + "moe/router/kernel"].astype(F32)), w[name].astype(F32))
                out[name] = w[name].astype(drawn[name].dtype)
                h = h + _in_blocks(lambda xb: m["moe"](xb, p + "moe/")[0], TOKEN_BLOCK, x)
            else:
                h = h + _in_blocks(lambda xb: m["swiglu"](xb, p + "mlp/"), TOKEN_BLOCK, x)
        return out

    with jax.default_matmul_precision("default"):   # whatever precision the caller runs under
        return jax.jit(run)(w, jnp.asarray(tok[0]))


def init_adapters(c: dict, a: dict, seed: int) -> dict:
    """Float32 adapters from the seed: ``a`` normal / sqrt(fan_in), ``b``
    normal x 0.05: both non-zero, so both have a gradient at step 1.  The b
    factor of a kernel that writes into the residual stream (``wo``'s) is
    ``residual_scale`` smaller, as its kernel is: at 0.05 its path alone,
    ``x a b`` over the mixer's output, put back the direction common to every
    token that the kernels' scaling keeps small, and the busiest held expert
    drew twice its even share again."""
    key = jax.random.fold_in(seed_key(seed), 1 << 20)
    out = {}
    for i, (name, shape) in enumerate(sorted(adapter_shapes(c, a).items())):
        x = jax.random.normal(jax.random.fold_in(key, i), shape, F32)
        if name.endswith("/a"):
            out[name] = x / math.sqrt(shape[0])
        else:
            out[name] = x * ADAPTER_B_STD * (residual_scale(c) if RESIDUAL_OUT.fullmatch(name[:-2]) else 1.0)
    return out


# ------------------------------------------------------------------ the model
def route(x, w_r, bias, z: dict):
    """(chosen experts (s, k), their gates (s, k)): the best of ``s + bias``,
    gated by ``s``."""
    s = jax.nn.sigmoid(x @ w_r)
    _, idx = jax.lax.top_k(jax.lax.stop_gradient(s + bias), z["k"])   # ties: the lower index first
    vals = jnp.take_along_axis(s, idx, axis=-1)
    gates = vals / (jnp.sum(vals, -1, keepdims=True) + 1e-20) if z["norm"] else vals
    return idx, z["scale"] * gates


def held_part(x, idx, gates, w_gate, w_up, w_down, first: int, q8):
    """What experts ``first ..`` add: each on every token under a 0/1 mask,
    one expert a step of a ``lax.scan`` over the stacked kernels."""
    def one(y, e_w):
        e, wg, wu, wd = e_w
        g = jnp.sum(jnp.where(idx == first + e, gates, 0.0), -1)
        h = jax.nn.silu(q8(x) @ q8(wg.astype(F32))) * (q8(x) @ q8(wu.astype(F32)))
        return y + g[:, None] * (q8(h) @ q8(wd.astype(F32))), None

    held = jnp.arange(w_gate.shape[0])
    return jax.lax.scan(one, jnp.zeros_like(x), (held, w_gate, w_up, w_down))[0]


def delta_rule(q, k, v, g, beta):
    """(s, h, d) float32 each but beta (s, h) -> (s, h, d_v): the recurrence
    token by token, ``KDA_BLOCK`` tokens to a checkpointed step."""
    s, h, dk = q.shape
    scale = dk ** -0.5

    def one_token(state, xs):
        qt, kt, vt, gt, bt = xs
        state = jnp.exp(gt)[:, :, None] * state
        u = bt[:, None] * (vt - jnp.einsum("hk,hkv->hv", kt, state))
        state = state + kt[:, :, None] * u[:, None, :]
        return state, scale * jnp.einsum("hk,hkv->hv", qt, state)

    n = next(b for b in range(min(KDA_BLOCK, s), 0, -1) if s % b == 0)
    block = jax.checkpoint(lambda state, xs: jax.lax.scan(one_token, state, xs))
    _, out = jax.lax.scan(block, jnp.zeros((h, dk, v.shape[-1]), F32),
                          tuple(t.reshape(s // n, n, *t.shape[1:]) for t in (q, k, v, g, beta)))
    return out.reshape(s, h, v.shape[-1])


def parts(w: dict, lora: dict, c: dict, a: dict, control=None) -> dict:
    """The model's parts as functions of one sequence's activations (tokens,
    ...) under base ``w`` and adapters ``lora``; a part's kernels are named by
    its path prefix ``p``."""
    z = sizes(c)
    q8 = _fake_fp8 if control == "fp8" else (lambda t: t)
    scale = a["lora_alpha"] / a["lora_rank"]

    def proj(x, name, n_in=1):
        kern = w[name].astype(F32)
        flat_x = x.reshape(x.shape[0], -1)
        y = q8(flat_x) @ q8(kern.reshape(flat_x.shape[1], -1))
        if name + "/a" in lora:
            y = y + scale * (q8(flat_x) @ q8(lora[name + "/a"])) @ q8(lora[name + "/b"])
        return y.reshape(x.shape[0], *kern.shape[n_in:])

    def norm(x, name):
        return _rms(x, w[name].astype(F32), z["eps"])

    def swiglu(x, p):
        return proj(jax.nn.silu(proj(x, p + "w_gate/kernel")) * proj(x, p + "w_up/kernel"),
                    p + "w_down/kernel")

    def conv(y, name):
        """Depthwise causal: ``out_t = sum_i kern[i] y[t - (taps - 1) + i]``."""
        kern = w[name].astype(F32)
        taps, s = kern.shape[0], y.shape[0]
        padded = jnp.pad(y, ((taps - 1, 0), (0, 0)))
        return sum(kern[i] * padded[i: i + s] for i in range(taps))

    def kda(x, p):
        s, h, dk = x.shape[0], z["kh"], z["kd"]

        def branch(n):
            y = proj(x, p + f"w{n}/kernel").reshape(s, h * dk)
            return jax.nn.silu(conv(y, p + f"conv_{n}")).reshape(s, h, dk)

        l2 = lambda t: t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + L2_EPS)
        q, k, v = l2(branch("q")), l2(branch("k")), branch("v")
        f = proj(proj(x, p + "wf_a/kernel"), p + "wf_b/kernel") + w[p + "dt_bias"].astype(F32).reshape(h, dk)
        g = -jnp.exp(w[p + "A_log"].astype(F32))[:, None] * jax.nn.softplus(f)
        beta = jax.nn.sigmoid(proj(x, p + "wbeta/kernel"))
        o = delta_rule(q, k, v, g, beta)
        gate = jax.nn.sigmoid(proj(proj(x, p + "wg_a/kernel"), p + "wg_b/kernel") + w[p + "wg_b/bias"].astype(F32))
        return proj(norm(o, p + "o_norm/scale") * gate, p + "wo/kernel", n_in=2)

    def mla(x, p):
        s, h, nope, rot, kvl = x.shape[0], z["h"], z["nope"], z["rot"], z["kvl"]
        pos = jnp.arange(s)
        q = proj(x, p + "wq/kernel")                                     # (s, h, nope + rot)
        kv_a = proj(x, p + "wkv_a/kernel")
        kv = proj(norm(kv_a[:, :kvl], p + "kv_a_norm/scale"), p + "wkv_b/kernel")   # (s, h, nope + dv)
        keys = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(kv_a[:, None, kvl:], (s, h, rot))], -1)

        def rows(qb, tb):
            logits = jnp.einsum("nhd,thd->nht", qb, keys) / math.sqrt(nope + rot)
            probs = jax.nn.softmax(jnp.where((pos[None, :] <= tb[:, None])[:, None, :], logits, -jnp.inf), -1)
            return jnp.einsum("nht,thd->nhd", probs, kv[..., nope:])

        return proj(_in_blocks(rows, QUERY_BLOCK, q, pos), p + "wo/kernel", n_in=2)

    def moe(x, p):
        """(the layer's result, each token's assignments on held experts)."""
        idx, gates = route(x, w[p + "router/kernel"].astype(F32),
                           w[p + "router/e_score_correction_bias"].astype(F32), z)
        kernels = [w[p + "experts/" + n] for n in ("w_gate", "w_up", "w_down")]
        y = swiglu(x, p + "shared/") + held_part(x, idx, gates, *kernels, z["first"], q8)
        on_held = (idx >= z["first"]) & (idx < z["first"] + z["held"])
        return y, jnp.sum(on_held, -1, dtype=F32)

    def layer(h, p, kind, experts):
        """(the layer's output, its assignments on held experts)."""
        h = h + (kda if kind == "kda" else mla)(norm(h, p + "attn_norm/scale"), p + "attn/")
        x = norm(h, p + "mlp_norm/scale")
        if experts:
            y, held = _in_blocks(lambda xb: moe(xb, p + "moe/"), TOKEN_BLOCK, x)
            return h + y, jnp.sum(held)
        return h + _in_blocks(lambda xb: swiglu(xb, p + "mlp/"), TOKEN_BLOCK, x), jnp.float32(0)

    def head_losses(x, y):
        def one(xb, yb):
            logits = proj(xb, "lm_head/kernel")
            logp = logits - jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
            return -jnp.take_along_axis(logp, yb[:, None], axis=-1)[:, 0]
        return _in_blocks(one, TOKEN_BLOCK, x, y)

    return {"proj": proj, "norm": norm, "conv": conv, "swiglu": swiglu, "kda": kda, "mla": mla, "moe": moe,
            "layer": layer, "head_losses": head_losses}


def row_loss(w: dict, lora: dict, tokens, targets, c: dict, a: dict, control=None):
    """One sequence: (mean next-token loss, assignments on held experts per
    layer (float32; 0 for a dense one))."""
    m = parts(w, lora, c, a, control)
    h = w["embed/embedding"][tokens].astype(F32)
    held = []
    for p, kind, experts in layer_kinds(c):
        h, n = jax.checkpoint(m["layer"], static_argnums=(1, 2, 3))(h, p, kind, experts)
        held.append(n)
    loss = jnp.mean(m["head_losses"](m["norm"](h, "final_norm/scale"), targets))
    return loss, jnp.stack(held)


def change_norms(c: dict, a: dict, seed: int, lora: dict) -> dict[str, float]:
    """Norm per adapter leaf of ``lora`` minus the adapters the seed gives."""
    first = init_adapters(c, a, seed)
    return leaf_norms({k: lora[k] - first[k] for k in sorted(lora)})


class ReferenceTrainer:
    """Follows the trainer's first steps in float32 and records, per step,
    the loss and the clipped gradient's norm per adapter leaf, and at the end
    the norm of each adapter leaf's change (``ref_sala.ReferenceTrainer``'s
    AdamW, written out).  ``held`` holds the last step's assignments on held
    experts, per layer."""

    def __init__(self, c: dict, a: dict, seed: int, control: str | None = None):
        self.c, self.a, self.seed = c, a, seed
        self.w = init_weights(c, seed)
        self.lora = init_adapters(c, a, seed)
        self.mu = {k: jnp.zeros_like(v) for k, v in self.lora.items()}
        self.nu = {k: jnp.zeros_like(v) for k, v in self.lora.items()}
        self.step_idx = 0
        self.held = None
        with jax.default_matmul_precision("highest"):
            self._grad = jax.jit(jax.value_and_grad(
                lambda lora, w, t, y: row_loss(w, lora, t, y, c, a, control), has_aux=True))

        def adam(p, g, mu, nu, clip, lr, t):
            g = g * clip
            mu = B1 * mu + (1 - B1) * g
            nu = B2 * nu + (1 - B2) * g * g
            u = (mu / (1 - B1 ** t)) / (jnp.sqrt(nu / (1 - B2 ** t)) + ADAM_EPS)
            return p - lr * (u + a["weight_decay"] * p), mu, nu

        self._adam = jax.jit(adam)

    def step(self, tokens: np.ndarray, targets: np.ndarray) -> dict:
        rows = tokens.shape[0]
        acc, loss, held = None, 0.0, 0.0
        with jax.default_matmul_precision("highest"):
            for r in range(rows):
                (l, n), g = self._grad(self.lora, self.w, jnp.asarray(tokens[r]), jnp.asarray(targets[r]))
                loss, held = loss + float(l), held + np.asarray(n)
                acc = g if acc is None else jax.tree_util.tree_map(jnp.add, acc, g)
        self.held = [float(x) for x in held]
        raw = {k: v / rows for k, v in leaf_norms(acc).items()}
        gnorm = math.sqrt(sum(v * v for v in raw.values()))
        clip = 1.0 if gnorm < self.a["grad_clip"] else self.a["grad_clip"] / gnorm
        lr, t = lr_at(self.step_idx, self.a), self.step_idx + 1
        for name in sorted(acc):
            self.lora[name], self.mu[name], self.nu[name] = self._adam(
                self.lora[name], acc[name], self.mu[name], self.nu[name],
                jnp.float32(clip / rows), jnp.float32(lr), jnp.float32(t))
        self.step_idx += 1
        return {"loss": loss / rows, "grad_norms": {k: v * clip for k, v in raw.items()},
                "grad_global_norm": gnorm}

    def change_norms(self) -> dict[str, float]:
        return change_norms(self.c, self.a, self.seed, self.lora)
