"""Plain float32 reference of a Keye-VL-2.0 (language model) adapter
fine-tuning step, as ONE expert-parallel rank computes it.

Straightforward ``jax.numpy`` at ``highest`` matmul precision, nothing imported
from the program (the helpers that are not the model's come from
``ref_sala.py``: the seed's key, RMSNorm, half-split RoPE, the float8 control's
rounding, the blockwise ``lax.map``, the batch, the schedule; the held
experts' loop from ``ref_pangu.py``).  For a sequence ``x`` of (tokens,
hidden), every layer alike:

layer  ``x = x + DSA(N1(x))``, ``x = x + MoE(N2(x))``, RMSNorms with learned
    scales; a final RMSNorm, then the untied head.
DSA  ``q = rope(N_q(x W_q))`` (32 heads of 128), ``k = rope(N_k(x W_k))``,
    ``v = x W_v`` (4 heads); the indexer on the same ``x``, without a
    gradient: ``q^I = rope(x W_qI)`` (16 heads of 64), ``k^I =
    rope(LayerNorm(x W_kI))`` (one of 64), ``w = x W_w / sqrt(16 x 64)``,
    ``I(t, s) = sum_j w_j ReLU(q^I_j . k^I_s)``; query ``t`` keeps the
    ``topk`` keys ``s <= t`` of largest ``I``, written out per query by
    ``lax.top_k`` (ties to the lower index; -0.0 read as +0.0), every key
    where fewer are visible; each head's softmax over the kept keys' scores
    ``q . k / sqrt(128)``; ``W_o``.
MoE  ``p = softmax(x W_r)`` over ALL ``router_experts`` (Qwen3-MoE's router);
    the ``num_experts_per_tok`` best (``lax.top_k``; no gradient through the
    choice), gates ``p_I / sum(p_I)``; ``y = sum over the HELD experts e in I
    of g_e SwiGLU_e(x)``, each held expert on every token under a 0/1 mask.
    What the absent experts would add is left out (the ``model-configs``
    guide, section 4), and that partial result goes on.

The frozen base is drawn in float32 from the seed and rounded to bfloat16
(what the configuration's ``precision`` states); the reference holds those
bfloat16 values and reads each kernel in float32 where it is used.
Rank-``r`` adapters enter as ``x W + (alpha / r) (x a) b``; their gradients
come from autodiff; the global-norm clip and AdamW are written out.

Departures from the published model: the cut (``reduced`` in the
configuration file: depth, the experts held, the vocabulary's slice);
everything the file lists under ``assumed``; random weights and non-zero
adapter factors.

To fit 32,768 tokens beside the base on a 16 GB chip each layer is wrapped in
``jax.checkpoint``, attention takes its queries ``QUERY_BLOCK`` at a time
(their index scores over every key, their choice, their rows of scores), and
the experts and the head with the loss take their tokens a block at a time.
A layer's choice is packed a bit a key (key ``32 w + j`` of a query at bit
``j`` of its word ``w``), as the program packs its own.

``control="fp8"`` rounds the operands of every projection (the indexer's
too) and expert product to float8_e4m3 (the router, the index scores and
attention's scores stay in float32).  ``fault="half_batch"`` is
``ref_sala.batch_tokens``'s.
"""

from __future__ import annotations

import math
import re

import jax
import jax.numpy as jnp
import numpy as np

from ref_pangu import held_part
from ref_sala import (ADAM_EPS, ADAPTER_B_STD, B1, B2, F32, TOKEN_BLOCK, _fake_fp8, _in_blocks, _leaf,
                      _rms, _rope, batch_tokens, leaf_norms, lr_at, seed_key)

__all__ = ["batch_tokens", "leaf_norms", "B1"]
QUERY_BLOCK = 128   # queries the attention takes at a time
WORD = 32           # keys a word of a packed choice holds


# ------------------------------------------------------------------ the sizes
def sizes(c: dict) -> dict:
    sa = c["sa_config"]
    if sa["indexer_num_kv_heads"] != 1 or c["attention_bias"] or c["mlp_only_layers"] \
            or c["decoder_sparse_step"] != 1 or c["use_sliding_window"] or c["tie_word_embeddings"]:
        raise ValueError("the reference has one indexer key, no attention bias, an expert layer in every "
                         "layer, no sliding window and an untied head")
    return {"d": c["hidden_size"], "fm": c["moe_intermediate_size"], "v": c["vocab_size"],
            "h": c["num_attention_heads"], "kv": c["num_key_value_heads"], "hd": c["head_dim"],
            "eps": c["rms_norm_eps"], "theta": float(c["rope_theta"]), "layers": c["num_hidden_layers"],
            "routed": c["router_experts"], "held": c["num_experts"], "first": c["first_expert"],
            "k": c["num_experts_per_tok"], "norm": c["norm_topk_prob"],
            "ih": sa["indexer_num_heads"], "ihd": sa["indexer_head_dim"], "topk": sa["topk"]}


def leaf_shapes(c: dict) -> dict[str, tuple]:
    z = sizes(c)
    d, h, kv, hd, ih, ihd = z["d"], z["h"], z["kv"], z["hd"], z["ih"], z["ihd"]
    shapes = {"embed/embedding": (z["v"], d), "final_norm/scale": (d,), "lm_head/kernel": (d, z["v"])}
    for i in range(z["layers"]):
        p = f"layer_{i}/"
        shapes.update({
            p + "attn_norm/scale": (d,), p + "mlp_norm/scale": (d,),
            p + "attn/wq/kernel": (d, h, hd), p + "attn/wk/kernel": (d, kv, hd),
            p + "attn/wv/kernel": (d, kv, hd), p + "attn/wo/kernel": (h, hd, d),
            p + "attn/q_norm/scale": (hd,), p + "attn/k_norm/scale": (hd,),
            p + "attn/indexer/wq/kernel": (d, ih, ihd), p + "attn/indexer/wk/kernel": (d, ihd),
            p + "attn/indexer/weights/kernel": (d, ih),
            p + "attn/indexer/k_norm/scale": (ihd,), p + "attn/indexer/k_norm/bias": (ihd,),
            p + "moe/router/kernel": (d, z["routed"]),
            p + "moe/experts/w_gate": (z["held"], d, z["fm"]), p + "moe/experts/w_up": (z["held"], d, z["fm"]),
            p + "moe/experts/w_down": (z["held"], z["fm"], d)})
    return shapes


def _fan_in(name: str, shape: tuple) -> int:
    if name.endswith("wo/kernel"):
        return shape[0] * shape[1]          # heads x head_dim
    if "/experts/" in name:
        return shape[1]                     # (held, in, out)
    return shape[0]


def adapter_shapes(c: dict, a: dict) -> dict[str, tuple]:
    """``{"<kernel path>/a": (fan_in, r), ".../b": (r, fan_out)}`` of the
    kernels the job's ``lora_targets`` name; the indexer's never."""
    out = {}
    for name, shape in leaf_shapes(c).items():
        if re.fullmatch(a["lora_targets"], name) and "/indexer/" not in name:
            fan_in = _fan_in(name, shape)
            out[name + "/a"] = (fan_in, a["lora_rank"])
            out[name + "/b"] = (a["lora_rank"], int(np.prod(shape)) // fan_in)
    return out


# ---------------------------------------------------------------- the weights
def _mean_std(name: str, shape: tuple) -> tuple[float, float]:
    """Every product's kernel normal / sqrt(fan_in), the embedding normal,
    norm scales 1 + 0.1 normal, the indexer's LayerNorm bias 0.1 normal."""
    if name.endswith("scale"):
        return 1.0, 0.1
    if name.endswith("bias"):
        return 0.0, 0.1
    if name == "embed/embedding":
        return 0.0, 1.0
    return 0.0, 1.0 / math.sqrt(_fan_in(name, shape))


def init_weights(c: dict, seed: int, shardings: dict | None = None, dtype=jnp.bfloat16) -> dict:
    """The frozen base from the seed (``_mean_std``), rounded to ``dtype``."""
    shapes, key = leaf_shapes(c), seed_key(seed)
    draw = jax.jit(_leaf, static_argnums=(1, 2, 3, 4))
    out = {}
    for i, name in enumerate(sorted(shapes)):
        leaf = draw(jax.random.fold_in(key, i), shapes[name], *_mean_std(name, shapes[name]), dtype)
        out[name] = leaf if shardings is None else jax.device_put(leaf, shardings[name])
    return out


def init_adapters(c: dict, a: dict, seed: int) -> dict:
    """Float32 adapters from the seed: ``a`` normal / sqrt(fan_in), ``b``
    normal x 0.05: both non-zero, so both have a gradient at step 1."""
    key = jax.random.fold_in(seed_key(seed), 1 << 20)
    out = {}
    for i, (name, shape) in enumerate(sorted(adapter_shapes(c, a).items())):
        x = jax.random.normal(jax.random.fold_in(key, i), shape, F32)
        out[name] = x / math.sqrt(shape[0]) if name.endswith("/a") else x * ADAPTER_B_STD
    return out


# ------------------------------------------------------------------ the model
def route(x, w_r, z: dict):
    """(chosen experts (s, k), their gates (s, k)): Qwen3-MoE's router."""
    p = jax.nn.softmax(x @ w_r, axis=-1)
    _, idx = jax.lax.top_k(jax.lax.stop_gradient(p), z["k"])      # ties: the lower index first
    vals = jnp.take_along_axis(p, idx, axis=-1)
    return idx, vals / jnp.sum(vals, -1, keepdims=True) if z["norm"] else vals


def choose(score, t, topk: int):
    """One block of queries' choice: score (n, s) float32, t (n,) the
    queries' positions -> (n, s) bool, the ``topk`` keys ``s <= t`` of
    largest score (``lax.top_k``: the lower index first among equals)."""
    n, s = score.shape
    visible = jnp.arange(s)[None, :] <= t[:, None]
    keyed = jnp.where(visible, jnp.where(score == 0, 0.0, score), -jnp.inf)
    _, top = jax.lax.top_k(keyed, min(topk, s))
    chosen = jnp.zeros((n, s), bool).at[jnp.arange(n)[:, None], top].set(True)
    return chosen & visible


def pack(chosen):
    """(n, s) bool -> (n, s / 32) uint32, key ``32 w + j`` at bit ``j`` of word ``w``."""
    words = chosen.reshape(chosen.shape[0], -1, WORD).astype(jnp.uint32)
    return jnp.sum(words << jnp.arange(WORD, dtype=jnp.uint32), -1, dtype=jnp.uint32)


def _layer_norm(x, scale, bias, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(jnp.mean(jnp.square(x - mu), -1, keepdims=True) + eps) * scale + bias


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

    def indexer(x, p):
        """(queries (s, ih, ihd), the one key (s, ihd), weights (s, ih)), no gradient."""
        x = jax.lax.stop_gradient(x)
        qi = _rope(proj(x, p + "indexer/wq/kernel"), z["theta"])
        ki = _layer_norm(proj(x, p + "indexer/wk/kernel"), w[p + "indexer/k_norm/scale"].astype(F32),
                         w[p + "indexer/k_norm/bias"].astype(F32), z["eps"])
        ki = _rope(ki[:, None], z["theta"])[:, 0]
        wi = proj(x, p + "indexer/weights/kernel") / math.sqrt(z["ih"] * z["ihd"])
        return jax.lax.stop_gradient((qi, ki, wi))

    def dsa(x, p):
        """(the mixer's output (s, d), its choice packed (s, s / 32), keys chosen)."""
        s, h, kv, hd = x.shape[0], z["h"], z["kv"], z["hd"]
        pos = jnp.arange(s)
        q = _rope(norm(proj(x, p + "wq/kernel"), p + "q_norm/scale"), z["theta"])
        k = _rope(norm(proj(x, p + "wk/kernel"), p + "k_norm/scale"), z["theta"])
        v = proj(x, p + "wv/kernel")
        qi, ki, wi = indexer(x, p)

        def rows(qb, qib, wib, tb):
            score = jnp.sum(jax.nn.relu(jnp.einsum("nhd,td->nht", qib, ki)) * wib[..., None], axis=1)
            chosen = choose(score, tb, z["topk"])
            logits = jnp.einsum("nkgd,tkd->nkgt", qb, k) / math.sqrt(hd)
            probs = jax.nn.softmax(jnp.where(chosen[:, None, None, :], logits, -jnp.inf), axis=-1)
            return jnp.einsum("nkgt,tkd->nkgd", probs, v), pack(chosen), jnp.sum(chosen, -1, dtype=F32)

        out, bits, kept = _in_blocks(rows, QUERY_BLOCK, q.reshape(s, kv, h // kv, hd), qi, wi, pos)
        return proj(out.reshape(s, h, hd), p + "wo/kernel", n_in=2), bits, jnp.sum(kept)

    def moe(x, p):
        """(the layer's result, each token's assignments on held experts)."""
        idx, gates = route(x, w[p + "router/kernel"].astype(F32), z)
        kernels = [w[p + "experts/" + n] for n in ("w_gate", "w_up", "w_down")]
        y = held_part(x, idx, gates, *kernels, z["first"], q8)
        on_held = (idx >= z["first"]) & (idx < z["first"] + z["held"])
        return y, jnp.sum(on_held, -1, dtype=F32)

    def layer(h, p):
        """(the layer's output, keys chosen, assignments on held experts)."""
        o, _, kept = dsa(norm(h, p + "attn_norm/scale"), p + "attn/")
        h = h + o
        y, held = _in_blocks(lambda xb: moe(xb, p + "moe/"), TOKEN_BLOCK, norm(h, p + "mlp_norm/scale"))
        return h + y, kept, jnp.sum(held)

    def head_losses(x, y):
        def one(xb, yb):
            logits = proj(xb, "lm_head/kernel")
            logp = logits - jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
            return -jnp.take_along_axis(logp, yb[:, None], axis=-1)[:, 0]
        return _in_blocks(one, TOKEN_BLOCK, x, y)

    return {"proj": proj, "norm": norm, "indexer": indexer, "dsa": dsa, "moe": moe, "layer": layer,
            "head_losses": head_losses}


def row_loss(w: dict, lora: dict, tokens, targets, c: dict, a: dict, control=None):
    """One sequence: (mean next-token loss, (keys chosen per layer,
    assignments on held experts per layer))."""
    z, m = sizes(c), parts(w, lora, c, a, control)
    h = w["embed/embedding"][tokens].astype(F32)
    kept, held = [], []
    for i in range(z["layers"]):
        h, k, n = jax.checkpoint(m["layer"], static_argnums=(1,))(h, f"layer_{i}/")
        kept.append(k)
        held.append(n)
    loss = jnp.mean(m["head_losses"](m["norm"](h, "final_norm/scale"), targets))
    return loss, (jnp.stack(kept), jnp.stack(held))


def change_norms(c: dict, a: dict, seed: int, lora: dict) -> dict[str, float]:
    """Norm per adapter leaf of ``lora`` minus the adapters the seed gives."""
    first = init_adapters(c, a, seed)
    return leaf_norms({k: lora[k] - first[k] for k in sorted(lora)})


class ReferenceTrainer:
    """Follows the trainer's first steps in float32 and records, per step,
    the loss and the clipped gradient's norm per adapter leaf, and at the end
    the norm of each adapter leaf's change (``ref_sala.ReferenceTrainer``'s
    AdamW, written out).  ``kept`` and ``held`` hold the last step's keys
    chosen and assignments on held experts, per layer."""

    def __init__(self, c: dict, a: dict, seed: int, control: str | None = None):
        self.c, self.a, self.seed = c, a, seed
        self.w = init_weights(c, seed)
        self.lora = init_adapters(c, a, seed)
        self.mu = {k: jnp.zeros_like(v) for k, v in self.lora.items()}
        self.nu = {k: jnp.zeros_like(v) for k, v in self.lora.items()}
        self.step_idx = 0
        self.kept = self.held = None
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
        acc, loss, kept, held = None, 0.0, 0.0, 0.0
        with jax.default_matmul_precision("highest"):
            for r in range(rows):
                (l, (k, n)), g = self._grad(self.lora, self.w, jnp.asarray(tokens[r]),
                                                  jnp.asarray(targets[r]))
                loss, kept, held = loss + float(l), kept + np.asarray(k), held + np.asarray(n)
                acc = g if acc is None else jax.tree_util.tree_map(jnp.add, acc, g)
        self.kept, self.held = [float(x) for x in kept], [float(x) for x in held]
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
