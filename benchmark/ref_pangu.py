"""Plain float32 reference of an openPangu-Ultra-MoE adapter fine-tuning step,
as ONE expert-parallel rank computes it.

Straightforward ``jax.numpy`` at ``highest`` matmul precision, nothing imported
from the program (the helpers that are not the model's come from
``ref_sala.py``: the seed's key, RMSNorm, half-split RoPE, the float8 control's
rounding, the blockwise ``lax.map``, the batch, the schedule).  For a sequence
``x`` of (tokens, hidden):

block (``sandwich_norm``)  ``x = x + N2(MLA(N1(x)))``, ``x = x + N4(FFN(N3(x)))``,
    RMSNorms with learned scales; the first ``first_k_dense_replace`` layers
    have the dense SwiGLU, the others the expert layer.
MLA (expanded form)  ``c_q = N_q(x W_dq)``, ``[q_n | q_r] = c_q W_uq`` per head;
    ``[c_kv | k_r] = x W_dkv``, ``[k_n | v] = N_kv(c_kv) W_ukv`` per head;
    ``q = [q_n | rope(q_r)]``, ``k = [k_n | rope(k_r)]`` with the one ``k_r``
    for every head; ``softmax_causal(q k^T / sqrt(nope + rope)) v``; ``W_o``.
    No bias, no YaRN factor (the config has no ``rope_scaling``).
expert layer  ``s = sigmoid(x W_r)`` over ALL ``router_experts``; the
    ``num_experts_per_tok`` best (``lax.top_k``: ties to the lower index; no
    gradient through the choice); ``g = routed_scaling_factor * s_I / (sum(s_I)
    + 1e-20)``; ``y = SwiGLU_shared(x) + sum over the HELD experts e in I of
    g_e SwiGLU_e(x)``: a loop over the held experts, each on every token under
    a 0/1 mask.  What the absent experts would add is left out (the
    ``model-configs`` guide, section 4), and that partial result goes on.
MTP  ``h' = [N_e(E[t_{i+1}]) | N_h(h_i)] W_p`` with ``h_i`` the last layer's
    output before the final norm; one expert-layer block; a final norm of its
    own; the shared head; cross-entropy against ``t_{i+2}``, the last position
    left out.  ``loss = mean(L_main) + mtp_weight * mean(L_mtp)``.

The frozen base is drawn in float32 from the seed and rounded to bfloat16
(what the configuration's ``precision`` states); the reference holds those
bfloat16 values as they are (exact; the float32 copy of the whole base would be
16.6 GB) and reads each kernel in float32 where it is used.  Rank-``r``
adapters enter as ``x W + (alpha / r) (x a) b``; their gradients come from
autodiff; the global-norm clip and AdamW are written out.

Departures from the published model: the cut (``reduced`` in the configuration
file: depth, one leading dense layer, the experts held, the vocabulary's
slice); everything the file lists under ``assumed`` (sigmoid scores with no
group-limited choice and no selection bias, the 1e-20, the sandwich norms'
placement, half-split RoPE, the MTP's concatenation order and shared embedding
and head, the held experts 0-7); random weights and non-zero adapter factors.

To fit 8,192 tokens beside the base on a 16 GB chip each layer is wrapped in
``jax.checkpoint``, attention takes its queries and the dense SwiGLU, the
experts and the head with the loss take their tokens a block at a time.

``control="fp8"`` rounds the operands of every projection and expert product
to float8_e4m3 (the router stays in float32: its precision is not bfloat16's
to begin with).  ``fault="half_batch"`` is ``ref_sala.batch_tokens``'s.
"""

from __future__ import annotations

import math
import re

import jax
import jax.numpy as jnp
import numpy as np

from flops_pangu import blocks as block_prefixes   # (path prefix, has experts) of every block
from ref_sala import (ADAM_EPS, ADAPTER_B_STD, B1, B2, F32, QUERY_BLOCK, TOKEN_BLOCK, _fake_fp8,
                      _in_blocks, _leaf, _rms, _rope, batch_tokens, leaf_norms, lr_at, seed_key)

__all__ = ["batch_tokens", "leaf_norms", "B1"]
HEAD_BLOCK = 16   # heads the reference's attention takes at a time


# ------------------------------------------------------------------ the sizes
def sizes(c: dict) -> dict:
    return {"d": c["hidden_size"], "f": c["intermediate_size"], "fm": c["moe_intermediate_size"],
            "v": c["vocab_size"], "h": c["num_attention_heads"], "ql": c["q_lora_rank"],
            "kvl": c["kv_lora_rank"], "nope": c["qk_nope_head_dim"], "rot": c["qk_rope_head_dim"],
            "dv": c["v_head_dim"], "eps": c["rms_norm_eps"], "theta": float(c["rope_theta"]),
            "layers": c["num_hidden_layers"], "dense": c["first_k_dense_replace"],
            "routed": c["router_experts"], "held": c["n_routed_experts"], "first": c["first_expert"],
            "k": c["num_experts_per_tok"], "shared": c["n_shared_experts"],
            "scale": c["routed_scaling_factor"], "norm": c["norm_topk_prob"],
            "mtp": c["num_nextn_predict_layers"]}


def leaf_shapes(c: dict) -> dict[str, tuple]:
    z = sizes(c)
    d, h = z["d"], z["h"]
    shapes = {"embed/embedding": (z["v"], d), "final_norm/scale": (d,), "lm_head/kernel": (d, z["v"])}
    for p, experts in block_prefixes(c):
        shapes.update({
            p + "attn_norm/scale": (d,), p + "post_attn_norm/scale": (d,),
            p + "mlp_norm/scale": (d,), p + "post_mlp_norm/scale": (d,),
            p + "attn/wq_a/kernel": (d, z["ql"]), p + "attn/q_a_norm/scale": (z["ql"],),
            p + "attn/wq_b/kernel": (z["ql"], h, z["nope"] + z["rot"]),
            p + "attn/wkv_a/kernel": (d, z["kvl"] + z["rot"]), p + "attn/kv_a_norm/scale": (z["kvl"],),
            p + "attn/wkv_b/kernel": (z["kvl"], h, z["nope"] + z["dv"]),
            p + "attn/wo/kernel": (h, z["dv"], d)})
        if experts:
            fs = z["shared"] * z["fm"]
            shapes.update({
                p + "moe/router/kernel": (d, z["routed"]),
                p + "moe/experts/w_gate": (z["held"], d, z["fm"]),
                p + "moe/experts/w_up": (z["held"], d, z["fm"]),
                p + "moe/experts/w_down": (z["held"], z["fm"], d),
                p + "moe/shared/w_gate/kernel": (d, fs), p + "moe/shared/w_up/kernel": (d, fs),
                p + "moe/shared/w_down/kernel": (fs, d)})
        else:
            shapes.update({p + "mlp/w_gate/kernel": (d, z["f"]), p + "mlp/w_up/kernel": (d, z["f"]),
                           p + "mlp/w_down/kernel": (z["f"], d)})
    if z["mtp"]:
        shapes.update({"mtp/enorm/scale": (d,), "mtp/hnorm/scale": (d,), "mtp/final_norm/scale": (d,),
                       "mtp/proj/kernel": (2 * d, d)})
    return shapes


def _fan_in(name: str, shape: tuple) -> int:
    if name.endswith("wo/kernel"):
        return shape[0] * shape[1]          # heads x v_head_dim
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
#: standard deviation of attention's logits under the benchmark's weights
LOGIT_STD = 3.0


def _mean_std(name: str, shape: tuple, c: dict) -> tuple[float, float]:
    """Every product's kernel normal / sqrt(fan_in) (so the router's inputs to
    the sigmoid and the logits have unit entries) and the embedding normal;
    norm scales 1 + 0.1 normal.  Two departures make random weights route as
    a trained, load-balanced model does (with neither, every late token's
    attention output is nearly the same mean of thousands of values, the four
    norms pass that common vector on at full size, each router sees it as a
    fixed offset per expert, and the busiest of 256 experts draws 6 to 18
    times its even share): the two norms AFTER a block's branches are scaled
    by 1 / sqrt(published depth) (Pangu Ultra's depth-scaled sandwich-norm
    initialisation), and ``W_uq`` is ``LOGIT_STD`` times larger, so that
    attention's logits have that deviation and a query reads a few keys, as a
    trained model's does, and not the average of all."""
    depth = c["published"]["num_hidden_layers"]
    if name.endswith("scale"):
        post = "post_attn_norm" in name or "post_mlp_norm" in name
        return (1.0 / math.sqrt(depth), 0.1 / math.sqrt(depth)) if post else (1.0, 0.1)
    if name == "embed/embedding":
        return 0.0, 1.0
    std = 1.0 / math.sqrt(_fan_in(name, shape))
    return 0.0, std * LOGIT_STD if name.endswith("attn/wq_b/kernel") else std


def init_weights(c: dict, seed: int, shardings: dict | None = None, dtype=jnp.bfloat16) -> dict:
    """The frozen base from the seed (``_mean_std``), rounded to ``dtype``."""
    shapes, key = leaf_shapes(c), seed_key(seed)
    draw = jax.jit(_leaf, static_argnums=(1, 2, 3, 4))
    out = {}
    for i, name in enumerate(sorted(shapes)):
        leaf = draw(jax.random.fold_in(key, i), shapes[name], *_mean_std(name, shapes[name], c), dtype)
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
    """(chosen experts (s, k), their gates (s, k))."""
    s = jax.nn.sigmoid(x @ w_r)
    vals, idx = jax.lax.top_k(jax.lax.stop_gradient(s), z["k"])      # ties: the lower index first
    vals = jnp.take_along_axis(s, idx, axis=-1)
    gates = vals / (jnp.sum(vals, -1, keepdims=True) + 1e-20) if z["norm"] else vals
    return idx, z["scale"] * gates


def held_part(x, idx, gates, w_gate, w_up, w_down, first: int, q8=lambda t: t):
    """What experts ``first ..`` add: each on every token, under a 0/1 mask."""
    y = jnp.zeros_like(x)
    for e in range(w_gate.shape[0]):
        g = jnp.sum(jnp.where(idx == first + e, gates, 0.0), -1)
        h = jax.nn.silu(q8(x) @ q8(w_gate[e].astype(F32))) * (q8(x) @ q8(w_up[e].astype(F32)))
        y = y + g[:, None] * (q8(h) @ q8(w_down[e].astype(F32)))
    return y


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

    def mla(x, p):
        """Latent attention, ``HEAD_BLOCK`` heads at a time (their share of the
        two up-projections, their scores over full rows of keys, their share
        of ``W_o``), so that 128 heads' float32 q, k, v never exist at once."""
        s, h, nope, rot, dv, kvl = x.shape[0], z["h"], z["nope"], z["rot"], z["dv"], z["kvl"]
        hb = next(b for b in range(min(HEAD_BLOCK, h), 0, -1) if h % b == 0)
        pos = jnp.arange(s)
        c_q = norm(proj(x, p + "wq_a/kernel"), p + "q_a_norm/scale")
        kv_a = proj(x, p + "wkv_a/kernel")
        c_kv = norm(kv_a[:, :kvl], p + "kv_a_norm/scale")
        k_r = _rope(kv_a[:, None, kvl:], z["theta"])                     # one rotary key for all heads

        def by_heads(name, heads_axis):
            """Kernel ``name`` and its adapter factors with the head blocks
            leading: ``{"w": (h / hb, ..), "a": .., "b": ..}``."""
            def lead(t, axis):
                t = t.reshape(t.shape[:axis] + (h // hb, hb) + t.shape[axis + 1:])
                return jnp.moveaxis(t, axis, 0)
            kern = w[name].astype(F32)
            out = {"w": lead(kern, heads_axis)}
            if name + "/a" in lora:
                a_, b_ = lora[name + "/a"], lora[name + "/b"]
                if heads_axis == 0:     # wo: (heads, dv, d); a is (heads x dv, r)
                    out.update(a=lead(a_.reshape(h, dv, -1), 0), b=jnp.broadcast_to(b_, (h // hb,) + b_.shape))
                else:                   # (latent, heads, width); b is (r, heads x width)
                    out.update(a=jnp.broadcast_to(a_, (h // hb,) + a_.shape),
                               b=lead(b_.reshape(b_.shape[0], h, -1), 1))
            return out

        def times(x, k):
            flat_x = x.reshape(s, -1)
            y = q8(flat_x) @ q8(k["w"].reshape(flat_x.shape[1], -1))
            if "a" in k:
                y = y + scale * (q8(flat_x) @ q8(k["a"].reshape(flat_x.shape[1], -1))) @ q8(
                    k["b"].reshape(k["b"].shape[0], -1))
            return y

        def head_block(k):
            q = times(c_q, k["q"]).reshape(s, hb, nope + rot)
            kv = times(c_kv, k["kv"]).reshape(s, hb, nope + dv)
            q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], z["theta"])], -1)
            keys = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_r, (s, hb, rot))], -1)

            def rows(qb, tb):
                logits = jnp.einsum("nhd,thd->nht", qb, keys) / math.sqrt(nope + rot)
                probs = jax.nn.softmax(jnp.where((pos[None, :] <= tb[:, None])[:, None, :], logits, -jnp.inf), -1)
                return jnp.einsum("nht,thd->nhd", probs, kv[..., nope:])

            return times(_in_blocks(rows, QUERY_BLOCK, q, pos), k["o"])

        kernels = {"q": by_heads(p + "wq_b/kernel", 1), "kv": by_heads(p + "wkv_b/kernel", 1),
                   "o": by_heads(p + "wo/kernel", 0)}
        if h == hb:
            return head_block(jax.tree_util.tree_map(lambda t: t[0], kernels))
        return jax.lax.scan(lambda y, k: (y + jax.checkpoint(head_block)(k), None),
                            jnp.zeros((s, z["d"]), F32), kernels)[0]

    def moe(x, p):
        """(the layer's result, each token's assignments on held experts)."""
        idx, gates = route(x, w[p + "router/kernel"].astype(F32), z)
        kernels = [w[p + "experts/" + n] for n in ("w_gate", "w_up", "w_down")]
        y = swiglu(x, p + "shared/") + held_part(x, idx, gates, *kernels, z["first"], q8)
        on_held = (idx >= z["first"]) & (idx < z["first"] + z["held"])
        return y, jnp.sum(on_held, -1, dtype=F32)

    def block(h, p, experts):
        """(the block's output, its assignments on held experts)."""
        h = h + norm(mla(norm(h, p + "attn_norm/scale"), p + "attn/"), p + "post_attn_norm/scale")
        x = norm(h, p + "mlp_norm/scale")
        if experts:
            y, held = _in_blocks(lambda xb: moe(xb, p + "moe/"), TOKEN_BLOCK, x)
            held = jnp.sum(held)
        else:
            y, held = _in_blocks(lambda xb: swiglu(xb, p + "mlp/"), TOKEN_BLOCK, x), jnp.float32(0)
        return h + norm(y, p + "post_mlp_norm/scale"), held

    def head_losses(x, y):
        def one(xb, yb):
            logits = proj(xb, "lm_head/kernel")
            logp = logits - jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
            return -jnp.take_along_axis(logp, yb[:, None], axis=-1)[:, 0]
        return _in_blocks(one, TOKEN_BLOCK, x, y)

    return {"proj": proj, "norm": norm, "swiglu": swiglu, "mla": mla, "moe": moe, "block": block,
            "head_losses": head_losses}


def row_loss(w: dict, lora: dict, tokens, targets, c: dict, a: dict, control=None):
    """One sequence: (mean next-token loss + mtp_weight x mean MTP loss, (the
    mean MTP loss, assignments on held experts per block (float32)))."""
    z, m = sizes(c), parts(w, lora, c, a, control)
    norm, block, head_losses = m["norm"], m["block"], m["head_losses"]
    s = tokens.shape[0]
    blocks = block_prefixes(c)
    h = w["embed/embedding"][tokens].astype(F32)
    held = []
    for p, experts in blocks[: z["layers"]]:
        h, n = jax.checkpoint(block, static_argnums=(1, 2))(h, p, experts)
        held.append(n)
    loss = jnp.mean(head_losses(norm(h, "final_norm/scale"), targets))
    mtp_loss = jnp.float32(0)
    if z["mtp"]:
        both = jnp.concatenate([norm(w["embed/embedding"][targets].astype(F32), "mtp/enorm/scale"),
                                norm(h, "mtp/hnorm/scale")], -1)
        h2, n = jax.checkpoint(block, static_argnums=(1, 2))(m["proj"](both, "mtp/proj/kernel"), *blocks[-1])
        held.append(n)
        # position i reads the token after next; the last position has none
        after_next = head_losses(norm(h2, "mtp/final_norm/scale"), jnp.roll(targets, -1))
        mtp_loss = jnp.sum(after_next[: s - 1]) / (s - 1)
    return loss + a["mtp_weight"] * mtp_loss, (mtp_loss, jnp.stack(held))


def change_norms(c: dict, a: dict, seed: int, lora: dict) -> dict[str, float]:
    """Norm per adapter leaf of ``lora`` minus the adapters the seed gives."""
    first = init_adapters(c, a, seed)
    return leaf_norms({k: lora[k] - first[k] for k in sorted(lora)})


class ReferenceTrainer:
    """Follows the trainer's first steps in float32 and records, per step,
    the loss and the clipped gradient's norm per adapter leaf, and at the end
    the norm of each adapter leaf's change (``ref_sala.ReferenceTrainer``'s
    AdamW, written out)."""

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
        acc, loss, mtp_loss, held = None, 0.0, 0.0, 0.0
        with jax.default_matmul_precision("highest"):
            for r in range(rows):
                (l, (m, n)), g = self._grad(self.lora, self.w, jnp.asarray(tokens[r]),
                                            jnp.asarray(targets[r]))
                loss, mtp_loss, held = loss + float(l), mtp_loss + float(m), held + np.asarray(n)
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
        return {"loss": loss / rows, "mtp_loss": mtp_loss / rows,
                "grad_norms": {k: v * clip for k, v in raw.items()}, "grad_global_norm": gnorm}

    def change_norms(self) -> dict[str, float]:
        return change_norms(self.c, self.a, self.seed, self.lora)
