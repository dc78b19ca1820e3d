"""Plain float32 reference of the Mistral/Llama-shaped decoder's training step.

Straightforward ``jax.numpy`` at ``highest`` matmul precision: embedding,
RMSNorm, rotary embeddings (half-split convention), grouped-query causal
softmax attention, SwiGLU, untied output head, mean next-token cross entropy,
gradients by autodiff, global-norm clipping and AdamW under a linear-warmup
cosine schedule, each written out.  No remat, no bf16, no sharding, no flax,
no optax, nothing imported from the program.

Departures from the published Mistral-7B-v0.1: depth is cut (see the
configuration file's ``reduced``), weights are random from the seed, and the
4096-token sliding window is not applied because it does not bind at the
2048-token sequences the cells train on.

To fit beside nothing else on a 16 GB chip the step runs one batch row at a
time, summing gradients, and keeps Adam's moments on the host between steps.

``control="fp8"`` is the same arithmetic with the operands of every
projection rounded to float8_e4m3 (per-tensor absmax scale, straight-through
gradient): the precision step below the configuration's bfloat16.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

B1, B2, ADAM_EPS = 0.9, 0.95, 1e-8
E4M3_MAX = 448.0


def leaf_shapes(c: dict) -> dict[str, tuple]:
    d, f, v = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    h, kv, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    shapes = {"embed/embedding": (v, d)}
    for i in range(c["num_hidden_layers"]):
        p = f"layer_{i}/"
        shapes.update({
            p + "attn_norm/scale": (d,), p + "attn/wq/kernel": (d, h, hd),
            p + "attn/wk/kernel": (d, kv, hd), p + "attn/wv/kernel": (d, kv, hd),
            p + "attn/wo/kernel": (h, hd, d), p + "mlp_norm/scale": (d,),
            p + "mlp/w_gate/kernel": (d, f), p + "mlp/w_up/kernel": (d, f),
            p + "mlp/w_down/kernel": (f, d),
        })
    shapes.update({"final_norm/scale": (d,), "lm_head/kernel": (d, v)})
    return shapes


def seed_key(seed: int):
    """A key from any whole number: the low 31 bits seed it, the rest fold
    in.  The ``rbg`` generator: the chip draws 698M normals in well under a
    second, where threefry takes twelve."""
    return jax.random.fold_in(jax.random.key(seed % (2 ** 31), impl="rbg"), seed // (2 ** 31))


def _leaf(key, name: str, shape: tuple):
    """One leaf: normal with standard deviation 1/sqrt(fan_in) for
    projections, 1 for the embedding, and norm scales jittered about 1 so
    that no two leaves behave alike."""
    if name.endswith("scale"):
        return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    if name.startswith("embed"):
        return jax.random.normal(key, shape, jnp.float32)
    fan_in = shape[0] * shape[1] if name.endswith("wo/kernel") else shape[0]
    return jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)


def _leaf_keys(c: dict, seed: int) -> dict:
    key = seed_key(seed)
    return {name: jax.random.fold_in(key, i) for i, name in enumerate(sorted(leaf_shapes(c)))}


def init_weights(c: dict, seed: int, shardings: dict | None = None) -> dict:
    """The benchmark's weights from the seed, float32 (the type the trainer
    keeps its master copy in).  Each leaf is drawn whole on one device and
    then placed as ``shardings`` say, so the values do not depend on the
    placement."""
    shapes, keys = leaf_shapes(c), _leaf_keys(c, seed)
    draw = jax.jit(_leaf, static_argnums=(1, 2))
    out = {}
    for name in sorted(shapes):
        leaf = draw(keys[name], name.split("/", 1)[-1] if name.startswith("layer_") else name,
                    shapes[name])
        out[name] = leaf if shardings is None else jax.device_put(leaf, shardings[name])
    return out


def change_norms(c: dict, seed: int, w: dict) -> dict[str, float]:
    """Norm per leaf of ``w`` minus the weights the seed gives, leaf by leaf."""
    w0 = init_weights(c, seed, {k: v.sharding for k, v in w.items()})
    norm = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))), donate_argnums=(1,))
    return {k: float(norm(w[k], w0.pop(k))) for k in sorted(w)}


def batch_tokens(seed: int, step: int, batch: int, seq_len: int, vocab: int):
    """Step ``step``'s batch: distinct random rows; targets are the tokens
    shifted by one (the last target wraps, as ``jnp.roll`` does)."""
    g = np.random.default_rng([seed, step])
    tokens = g.integers(0, vocab, size=(batch, seq_len), dtype=np.int32)
    return tokens, np.roll(tokens, -1, axis=1)


def lr_at(step: int, a: dict) -> float:
    """Linear warm-up from 0 over ``warmup_steps``, then cosine to 0 at
    ``max(total_steps, warmup_steps + 1)``."""
    w, total = a["warmup_steps"], max(a["total_steps"], a["warmup_steps"] + 1)
    if step < w:
        return a["learning_rate"] * step / w
    frac = min(step - w, total - w) / (total - w)
    return a["learning_rate"] * 0.5 * (1.0 + math.cos(math.pi * frac))


def _fake_fp8(x):
    s = jnp.max(jnp.abs(x)) / E4M3_MAX + 1e-30
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    s, hd = x.shape[0], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * freqs
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def row_logits(w: dict, tokens, c: dict, control: str | None = None):
    """Logits (s, vocab) of one sequence."""
    q8 = _fake_fp8 if control == "fp8" else (lambda t: t)
    h, kv, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    s = tokens.shape[0]
    x = w["embed/embedding"][tokens]
    causal = jnp.tril(jnp.ones((s, s), bool))
    for i in range(c["num_hidden_layers"]):
        p = f"layer_{i}/"
        y = q8(_rms(x, w[p + "attn_norm/scale"], eps))
        q = _rope(jnp.einsum("sd,dhk->shk", y, q8(w[p + "attn/wq/kernel"])), theta)
        k = _rope(jnp.einsum("sd,dhk->shk", y, q8(w[p + "attn/wk/kernel"])), theta)
        v = jnp.einsum("sd,dhk->shk", y, q8(w[p + "attn/wv/kernel"]))
        k, v = jnp.repeat(k, h // kv, axis=1), jnp.repeat(v, h // kv, axis=1)
        scores = jnp.einsum("shk,thk->hst", q, k) / math.sqrt(hd)
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        o = jnp.einsum("hst,thk->shk", probs, v)
        x = x + jnp.einsum("shk,hkd->sd", q8(o), q8(w[p + "attn/wo/kernel"]))
        y = q8(_rms(x, w[p + "mlp_norm/scale"], eps))
        g = y @ q8(w[p + "mlp/w_gate/kernel"])
        u = y @ q8(w[p + "mlp/w_up/kernel"])
        x = x + q8(jax.nn.silu(g) * u) @ q8(w[p + "mlp/w_down/kernel"])
    return q8(_rms(x, w["final_norm/scale"], eps)) @ q8(w["lm_head/kernel"])


def row_loss_sum(w, tokens, targets, c, control=None):
    logits = row_logits(w, tokens, c, control)
    logp = logits - jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
    return -jnp.sum(jnp.take_along_axis(logp, targets[:, None], axis=-1))


def leaf_norms(tree: dict) -> dict[str, float]:
    sq = jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
                            for k, v in t.items()})(tree)
    return {k: float(v) for k, v in sq.items()}


class ReferenceTrainer:
    """Follows the trainer's first steps in float32 and records, per step,
    the loss, the clipped gradient's norm per leaf, and at the end the norm
    of each leaf's change."""

    def __init__(self, c: dict, a: dict, seed: int, control: str | None = None,
                 shardings: dict | None = None, fault: str | None = None):
        self.c, self.a, self.seed, self.fault = c, a, seed, fault
        self.w = init_weights(c, seed, shardings)
        self.mu_host: dict = {}  # Adam's moments live on the host between steps
        self.nu_host: dict = {}
        self.step_idx = 0
        with jax.default_matmul_precision("highest"):
            self._grad = jax.jit(jax.value_and_grad(
                lambda w, t, y: row_loss_sum(w, t, y, c, control)))
        self._add = jax.jit(lambda acc, g: jax.tree_util.tree_map(jnp.add, acc, g),
                            donate_argnums=(0,))

        def adam(p, g, mu, nu, clip, lr, t):
            g = g * clip
            mu = B1 * mu + (1 - B1) * g
            nu = B2 * nu + (1 - B2) * g * g
            u = (mu / (1 - B1 ** t)) / (jnp.sqrt(nu / (1 - B2 ** t)) + ADAM_EPS)
            return p - lr * (u + a["weight_decay"] * p), mu, nu

        self._adam = jax.jit(adam, donate_argnums=(0, 2, 3))

    def step(self, tokens: np.ndarray, targets: np.ndarray, last: bool = False) -> dict:
        """One step; ``last`` skips bringing the moments back to the host."""
        if self.fault == "half_batch":  # half of the batch left out, the mean over the rest
            tokens, targets = tokens[: tokens.shape[0] // 2], targets[: tokens.shape[0] // 2]
        n_tok = tokens.size
        acc, loss = None, 0.0
        with jax.default_matmul_precision("highest"):
            for r in range(tokens.shape[0]):
                l, g = self._grad(self.w, jnp.asarray(tokens[r]), jnp.asarray(targets[r]))
                loss += float(l)
                acc = g if acc is None else self._add(acc, g)
        raw = leaf_norms(acc)
        raw = {k: v / n_tok for k, v in raw.items()}
        gnorm = math.sqrt(sum(v * v for v in raw.values()))
        clip = 1.0 if gnorm < self.a["grad_clip"] else self.a["grad_clip"] / gnorm
        lr, t = lr_at(self.step_idx, self.a), self.step_idx + 1
        for name in sorted(acc):
            g = acc.pop(name)
            if self.step_idx == 0:
                mu, nu = jnp.zeros_like(g), jnp.zeros_like(g)
            else:
                mu = jax.device_put(self.mu_host.pop(name), g.sharding)
                nu = jax.device_put(self.nu_host.pop(name), g.sharding)
            self.w[name], mu, nu = self._adam(self.w[name], g, mu, nu,
                                              jnp.float32(clip / n_tok), jnp.float32(lr),
                                              jnp.float32(t))
            if not last:
                self.mu_host[name], self.nu_host[name] = np.asarray(mu), np.asarray(nu)
        self.step_idx += 1
        return {"loss": loss / n_tok, "grad_norms": {k: v * clip for k, v in raw.items()},
                "grad_global_norm": gnorm}

    def change_norms(self) -> dict[str, float]:
        return change_norms(self.c, self.seed, self.w)
