"""Plain float32 reference of a MiniCPM-SALA adapter fine-tuning step.

Straightforward ``jax.numpy`` at ``highest`` matmul precision, nothing imported
from the program: the muP-scaled trunk (``h0 = scale_emb E[token]``, each
residual branch times ``scale_depth / sqrt(mup_denominator)``, the final hidden
over ``hidden_size / dim_model_base``), RMSNorm, SwiGLU, and the two mixers of
``openbmb/MiniCPM-SALA``:

``lightning-attn``  QK-norm (RMSNorm over each head, learned scale), RoPE
    (half-split), per head the decayed state ``S_t = lam S_{t-1} + k_t^T v_t``,
    ``o_t = q_t S_t / sqrt(d)``, then ``W_o(RMSNorm(o) * sigmoid(W_g x))`` with
    the norm over all heads.  ``lightning_recurrent`` is that recurrence token
    by token; a 16k sequence's backward pass through it would keep 34 GB of
    states, so the step uses ``lightning_chunked`` (the same sums regrouped in
    chunks of 128, float32), which ``tests/test_sala.py`` holds equal to it.
``minicpm4``  InfLLM-v2: QK-norm, no RoPE, the block selection written out per
    query (compressed keys, the softmax over those that end at or before the
    query summed over the group's heads, a block's score the largest of the
    compressed keys that overlap it by an explicit overlap table; block 0, the
    window's blocks and the ``topk`` best of the others by a stable sort), a
    full row of masked scores per query and head, then ``W_o(o * sigmoid(W_g x))``.
    At most ``dense_len`` tokens: plain causal attention.

The frozen base is the program's own: drawn in float32 from the seed, rounded
to bfloat16 (what the configuration's ``precision`` states) and kept so; the
reference reads those values in float32.  Rank-``r`` adapters on the targets
enter as ``x W + (alpha / r) (x a) b``; their gradients come from autodiff, the
global-norm clip and AdamW (linear warm-up, cosine) are written out.

Departures from the published model: depth 4 of 32 with one period of the
mixer list (``reduced`` in the configuration file); the InfLLM-v2 constants
(kernel 32, stride 16, block 64, top-k 64, 1 initial block, window 2048,
``dense_len`` 8192, from MiniCPM4-8B's ``sparse_config``), the decay slopes
``2^(-8 (h + 1) / 32)`` with no layer factor and the output norm over all heads
are assumed, not read from the model's own files; weights and adapters are
random from the seed, both adapter factors non-zero.

To fit a 16k sequence beside the base on a 16 GB chip each layer is wrapped in
``jax.checkpoint``, the sparse layer takes its queries and the SwiGLU and the
head with the loss take their tokens a block at a time.

``control="fp8"`` rounds the operands of every projection to float8_e4m3
(per-tensor absmax scale, straight-through gradient): the precision step below
the configuration's bfloat16.  ``fault="half_batch"`` leaves half of each
batch out (of a single row: its second half, the first half standing in its
place, so the shape stays).
"""

from __future__ import annotations

import math
import re

import jax
import jax.numpy as jnp
import numpy as np

B1, B2, ADAM_EPS = 0.9, 0.95, 1e-8
E4M3_MAX = 448.0
ADAPTER_B_STD = 0.05
REF_LIGHTNING_CHUNK = 128
QUERY_BLOCK = 256    # queries the sparse layer takes at a time
TOKEN_BLOCK = 2048   # tokens the SwiGLU and the head take at a time
F32 = jnp.float32


# ------------------------------------------------------------------ the sizes
def sizes(c: dict) -> dict:
    s = c["sparse_config"]
    return {"d": c["hidden_size"], "f": c["intermediate_size"], "v": c["vocab_size"],
            "h": c["num_attention_heads"], "kv": c["num_key_value_heads"], "hd": c["head_dim"],
            "lh": c["lightning_nh"], "lhd": c["lightning_head_dim"], "eps": c["rms_norm_eps"],
            "theta": float(c["rope_theta"]), "layers": c["num_hidden_layers"],
            "mixers": list(c["mixer_types"])[: c["num_hidden_layers"]],
            "r": c["scale_depth"] / math.sqrt(c["mup_denominator"]),
            "scale_emb": float(c["scale_emb"]),
            "logit_div": c["hidden_size"] / c["dim_model_base"], **s}


def leaf_shapes(c: dict) -> dict[str, tuple]:
    z = sizes(c)
    d, f, v = z["d"], z["f"], z["v"]
    shapes = {"embed/embedding": (v, d)}
    for i, kind in enumerate(z["mixers"]):
        p = f"layer_{i}/"
        if kind == "minicpm4":
            h, kv, hd = z["h"], z["kv"], z["hd"]
        elif kind == "lightning-attn":
            h, kv, hd = z["lh"], z["lh"], z["lhd"]
            shapes[p + "attn/o_norm/scale"] = (h * hd,)
        else:
            raise ValueError(f"no reference for mixer {kind!r}")
        shapes.update({
            p + "attn_norm/scale": (d,), p + "attn/wq/kernel": (d, h, hd),
            p + "attn/wk/kernel": (d, kv, hd), p + "attn/wv/kernel": (d, kv, hd),
            p + "attn/wg/kernel": (d, h, hd), p + "attn/wo/kernel": (h, hd, d),
            p + "attn/q_norm/scale": (hd,), p + "attn/k_norm/scale": (hd,),
            p + "mlp_norm/scale": (d,), p + "mlp/w_gate/kernel": (d, f),
            p + "mlp/w_up/kernel": (d, f), p + "mlp/w_down/kernel": (f, d),
        })
    shapes.update({"final_norm/scale": (d,), "lm_head/kernel": (d, v)})
    return shapes


def adapter_shapes(c: dict, a: dict) -> dict[str, tuple]:
    """``{"<kernel path>/a": (fan_in, r), ".../b": (r, fan_out)}`` of the
    kernels the job's ``lora_targets`` name.  ``wo`` contracts heads x head_dim."""
    out = {}
    for name, shape in leaf_shapes(c).items():
        if not re.fullmatch(a["lora_targets"], name):
            continue
        fan_in = shape[0] * shape[1] if name.endswith("wo/kernel") else shape[0]
        out[name + "/a"] = (fan_in, a["lora_rank"])
        out[name + "/b"] = (a["lora_rank"], int(np.prod(shape)) // fan_in)
    return out


# ---------------------------------------------------------------- the weights
def seed_key(seed: int):
    """A key from any whole number (``rbg``: the chip draws 1.7G normals in
    seconds): the low 31 bits seed it, the rest fold in."""
    return jax.random.fold_in(jax.random.key(seed % (2 ** 31), impl="rbg"), seed // (2 ** 31))


def _leaf(key, shape: tuple, mean: float, std: float, dtype):
    return (mean + std * jax.random.normal(key, shape, F32)).astype(dtype)


def _mean_std(name: str, shape: tuple, z: dict) -> tuple[float, float]:
    """Projections: normal with standard deviation 1/sqrt(fan_in); the
    embedding 1/scale_emb (so that ``h0`` has unit entries) and the head
    (hidden_size/dim_model_base)/sqrt(fan_in) (so that the logits do); norm
    scales jittered about 1, so that no two leaves behave alike."""
    if name.endswith("scale"):
        return 1.0, 0.1
    if name == "embed/embedding":
        return 0.0, 1.0 / z["scale_emb"]
    if name == "lm_head/kernel":
        return 0.0, z["logit_div"] / math.sqrt(shape[0])
    fan_in = shape[0] * shape[1] if name.endswith("wo/kernel") else shape[0]
    return 0.0, 1.0 / math.sqrt(fan_in)


def init_weights(c: dict, seed: int, shardings: dict | None = None, dtype=jnp.bfloat16) -> dict:
    """The frozen base from the seed, rounded to ``dtype`` (bfloat16: what the
    program holds and the reference reads), each leaf placed as ``shardings``
    say."""
    z, shapes, key = sizes(c), leaf_shapes(c), seed_key(seed)
    draw = jax.jit(_leaf, static_argnums=(1, 2, 3, 4))
    out = {}
    for i, name in enumerate(sorted(shapes)):
        leaf = draw(jax.random.fold_in(key, i), shapes[name], *_mean_std(name, shapes[name], z), dtype)
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


def batch_tokens(seed: int, step: int, batch: int, seq_len: int, vocab: int,
                 fault: str | None = None):
    """Step ``step``'s batch: distinct random rows; targets are the tokens
    shifted by one (the last wraps).  ``half_batch`` leaves half of the rows
    out, or of a single row its second half (the first stands in for it)."""
    g = np.random.default_rng([seed, step])
    tokens = g.integers(0, vocab, size=(batch, seq_len), dtype=np.int32)
    if fault == "half_batch":
        if batch > 1:
            tokens = tokens[: batch // 2]
        else:
            tokens[:, seq_len // 2:] = tokens[:, : seq_len - seq_len // 2]
    return tokens, np.roll(tokens, -1, axis=1)


def lr_at(step: int, a: dict) -> float:
    w, total = a["warmup_steps"], max(a["total_steps"], a["warmup_steps"] + 1)
    if step < w:
        return a["learning_rate"] * step / w
    frac = min(step - w, total - w) / (total - w)
    return a["learning_rate"] * 0.5 * (1.0 + math.cos(math.pi * frac))


# ------------------------------------------------------------------ the parts
def _fake_fp8(x):
    s = jnp.max(jnp.abs(x)) / E4M3_MAX + 1e-30
    q = (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    return x + jax.lax.stop_gradient(q - x)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    s, hd = x.shape[0], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(s, dtype=F32)[:, None, None] * freqs
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _in_blocks(fn, n_block: int, *xs):
    """``fn`` over the leading axis of ``xs`` a block at a time, each block
    rematerialised in the backward pass."""
    s = xs[0].shape[0]
    n_block = next(b for b in range(min(n_block, s), 0, -1) if s % b == 0)
    if n_block == s:
        return fn(*xs)
    out = jax.lax.map(jax.checkpoint(lambda t: fn(*t)),
                      tuple(x.reshape(s // n_block, n_block, *x.shape[1:]) for x in xs))
    return jax.tree_util.tree_map(lambda o: o.reshape(s, *o.shape[2:]), out)


def decay_slopes(n_heads: int):
    return 2.0 ** (-8.0 * jnp.arange(1, n_heads + 1, dtype=F32) / n_heads)


def lightning_recurrent(q, k, v, slopes):
    """q, k, v: (s, h, d) -> (s, h, d): the recurrence, token by token."""
    lam = jnp.exp(-slopes)[:, None, None]

    def one(state, qkv):
        qt, kt, vt = qkv
        state = lam * state + kt[:, :, None] * vt[:, None, :]
        return state, jnp.einsum("hd,hde->he", qt, state)

    h, d = q.shape[1:]
    _, out = jax.lax.scan(one, jnp.zeros((h, d, d), F32), (q, k, v))
    return out / math.sqrt(d)


def lightning_chunked(q, k, v, slopes, chunk: int = REF_LIGHTNING_CHUNK):
    """The same sums in chunks: within a chunk ``sum_{j<=i} lam^(i-j) (q_i . k_j) v_j``,
    from the chunks before ``lam^(i+1) q_i S``; ``S <- lam^C S + sum_j lam^(C-1-j) k_j^T v_j``."""
    s, h, d = q.shape
    c = next(b for b in range(min(chunk, s), 0, -1) if s % b == 0)
    i = jnp.arange(c, dtype=F32)
    gap = i[:, None] - i[None, :]
    within = jnp.where(gap >= 0, jnp.exp(-slopes[:, None, None] * jnp.abs(gap)), 0.0)   # (h, c, c)

    def one(state, qkv):
        qc, kc, vc = qkv                                                  # (c, h, d)
        out = jnp.einsum("hij,jhe->ihe", jnp.einsum("ihd,jhd->hij", qc, kc) * within, vc)
        out = out + jnp.exp(-slopes[None, :, None] * (i[:, None, None] + 1.0)) * jnp.einsum(
            "ihd,hde->ihe", qc, state)
        k_kept = kc * jnp.exp(-slopes[None, :, None] * (c - 1.0 - i[:, None, None]))
        state = jnp.exp(-slopes * c)[:, None, None] * state + jnp.einsum("jhd,jhe->hde", k_kept, vc)
        return state, out

    _, out = jax.lax.scan(one, jnp.zeros((h, d, d), F32),
                          tuple(t.reshape(s // c, c, h, d) for t in (q, k, v)))
    return out.reshape(s, h, d) / math.sqrt(d)


def select_for_query(qt, t, kbar, z: dict, n_blocks: int):
    """One query's kept blocks, per KV head.  qt: (kv, g, d); kbar: (m, kv, d)
    -> (kv, n_blocks) bool."""
    ks, st, bs = z["kernel_size"], z["kernel_stride"], z["block_size"]
    m = kbar.shape[0]
    starts = np.arange(m) * st
    first, last = np.arange(n_blocks) * bs, np.arange(n_blocks) * bs + bs - 1
    overlap = jnp.asarray((starts[:, None] <= last[None, :])
                          & (starts[:, None] + ks - 1 >= first[None, :]))      # (m, n_blocks)
    valid = jnp.asarray(starts + ks - 1) <= t
    logits = jnp.where(valid, jnp.einsum("kgd,mkd->kgm", qt, kbar) / math.sqrt(qt.shape[-1]), -1e30)
    e = jnp.where(valid, jnp.exp(logits - jnp.max(logits, -1, keepdims=True)), 0.0)
    p = jnp.sum(e / jnp.maximum(jnp.sum(e, -1, keepdims=True), 1e-30), axis=1)   # (kv, m)
    score = jnp.max(jnp.where(overlap[None], p[:, :, None], 0.0), axis=1)        # (kv, n_blocks)
    blocks = jnp.arange(n_blocks)
    visible = blocks * bs <= t
    forced = visible & ((blocks < z["init_blocks"])
                        | (blocks * bs + bs - 1 >= t - z["window_size"] + 1))
    candidate = visible & ~forced
    order = jnp.argsort(jnp.where(candidate, -score, 1.0), axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    return forced[None] | (candidate[None] & (rank < z["topk"]))


def sparse_attention(q, k, v, z: dict):
    """q: (s, h, d); k, v: (s, kv, d) -> ((s, h, d), kept keys, causal keys)."""
    s, h, d = q.shape
    kv, bs = k.shape[1], z["block_size"]
    qg = q.reshape(s, kv, h // kv, d)
    pos = jnp.arange(s)
    select = s > z["dense_len"]
    if select:
        m = (s - z["kernel_size"]) // z["kernel_stride"] + 1
        idx = np.arange(m)[:, None] * z["kernel_stride"] + np.arange(z["kernel_size"])[None, :]
        kbar = jax.lax.stop_gradient(jnp.mean(k[idx], axis=1))                    # (m, kv, d)

    def rows(qb, tb):
        mask = pos[None, :] <= tb[:, None]                                        # (n, s)
        mask = jnp.broadcast_to(mask[:, None, :], (tb.shape[0], kv, s))
        if select:
            keep = jax.vmap(lambda qt, t: select_for_query(qt, t, kbar, z, s // bs))(
                jax.lax.stop_gradient(qb), tb)                                    # (n, kv, n_blocks)
            mask = mask & jnp.repeat(keep, bs, axis=-1)
        logits = jnp.einsum("nkgd,tkd->nkgt", qb, k) / math.sqrt(d)
        probs = jax.nn.softmax(jnp.where(mask[:, :, None, :], logits, -jnp.inf), axis=-1)
        return jnp.einsum("nkgt,tkd->nkgd", probs, v), jnp.sum(mask, dtype=F32, axis=(1, 2))

    out, kept = _in_blocks(rows, QUERY_BLOCK, qg, pos)
    return out.reshape(s, h, d), jnp.sum(kept), float(kv) * s * (s + 1) / 2


def row_loss_sum(w: dict, lora: dict, tokens, targets, c: dict, a: dict, control=None):
    """(summed next-token loss of one sequence, keys the sparse layers kept,
    keys causal layers would attend)."""
    z = sizes(c)
    q8 = _fake_fp8 if control == "fp8" else (lambda t: t)
    scale = a["lora_alpha"] / a["lora_rank"]

    def proj(x, name, n_in=1):
        """``x W`` over the first ``n_in`` dims of the kernel, plus the
        adapter's low-rank path where the job has one on it."""
        kern = w[name].astype(F32)
        flat_x = x.reshape(x.shape[0], -1)
        y = q8(flat_x) @ q8(kern.reshape(flat_x.shape[1], -1))
        if name + "/a" in lora:
            y = y + scale * (q8(flat_x) @ q8(lora[name + "/a"])) @ q8(lora[name + "/b"])
        return y.reshape(x.shape[0], *kern.shape[n_in:])

    def layer(h, i):
        p = f"layer_{i}/"
        kind = z["mixers"][i]
        x = _rms(h, w[p + "attn_norm/scale"].astype(F32), z["eps"])
        q, k, v = (proj(x, p + f"attn/w{n}/kernel") for n in "qkv")
        q = _rms(q, w[p + "attn/q_norm/scale"].astype(F32), z["eps"])
        k = _rms(k, w[p + "attn/k_norm/scale"].astype(F32), z["eps"])
        kept = causal = 0.0
        if kind == "lightning-attn":
            o = lightning_chunked(_rope(q, z["theta"]), _rope(k, z["theta"]), v, decay_slopes(z["lh"]))
            o = _rms(o.reshape(o.shape[0], -1), w[p + "attn/o_norm/scale"].astype(F32),
                     z["eps"]).reshape(o.shape)
        else:
            o, kept, causal = sparse_attention(q, k, v, z)
        o = o * jax.nn.sigmoid(proj(x, p + "attn/wg/kernel"))
        h = h + z["r"] * proj(o, p + "attn/wo/kernel", n_in=2)

        def mlp(x):
            return proj(jax.nn.silu(proj(x, p + "mlp/w_gate/kernel")) * proj(x, p + "mlp/w_up/kernel"),
                        p + "mlp/w_down/kernel")

        x = _rms(h, w[p + "mlp_norm/scale"].astype(F32), z["eps"])
        return h + z["r"] * _in_blocks(mlp, TOKEN_BLOCK, x), kept, causal

    h = z["scale_emb"] * w["embed/embedding"][tokens].astype(F32)
    kept = causal = 0.0
    for i in range(z["layers"]):
        h, k_i, c_i = jax.checkpoint(layer, static_argnums=(1,))(h, i)
        kept, causal = kept + k_i, causal + c_i

    def head(x, y):
        logits = proj(x, "lm_head/kernel")
        logp = logits - jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
        return -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]

    x = _rms(h, w["final_norm/scale"].astype(F32), z["eps"]) / z["logit_div"]
    return jnp.sum(_in_blocks(head, TOKEN_BLOCK, x, targets)), (kept, causal)


def leaf_norms(tree: dict) -> dict[str, float]:
    sq = jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(F32))))
                            for k, v in t.items()})(tree)
    return {k: float(v) for k, v in sq.items()}


def change_norms(c: dict, a: dict, seed: int, lora: dict) -> dict[str, float]:
    """Norm per adapter leaf of ``lora`` minus the adapters the seed gives."""
    first = init_adapters(c, a, seed)
    return leaf_norms({k: lora[k] - first[k] for k in sorted(lora)})


class ReferenceTrainer:
    """Follows the trainer's first steps in float32 and records, per step,
    the loss and the clipped gradient's norm per adapter leaf, and at the end
    the norm of each adapter leaf's change."""

    def __init__(self, c: dict, a: dict, seed: int, control: str | None = None):
        self.c, self.a, self.seed = c, a, seed
        self.w = init_weights(c, seed)
        self.lora = init_adapters(c, a, seed)
        self.mu = {k: jnp.zeros_like(v) for k, v in self.lora.items()}
        self.nu = {k: jnp.zeros_like(v) for k, v in self.lora.items()}
        self.step_idx = 0
        self.attended = None
        with jax.default_matmul_precision("highest"):
            self._grad = jax.jit(jax.value_and_grad(
                lambda lora, w, t, y: row_loss_sum(w, lora, t, y, c, a, control), has_aux=True))

        def adam(p, g, mu, nu, clip, lr, t):
            g = g * clip
            mu = B1 * mu + (1 - B1) * g
            nu = B2 * nu + (1 - B2) * g * g
            u = (mu / (1 - B1 ** t)) / (jnp.sqrt(nu / (1 - B2 ** t)) + ADAM_EPS)
            return p - lr * (u + a["weight_decay"] * p), mu, nu

        self._adam = jax.jit(adam)

    def step(self, tokens: np.ndarray, targets: np.ndarray) -> dict:
        n_tok = tokens.size
        acc, loss = None, 0.0
        with jax.default_matmul_precision("highest"):
            for r in range(tokens.shape[0]):
                (l, attended), g = self._grad(self.lora, self.w, jnp.asarray(tokens[r]),
                                              jnp.asarray(targets[r]))
                loss += float(l)
                acc = g if acc is None else jax.tree_util.tree_map(jnp.add, acc, g)
        self.attended = tuple(float(x) for x in attended)
        raw = {k: v / n_tok for k, v in leaf_norms(acc).items()}
        gnorm = math.sqrt(sum(v * v for v in raw.values()))
        clip = 1.0 if gnorm < self.a["grad_clip"] else self.a["grad_clip"] / gnorm
        lr, t = lr_at(self.step_idx, self.a), self.step_idx + 1
        for name in sorted(acc):
            self.lora[name], self.mu[name], self.nu[name] = self._adam(
                self.lora[name], acc[name], self.mu[name], self.nu[name],
                jnp.float32(clip / n_tok), jnp.float32(lr), jnp.float32(t))
        self.step_idx += 1
        return {"loss": loss / n_tok, "grad_norms": {k: v * clip for k, v in raw.items()},
                "grad_global_norm": gnorm}

    def change_norms(self) -> dict[str, float]:
        return change_norms(self.c, self.a, self.seed, self.lora)
