"""Plain float32 reference of a Granite 4.0-H (``granitemoehybrid``) adapter
fine-tuning step on packed documents.

Straightforward ``jax.numpy`` at ``highest`` matmul precision, nothing imported
from the program (the helpers that are not the model's come from
``ref_sala.py``: the seed's key, RMSNorm, the float8 control's rounding, the
blockwise ``lax.map``, the schedule, the norms per leaf).  For ONE document
``x`` of (tokens, hidden):

block  ``x = x + r * mixer(RMSNorm(x))``, ``x = x + r * MLP(RMSNorm(x))`` with
    ``r = residual_multiplier``, ``MLP(u) = (silu(u W_g) * (u W_u)) W_d``;
    ``h0 = embedding_multiplier * E[token]``; ``logits = RMSNorm(h_L) E^T /
    logits_scaling`` (the head is the embedding: ``tie_word_embeddings``).
mamba  (HF ``GraniteMoeHybridMambaLayer`` / Bamba, its torch path without the
    fused kernels) ``[z | xBC | dt] = u W_in``; ``xBC_t = silu(b + sum_i w_i
    xBC_{t-3+i})``, four shifted products, zeros before the first token;
    ``xBC -> x`` (heads x head width), ``B``, ``C`` (groups x state);
    ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the recurrence TOKEN BY
    TOKEN, ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t`` from ``h = 0``,
    ``y_t = h_t C_t + D x_t`` (under nested ``jax.checkpoint`` so that 8,191
    steps' states are not all kept); ``y = RMSNorm(y * silu(z)) * w`` over the
    whole inner width; ``out = y W_out``.
attention  ``q k^T * attention_multiplier``, causal softmax a block of queries
    at a time, no positions (``nope``), grouped-query heads, ``W_o``.

PACKING THE PLAIN WAY: each document of a row is run ALONE, from a zero state
and with nothing before its first token, and the documents' losses are
summed over the positions that have a target in the document (all but its
last) and divided by their number in the batch.  No segment id, mask or
reset exists here, so none of the program's has a twin.  Documents of
different lengths would each compile the model anew, and on the chip a
padded length costs more to compile (33 s, a program of 110 MB that no cache
of 256 MiB keeps) than all its documents cost to run (0.33 ms a token and
step, most of it the recurrence), so a document is padded BEHIND its last
token to a quarter of the row or a sixteenth (``bucket``: the cell's sixteen
documents make six of 8,192 and ten of 2,048, two programs): causal mixers
cannot see what follows, and the padded positions are left out of the sum.

The frozen base is drawn in float32 from the seed and rounded to bfloat16
(what the configuration's ``precision`` states); the reference reads those
values in float32.  Rank-``r`` adapters enter as ``x W + (alpha / r) (x a) b``;
their gradients come from autodiff, the global-norm clip and AdamW are
written out.

Departures from HF's module: ``time_step_limit`` (0, inf) is no clamp and is
left out; ``mamba_n_groups`` 1, so HF's group norm is the one norm over the
inner width; no cache; random weights and non-zero adapter factors.  From the
published model: the cut (``reduced`` in the configuration file).

``control="fp8"`` rounds the operands of every projection and of the head to
float8_e4m3.  ``fault="half_batch"`` replaces the second half of the row's
tokens by the first (the documents' boundaries stay).  ``fault="no_reset"``
runs each row as ONE document (state, convolution and attention carried
across every boundary) and still counts the loss where the packed row does.
"""

from __future__ import annotations

import itertools
import math
import re

import jax
import jax.numpy as jnp
import numpy as np

from ref_sala import (ADAM_EPS, ADAPTER_B_STD, B1, B2, F32, QUERY_BLOCK, TOKEN_BLOCK, _fake_fp8,
                      _in_blocks, _rms, leaf_norms, lr_at, seed_key)

__all__ = ["leaf_norms", "B1"]
SCAN_BLOCK = 128   # tokens of the recurrence under one inner checkpoint


# ------------------------------------------------------------------ the sizes
def sizes(c: dict) -> dict:
    h, p, g, n = c["mamba_n_heads"], c["mamba_d_head"], c["mamba_n_groups"], c["mamba_d_state"]
    if h * p != c["mamba_expand"] * c["hidden_size"]:
        raise ValueError("mamba_n_heads x mamba_d_head is mamba_expand x hidden_size")
    if (c["position_embedding_type"] != "nope" or c["attention_bias"] or c["mamba_proj_bias"]
            or not c["mamba_conv_bias"] or not c["tie_word_embeddings"] or c["num_local_experts"]):
        raise ValueError("the reference is granite-4.0-h-micro's: NoPE, no projection bias, a "
                         "convolution bias, a tied head, no routed experts")
    return {"d": c["hidden_size"], "f": c["shared_intermediate_size"], "v": c["vocab_size"],
            "h": c["num_attention_heads"], "kv": c["num_key_value_heads"],
            "hd": c["hidden_size"] // c["num_attention_heads"], "eps": c["rms_norm_eps"],
            "mh": h, "mp": p, "mg": g, "mn": n, "taps": c["mamba_d_conv"], "inner": h * p,
            "layers": list(c["layer_types"])[: c["num_hidden_layers"]],
            "r": c["residual_multiplier"], "emb": float(c["embedding_multiplier"]),
            "attn": c["attention_multiplier"], "logit_div": float(c["logits_scaling"])}


def leaf_shapes(c: dict) -> dict[str, tuple]:
    """The base's leaves under the program's paths."""
    z = sizes(c)
    d, f, inner, bc = z["d"], z["f"], z["inner"], 2 * z["mg"] * z["mn"]
    shapes = {"embed/embedding": (z["v"], d), "final_norm/scale": (d,)}
    for i, kind in enumerate(z["layers"]):
        p = f"layer_{i}/"
        shapes.update({p + "attn_norm/scale": (d,), p + "mlp_norm/scale": (d,),
                       p + "mlp/w_gate/kernel": (d, f), p + "mlp/w_up/kernel": (d, f),
                       p + "mlp/w_down/kernel": (f, d)})
        if kind == "mamba":
            shapes.update({p + "attn/in_proj/kernel": (d, 2 * inner + bc + z["mh"]),
                           p + "attn/conv_kernel": (z["taps"], inner + bc),
                           p + "attn/conv_bias": (inner + bc,), p + "attn/A_log": (z["mh"],),
                           p + "attn/D": (z["mh"],), p + "attn/dt_bias": (z["mh"],),
                           p + "attn/norm/scale": (inner,), p + "attn/out_proj/kernel": (inner, d)})
        elif kind == "attention":
            shapes.update({p + "attn/wq/kernel": (d, z["h"], z["hd"]),
                           p + "attn/wk/kernel": (d, z["kv"], z["hd"]),
                           p + "attn/wv/kernel": (d, z["kv"], z["hd"]),
                           p + "attn/wo/kernel": (z["h"], z["hd"], d)})
        else:
            raise ValueError(f"no reference for layer type {kind!r}")
    return shapes


def _fan_in(name: str, shape: tuple) -> int:
    return shape[0] * shape[1] if name.endswith("wo/kernel") else shape[0]


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
def _draw(key, kind: str, shape: tuple, scale: float, dtype):
    """One leaf of the base, by kind (jitted once a kind, shape and scale:
    the layers share their programs)."""
    if kind == "A_log":
        x = jnp.log(jnp.arange(1, shape[0] + 1, dtype=F32))
    elif kind == "ones":
        x = jnp.ones(shape, F32)
    elif kind == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, F32) * (math.log(1e-1) - math.log(1e-3))
                     + math.log(1e-3))
        x = dt + jnp.log(-jnp.expm1(-dt))
    elif kind == "uniform":
        x = jax.random.uniform(key, shape, F32, -1.0, 1.0) * scale
    elif kind == "norm_scale":
        x = 1.0 + scale * jax.random.normal(key, shape, F32)
    else:
        x = jax.random.normal(key, shape, F32) * scale
    return x.astype(dtype)


def _kind_scale(name: str, shape: tuple, z: dict) -> tuple[str, float]:
    """How a leaf is drawn.  Kernels normal / sqrt(fan_in), the embedding
    normal / embedding_multiplier (so that ``h0`` has unit entries), norm
    scales 1 + 0.1 normal; the Mamba mixer's own as HF initialises them: the
    convolution's kernel and bias uniform within 1 / sqrt(taps) (torch's
    Conv1d), ``A_log = log(1..heads)``, ``D = 1``, ``dt_bias`` the inverse
    softplus of a step drawn log-uniform from [1e-3, 1e-1]."""
    leaf = name.rsplit("/", 1)[-1]
    if leaf in ("A_log", "dt_bias"):
        return leaf, 0.0
    if leaf == "D":
        return "ones", 0.0
    if leaf in ("conv_kernel", "conv_bias"):
        return "uniform", 1.0 / math.sqrt(z["taps"])
    if leaf == "scale":
        return "norm_scale", 0.1
    if name == "embed/embedding":
        return "normal", 1.0 / z["emb"]
    return "normal", 1.0 / math.sqrt(_fan_in(name, shape))


_draw_program = jax.jit(_draw, static_argnums=(1, 2, 3, 4))   # the process's: the check draws the base again


def init_weights(c: dict, seed: int, shardings: dict | None = None, dtype=jnp.bfloat16) -> dict:
    """The frozen base from the seed (``_kind_scale``), rounded to ``dtype``,
    each leaf placed as ``shardings`` say."""
    z, shapes, key = sizes(c), leaf_shapes(c), seed_key(seed)
    out = {}
    for i, name in enumerate(sorted(shapes)):
        kind, scale = _kind_scale(name, shapes[name], z)
        leaf = _draw_program(jax.random.fold_in(key, i), kind, shapes[name], scale, dtype)
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


# ---------------------------------------------------------------- the traffic
def batch_documents(seed: int, step: int, batch: int, lengths, vocab: int,
                    fault: str | None = None) -> list[list[np.ndarray]]:
    """Step ``step``'s batch as the documents of each row: the traffic's
    lengths (their sum is the row) in an order drawn from (seed, step), ids
    uniform over the vocabulary.  ``half_batch``: the second half of a row's
    tokens repeats its first (the boundaries stay where they are)."""
    g = np.random.default_rng([seed, step])
    rows = []
    for _ in range(batch):
        order = g.permutation(len(lengths))
        tokens = g.integers(0, vocab, size=int(sum(lengths)), dtype=np.int32)
        if fault == "half_batch":
            s = tokens.size
            tokens[s // 2:] = tokens[: s - s // 2]
        cuts = np.cumsum([lengths[i] for i in order])[:-1]
        rows.append(np.split(tokens, cuts))
    return rows


def bucket(length: int, row: int) -> int:
    """The length a document is padded to behind its last token: the row, a
    quarter of it or a sixteenth, the smallest that holds it."""
    size = row
    while size // 4 >= max(length, row // 16, 1):
        size //= 4
    return size


# ------------------------------------------------------------------ the model
def conv_taps(x, kernel, bias):
    """``bias + sum_i kernel[i] * x[t - (taps - 1) + i]`` as shifted products."""
    taps, s = kernel.shape[0], x.shape[0]
    y = bias + kernel[-1] * x
    for back in range(1, min(taps, s)):
        y = y + kernel[taps - 1 - back] * jnp.pad(x[: s - back], ((back, 0), (0, 0)))
    return y


def ssm_recurrent(x, dt, a, b_in, c_in, d_skip):
    """x (s, h, p), dt (s, h), a (h,), b_in, c_in (s, g, n), d_skip (h,) ->
    (s, h, p): the recurrence token by token from a zero state, ``SCAN_BLOCK``
    tokens under an inner checkpoint and the blocks under an outer scan, so
    that the backward pass keeps a state a block and a block's states."""
    s, h, p = x.shape
    rep = h // b_in.shape[1]

    def one(state, xs):
        xt, dtt, bt, ct = xs
        bt, ct = jnp.repeat(bt, rep, axis=0), jnp.repeat(ct, rep, axis=0)       # (h, n)
        state = jnp.exp(dtt * a)[:, None, None] * state + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :]
        # (a product and a sum, which fuse with the update: one pass over the state)
        return state, jnp.sum(state * ct[:, None, :], axis=-1)

    blk = next(b for b in range(min(SCAN_BLOCK, s), 0, -1) if s % b == 0)
    blocks = tuple(t.reshape(s // blk, blk, *t.shape[1:]) for t in (x, dt, b_in, c_in))
    inner = jax.checkpoint(lambda state, xs: jax.lax.scan(one, state, xs))
    _, y = jax.lax.scan(inner, jnp.zeros((h, p, b_in.shape[-1]), F32), blocks)
    return y.reshape(s, h, p) + d_skip[:, None] * x


def parts(w: dict, lora: dict, c: dict, a: dict, control=None, unrolled: bool = False) -> dict:
    """The model's parts as functions of ONE document's activations (tokens,
    ...) under base ``w`` and adapters ``lora``; a part's kernels are named by
    its path prefix ``p``.  ``unrolled``: every layer its own code (``hidden``)."""
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

    def f32(name):
        return w[name].astype(F32)

    def mamba(u, p):
        s, inner, gn = u.shape[0], z["inner"], z["mg"] * z["mn"]
        zxbcdt = proj(u, p + "in_proj/kernel")
        gate, xbc, dt = zxbcdt[:, :inner], zxbcdt[:, inner: 2 * inner + 2 * gn], zxbcdt[:, 2 * inner + 2 * gn:]
        xbc = jax.nn.silu(conv_taps(xbc, f32(p + "conv_kernel"), f32(p + "conv_bias")))
        x = xbc[:, :inner].reshape(s, z["mh"], z["mp"])
        b_in = xbc[:, inner: inner + gn].reshape(s, z["mg"], z["mn"])
        c_in = xbc[:, inner + gn:].reshape(s, z["mg"], z["mn"])
        dt = jax.nn.softplus(dt + f32(p + "dt_bias"))
        y = ssm_recurrent(x, dt, -jnp.exp(f32(p + "A_log")), b_in, c_in, f32(p + "D"))
        y = _rms(y.reshape(s, inner) * jax.nn.silu(gate), f32(p + "norm/scale"), z["eps"])
        return proj(y, p + "out_proj/kernel")

    def attention(u, p):
        s, h, kv, hd = u.shape[0], z["h"], z["kv"], z["hd"]
        pos = jnp.arange(s)
        q = proj(u, p + "wq/kernel").reshape(s, kv, h // kv, hd)
        k, v = proj(u, p + "wk/kernel"), proj(u, p + "wv/kernel")

        def rows(qb, tb):
            logits = jnp.einsum("nkgd,tkd->nkgt", qb, k) * z["attn"]
            mask = (pos[None, :] <= tb[:, None])[:, None, None, :]
            return jnp.einsum("nkgt,tkd->nkgd", jax.nn.softmax(jnp.where(mask, logits, -jnp.inf), -1), v)

        out = _in_blocks(rows, QUERY_BLOCK, q, pos)
        return proj(out.reshape(s, h, hd), p + "wo/kernel", n_in=2)

    def layer(h, i):
        p = f"layer_{i}/"
        u = _rms(h, f32(p + "attn_norm/scale"), z["eps"])
        h = h + z["r"] * (mamba if z["layers"][i] == "mamba" else attention)(u, p + "attn/")

        def mlp(x):
            return proj(jax.nn.silu(proj(x, p + "mlp/w_gate/kernel")) * proj(x, p + "mlp/w_up/kernel"),
                        p + "mlp/w_down/kernel")

        return h + z["r"] * _in_blocks(mlp, TOKEN_BLOCK, _rms(h, f32(p + "mlp_norm/scale"), z["eps"]))

    def hidden(tokens):
        """The layers in order.  A run of layers of one kind goes through the
        run's first layer's code, under a scan that puts each layer's leaves
        in the first's place: the same sums, and one body a run for the
        compiler (unrolled, a layer costs the chip's compiler 3.5 s and 30 MB
        of program at every padded length; the scan costs 2.6 GB of
        temporaries at 8,192 tokens and 5 GB at 32,768, which a whole row as
        one document, ``no_reset``, has not got: that one is ``unrolled``)."""
        h = z["emb"] * w["embed/embedding"][tokens].astype(F32)
        for _, run in itertools.groupby(range(len(z["layers"])), key=z["layers"].__getitem__):
            run = list(run)
            if unrolled or len(run) == 1:
                for i in run:
                    h = jax.checkpoint(layer, static_argnums=(1,))(h, i)
                continue
            first, here = run[0], f"layer_{run[0]}/"
            stack = lambda tree: {k: jnp.stack([tree[f"layer_{i}/" + k[len(here):]] for i in run])
                                  for k in tree if k.startswith(here)}

            def one(h, leaves, first=first):
                m = parts({**w, **leaves[0]}, {**lora, **leaves[1]}, c, a, control)
                return m["layer"](h, first), None

            h, _ = jax.lax.scan(jax.checkpoint(one), h, (stack(w), stack(lora)))
        return _rms(h, f32("final_norm/scale"), z["eps"])

    def logits(x):
        return (q8(x) @ q8(f32("embed/embedding")).T) / z["logit_div"]

    def head_losses(x, y):
        def one(xb, yb):
            lg = logits(xb)
            logp = lg - jax.scipy.special.logsumexp(lg, axis=-1, keepdims=True)
            return -jnp.take_along_axis(logp, yb[:, None], axis=-1)[:, 0]
        return _in_blocks(one, TOKEN_BLOCK, x, y)

    return {"mamba": mamba, "attention": attention, "layer": layer, "hidden": hidden,
            "logits": logits, "head_losses": head_losses}


def doc_loss_sum(w: dict, lora: dict, tokens, targets, counted, c: dict, a: dict, control=None,
                 unrolled: bool = False):
    """One document alone: the summed next-token loss over its ``counted``
    positions (bool, (s,))."""
    m = parts(w, lora, c, a, control, unrolled)
    return jnp.sum(jnp.where(counted, m["head_losses"](m["hidden"](tokens), targets), 0.0))


def documents_alone(rows: list[list[np.ndarray]], fault: str | None = None):
    """What ``doc_loss_sum`` is given, a document at a time: (tokens, targets,
    counted), padded behind the document to ``bucket``; and the positions
    counted in all.  ``no_reset``: each row as one document, counted where
    the packed row counts."""
    out, n = [], 0
    for docs in rows:
        row = sum(len(d) for d in docs)
        if fault == "no_reset":
            tokens = np.concatenate(docs)
            counted = np.ones(row, bool)
            counted[np.cumsum([len(d) for d in docs]) - 1] = False
            out.append((tokens, np.roll(tokens, -1), counted))
            n += int(counted.sum())
            continue
        for d in docs:
            size = bucket(len(d), row)
            tokens = np.zeros(size, np.int32)
            tokens[: len(d)] = d
            out.append((tokens, np.roll(tokens, -1), np.arange(size) < len(d) - 1))
            n += len(d) - 1
    return out, n


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
        with jax.default_matmul_precision("highest"):
            self._grad = jax.jit(jax.value_and_grad(
                lambda lora, w, t, y, m, unrolled: doc_loss_sum(w, lora, t, y, m, c, a, control, unrolled)),
                static_argnums=5)

        def adam(p, g, mu, nu, clip, lr, t):
            g = g * clip
            mu = B1 * mu + (1 - B1) * g
            nu = B2 * nu + (1 - B2) * g * g
            u = (mu / (1 - B1 ** t)) / (jnp.sqrt(nu / (1 - B2 ** t)) + ADAM_EPS)
            return p - lr * (u + a["weight_decay"] * p), mu, nu

        self._adam = jax.jit(adam)

    def step(self, rows: list[list[np.ndarray]], fault: str | None = None) -> dict:
        alone, n = documents_alone(rows, fault)
        acc, loss = None, 0.0
        with jax.default_matmul_precision("highest"):
            for tokens, targets, counted in alone:
                l, g = self._grad(self.lora, self.w, jnp.asarray(tokens), jnp.asarray(targets),
                                  jnp.asarray(counted), fault == "no_reset")
                loss += float(l)
                acc = g if acc is None else jax.tree_util.tree_map(jnp.add, acc, g)
        raw = {k: v / n for k, v in leaf_norms(acc).items()}
        gnorm = math.sqrt(sum(v * v for v in raw.values()))
        clip = 1.0 if gnorm < self.a["grad_clip"] else self.a["grad_clip"] / gnorm
        lr, t = lr_at(self.step_idx, self.a), self.step_idx + 1
        for name in sorted(acc):
            self.lora[name], self.mu[name], self.nu[name] = self._adam(
                self.lora[name], acc[name], self.mu[name], self.nu[name],
                jnp.float32(clip / n), jnp.float32(lr), jnp.float32(t))
        self.step_idx += 1
        return {"loss": loss / n, "grad_norms": {k: v * clip for k, v in raw.items()},
                "grad_global_norm": gnorm, "loss_tokens": n}

    def change_norms(self) -> dict[str, float]:
        return change_norms(self.c, self.a, self.seed, self.lora)
