"""Llama-style decoder-only transformer (flax.linen).

Capability target: the reference's LLM stack trains HF Llama-2/TinyLlama via
torch + DeepSpeed (``train/llm/``, SURVEY.md §2.15).  This is the TPU-native
model: RMSNorm, rotary embeddings, (grouped-query) attention, SwiGLU MLP —
built for GSPMD sharding (pure einsum/Dense, static shapes) with optional
ring attention when a ``seq`` mesh axis is present (long-context,
SURVEY.md §5) and ``jax.checkpoint``-friendly block structure.

A configuration may give each layer its own mixer (``mixer_types``): the
softmax GQA ``Attention`` above, ``LightningAttention`` (linear attention with
a per-head decay, ``ops/lightning_attention.py``) or ``SparseAttention``
(InfLLM-v2 block-sparse softmax attention, ``ops/sparse_attention.py``), with
QK-norm, output gates, an output norm and the muP scalars of the MiniCPM
family (embedding, residual branches, logits).  Every mixer names its
projections ``attn/wq|wk|wv|wo/kernel``, so the LoRA targets and the sharding
rules are the same for all.  The defaults build the plain model.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import optax
from flax import linen as nn


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 8
    d_ff: int = 1408  # ~8/3 * d_model rounded
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = True  # jax.checkpoint each block (HBM <-> FLOPs trade)
    # remat policy: "full" recomputes everything in bwd; "dots" saves matmul
    # outputs and recomputes only cheap elementwise/norm ops (much less
    # recompute FLOPs for a modest HBM cost — the right default for MFU)
    remat_policy: str = "dots"
    # lm_head matmul dtype; bf16 keeps the (tokens, vocab) projection on the
    # MXU fast path (loss still upcasts logits to f32 for the softmax)
    logits_dtype: Any = jnp.bfloat16
    # -- per-layer mixers; everything from here on defaults to the plain model
    # one of MIXERS per layer (None: "attention" everywhere)
    mixer_types: Optional[tuple] = None
    head_dim: int = 0  # of the softmax mixers; 0 = d_model // n_heads
    # "minicpm4": InfLLM-v2's block selection (``SparseAttention``)
    sparse_kernel_size: int = 32
    sparse_kernel_stride: int = 16
    sparse_block_size: int = 64
    sparse_topk: int = 64
    sparse_init_blocks: int = 1
    sparse_window_size: int = 2048
    sparse_dense_len: int = 8192  # at most this many tokens: plain causal attention
    # "lightning-attn": linear attention with a per-head decay (``LightningAttention``)
    lightning_heads: int = 0  # 0 = n_heads
    lightning_head_dim: int = 0  # 0 = head_dim
    # muP scalars: h0 = scale_emb * E[token]; each residual branch times
    # scale_depth / sqrt(mup_depth or n_layers) (0 = plain residual);
    # final hidden / (d_model / dim_model_base) before the head (0 = as is)
    scale_emb: float = 1.0
    scale_depth: float = 0.0
    mup_depth: int = 0
    dim_model_base: int = 0
    # tokens of a sequence whose logits exist at a time when the model is
    # given the targets and returns the per-token loss (0 = all of them): a
    # 16k x 73k float32 logits matrix alone is 4.8 GB
    loss_chunk: int = 0

    def mixer(self, layer: int) -> str:
        kind = self.mixer_types[layer] if self.mixer_types else "attention"
        if kind not in MIXERS:
            raise ValueError(f"unknown mixer {kind!r} for layer {layer} (known: {sorted(MIXERS)})")
        return kind

    @property
    def sparse_selection(self) -> dict:
        """The sparse sizes as ``ops/sparse_attention.sparse_attention`` takes them."""
        return dict(kernel_size=self.sparse_kernel_size, kernel_stride=self.sparse_kernel_stride,
                    block_size=self.sparse_block_size, topk=self.sparse_topk,
                    init_blocks=self.sparse_init_blocks, window_size=self.sparse_window_size,
                    dense_len=self.sparse_dense_len)

    @property
    def has_sparse_layers(self) -> bool:
        return any(self.mixer(i) == "minicpm4" for i in range(self.n_layers))

    @classmethod
    def tiny(cls, vocab_size: int = 1024):
        return cls(vocab_size=vocab_size, d_model=128, n_layers=2, n_heads=4,
                   n_kv_heads=4, d_ff=352, max_seq_len=512)

    @classmethod
    def llama_7b(cls):
        """Llama-2-7B shape (the reference FedLLM target model)."""
        return cls(vocab_size=32000, d_model=4096, n_layers=32, n_heads=32,
                   n_kv_heads=32, d_ff=11008, max_seq_len=4096)


class RMSNorm(nn.Module):
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
        return (x * jax.lax.rsqrt(var + self.eps)).astype(x.dtype) * scale


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary position embedding, HF-Llama half-split (rotate_half) convention
    so pretrained Llama-2 checkpoints (the stated llama_7b target) load without
    permuting wq/wk.  x: (b, s, h, d)."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions[:, :, None, None].astype(jnp.float32) * freqs  # (b, s, 1, d/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


def _project(mdl: nn.Module, name: str, x, features, axis=-1):
    """The projection ``name`` of mixer ``mdl`` (a bias-free ``DenseGeneral``
    over ``axis``), plus the low-rank path ``(x a) b`` of its adapter where the
    variables hold one: collection ``lora``, under the mixer's scope, ``name``
    -> ``{"a": (fan_in, r), "b": (r, fan_out)}`` (``llm/lora.py:as_collection``
    lays a LoRA tree out so, ``b`` already times ``alpha / r``).  The frozen
    kernel then needs no gradient and no merged copy."""
    y = nn.DenseGeneral(features=features, axis=axis, use_bias=False, dtype=mdl.cfg.dtype,
                        name=name)(x)
    if mdl.has_variable("lora", name):
        ab = mdl.get_variable("lora", name)
        n_in = len(axis) if isinstance(axis, tuple) else 1
        flat = x.reshape(x.shape[: x.ndim - n_in] + (-1,))
        low = (flat @ ab["a"].astype(x.dtype)) @ ab["b"].astype(x.dtype)
        y = y + low.reshape(y.shape).astype(y.dtype)
    return y


class Attention(nn.Module):
    cfg: TransformerConfig
    mesh: Optional[Any] = None
    seq_axis: Optional[str] = None

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        hd = cfg.head_dim or cfg.d_model // cfg.n_heads
        q = _project(self, "wq", x, (cfg.n_heads, hd))
        k = _project(self, "wk", x, (cfg.n_kv_heads, hd))
        v = _project(self, "wv", x, (cfg.n_kv_heads, hd))
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
        if cfg.n_kv_heads != cfg.n_heads:  # GQA: repeat kv heads
            rep = cfg.n_heads // cfg.n_kv_heads
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        if self.mesh is not None and self.seq_axis and self.mesh.shape[self.seq_axis] > 1:
            from ..ops.ring_attention import ring_attention
            from ..parallel.mesh import AXIS_DATA, AXIS_MODEL

            out = ring_attention(
                q, k, v, self.mesh, axis=self.seq_axis, causal=True,
                dp_axis=AXIS_DATA, tp_axis=AXIS_MODEL,
            )
        else:
            from ..ops.ring_attention import dense_attention

            out = dense_attention(q, k, v, causal=True)
        return _project(self, "wo", out, cfg.d_model, axis=(-2, -1))


def _no_seq_axis(mdl) -> None:
    if mdl.mesh is not None and mdl.seq_axis and mdl.mesh.shape[mdl.seq_axis] > 1:
        raise NotImplementedError(f"{type(mdl).__name__} has no sequence-sharded form yet")


def _qk_normed(cfg, q, k):
    """QK-norm: RMSNorm over each head of q and k, one learned scale each."""
    return (RMSNorm(cfg.norm_eps, name="q_norm")(q).astype(cfg.dtype),
            RMSNorm(cfg.norm_eps, name="k_norm")(k).astype(cfg.dtype))


def _gated(mdl, x, out):
    """``out * sigmoid(W_g x)``, per head and channel."""
    return out * nn.sigmoid(_project(mdl, "wg", x, out.shape[-2:]))


class LightningAttention(nn.Module):
    """Linear attention with a fixed per-head decay (``ops/lightning_attention``):
    QK-norm, RoPE, the chunked recurrence, then
    ``W_o(RMSNorm(o) * sigmoid(W_g x))`` with the norm over all heads."""

    cfg: TransformerConfig
    mesh: Optional[Any] = None
    seq_axis: Optional[str] = None

    @nn.compact
    def __call__(self, x, positions):
        from ..ops.lightning_attention import decay_slopes, lightning_attention

        _no_seq_axis(self)
        cfg = self.cfg
        nh = cfg.lightning_heads or cfg.n_heads
        hd = cfg.lightning_head_dim or cfg.head_dim or cfg.d_model // cfg.n_heads
        q, k, v = (_project(self, n, x, (nh, hd)) for n in ("wq", "wk", "wv"))
        q, k = _qk_normed(cfg, q, k)
        q, k = rope(q, positions, cfg.rope_theta), rope(k, positions, cfg.rope_theta)
        with jax.named_scope("llm.mixer.lightning"):
            out = lightning_attention(q, k, v, decay_slopes(nh))
        flat = out.reshape(out.shape[:-2] + (nh * hd,))
        out = RMSNorm(cfg.norm_eps, name="o_norm")(flat).reshape(out.shape).astype(out.dtype)
        out = _gated(self, x, out)
        return _project(self, "wo", out, cfg.d_model, axis=(-2, -1))


class SparseAttention(nn.Module):
    """GQA softmax attention under InfLLM-v2's per-query block selection
    (``ops/sparse_attention``), as MiniCPM-SALA has it: QK-norm, no RoPE,
    ``W_o(o * sigmoid(W_g x))``; plain causal attention up to
    ``sparse_dense_len`` tokens.  Sows what it attended into collection
    ``stats`` (``sparse_kept``, ``sparse_causal``: keys, summed over batch, KV
    heads and queries) where the caller makes that collection mutable."""

    cfg: TransformerConfig
    mesh: Optional[Any] = None
    seq_axis: Optional[str] = None

    @nn.compact
    def __call__(self, x, positions):
        from ..ops.sparse_attention import sparse_attention

        _no_seq_axis(self)
        cfg = self.cfg
        hd = cfg.head_dim or cfg.d_model // cfg.n_heads
        q = _project(self, "wq", x, (cfg.n_heads, hd))
        k = _project(self, "wk", x, (cfg.n_kv_heads, hd))
        v = _project(self, "wv", x, (cfg.n_kv_heads, hd))
        q, k = _qk_normed(cfg, q, k)
        with jax.named_scope("llm.mixer.sparse"):
            out, kept, causal = sparse_attention(q, k, v, **cfg.sparse_selection)
        add = lambda a, b: a + b
        self.sow("stats", "sparse_kept", kept, init_fn=lambda: jnp.float32(0), reduce_fn=add)
        self.sow("stats", "sparse_causal", causal, init_fn=lambda: jnp.float32(0), reduce_fn=add)
        out = _gated(self, x, out)
        return _project(self, "wo", out, cfg.d_model, axis=(-2, -1))


MIXERS = {"attention": Attention, "lightning-attn": LightningAttention, "minicpm4": SparseAttention}


class MLP(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        with jax.named_scope("llm.mlp"):
            gate = nn.Dense(cfg.d_ff, use_bias=False, dtype=cfg.dtype, name="w_gate")(x)
            up = nn.Dense(cfg.d_ff, use_bias=False, dtype=cfg.dtype, name="w_up")(x)
            return nn.Dense(cfg.d_model, use_bias=False, dtype=cfg.dtype, name="w_down")(
                nn.silu(gate) * up
            )


class Block(nn.Module):
    cfg: TransformerConfig
    mesh: Optional[Any] = None
    seq_axis: Optional[str] = None
    mixer: str = "attention"

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        branch = lambda y: y
        if cfg.scale_depth:  # muP: each residual branch times scale_depth / sqrt(depth)
            # in float32: a bfloat16 1.4 / sqrt(32) is 0.17% short, on every branch alike
            r = cfg.scale_depth / (cfg.mup_depth or cfg.n_layers) ** 0.5
            branch = lambda y: (y.astype(jnp.float32) * r).astype(x.dtype)
        x = x + branch(MIXERS[self.mixer](cfg, self.mesh, self.seq_axis, name="attn")(
            RMSNorm(cfg.norm_eps, name="attn_norm")(x), positions
        ))
        x = x + branch(MLP(cfg, name="mlp")(RMSNorm(cfg.norm_eps, name="mlp_norm")(x)))
        return x


def block_remat_policy(cfg: TransformerConfig):
    """What a rematerialised ``Block`` keeps for its backward pass."""
    policy = (
        jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        if cfg.remat_policy == "dots" else None
    )
    if cfg.has_sparse_layers:  # the block mask has no gradient: keep it, select once
        from ..ops.sparse_attention import SPARSE_KEEP

        named = jax.checkpoint_policies.save_only_these_names(SPARSE_KEEP)
        policy = named if policy is None else (
            jax.checkpoint_policies.save_from_both_policies(policy, named))
    return policy


class Transformer(nn.Module):
    cfg: TransformerConfig
    mesh: Optional[Any] = None
    seq_axis: Optional[str] = None

    @nn.compact
    def __call__(self, tokens, train: bool = True, targets=None):
        """Logits (b, s, vocab); or, given ``targets`` (b, s), the float32
        next-token loss of each position (b, s), the head and the softmax
        taken ``cfg.loss_chunk`` positions at a time and rematerialised in the
        backward pass, so that the whole logits matrix never exists."""
        cfg = self.cfg
        b, s = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        x = nn.Embed(cfg.vocab_size, cfg.d_model, dtype=cfg.dtype, name="embed")(tokens)
        if cfg.scale_emb != 1.0:
            x = (x.astype(jnp.float32) * cfg.scale_emb).astype(cfg.dtype)
        block = Block
        if cfg.remat:
            policy = block_remat_policy(cfg)
            block = nn.remat(Block, static_argnums=(), policy=policy)
        for i in range(cfg.n_layers):
            x = block(cfg, self.mesh, self.seq_axis, cfg.mixer(i), name=f"layer_{i}")(x, positions)
        with jax.named_scope("llm.head_loss"):  # the trainer's loss carries the same name
            x = RMSNorm(cfg.norm_eps, name="final_norm")(x)
            if cfg.dim_model_base:
                x = (x.astype(jnp.float32) / (cfg.d_model / cfg.dim_model_base)).astype(x.dtype)
            head = nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.logits_dtype, name="lm_head")
            if targets is None:
                return head(x)

            def chunk_loss(head, xc, yc):
                return optax.softmax_cross_entropy_with_integer_labels(
                    head(xc).astype(jnp.float32), yc)

            n = cfg.loss_chunk or s
            if s % n:
                raise ValueError(f"loss_chunk {n} does not divide the sequence length {s}")
            return jnp.concatenate(
                [nn.remat(chunk_loss)(head, x[:, i: i + n], targets[:, i: i + n])
                 for i in range(0, s, n)], axis=1)
