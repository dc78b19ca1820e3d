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
family (embedding, residual branches, logits).  Those mixers name their
projections ``attn/wq|wk|wv|wo/kernel``, so the LoRA targets and the sharding
rules are the same for all.  A fourth mixer, ``MLAttention``, is the latent
attention of the DeepSeek-V3 / openPangu-Ultra family (two low-rank paths with
their own norms, a rotary part beside a non-rotary one, a value width of its
own: ``attn/wq_a|wq_b|wkv_a|wkv_b|wo/kernel``).  Layers from ``first_k_dense``
on may have a sparse expert layer (``MoE``, ``ops/moe.py``: a router over all
``n_routed_experts``, the ``experts_held`` this chip holds, shared experts) in
the dense SwiGLU's place; ``sandwich_norm`` norms each branch's output as well
as its input; ``mtp_layers`` adds a multi-token-prediction module (``MTP``)
whose loss ``Transformer.__call__(targets=...)`` returns beside the main one.
A fifth mixer, ``Mamba``, is the Mamba-2 state-space layer of the Granite
4.0-H / Bamba family (``attn/in_proj|out_proj/kernel``, a depthwise causal
convolution, the selective scan of ``ops/ssd.py``, a gated norm); beside it
the plain ``Attention`` may go without RoPE (``attn_rope``), with a scale of
its own (``attn_scale``) and, on packed rows, blockwise (no ``s x s``
scores); ``tie_embeddings`` reads the logits off the embedding.
A sixth, ``DSAttention``, is DeepSeek sparse attention as Keye-VL-2.0 has it
on grouped-query attention: a frozen learned indexer (``Indexer``,
``attn/indexer/wq|wk|weights/kernel``) chooses ``dsa_topk`` keys for each
query (``ops/dsa.py``), and the query's heads attend those alone; beside it
the expert router may score by a softmax over all the experts
(``router_scoring``).
A seventh, ``KDA``, is Kimi Delta Attention (the gated delta rule with a
decay per channel, ``ops/kda.py``, beside three short convolutions and
low-rank gates); beside it ``MLAttention`` may take its queries directly
(``q_lora_rank`` 0) and go without positions (``mla_use_nope``), and the
router may choose by a selection bias that its gates do not see
(``router_bias``).
``segments`` (document ids of packed rows) reach every mixer: state,
convolution and attention stop at a document's start, and the loss counts
the positions whose target lies in their own document.
The defaults build the plain model.
"""

from __future__ import annotations

import dataclasses
import functools
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
    # "mla": latent attention (``MLAttention``); the head widths of its
    # non-rotary and rotary query/key parts and of its values
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # sparse expert layers (``MoE``) from layer first_k_dense on, where
    # n_routed_experts > 0: the router has n_routed_experts outputs and picks
    # top_k of them; this chip holds experts first_expert .. first_expert +
    # experts_held - 1 (0 held = all of them) and adds their part alone
    first_k_dense: int = 0
    n_routed_experts: int = 0
    experts_held: int = 0
    first_expert: int = 0
    top_k: int = 0
    n_shared_experts: int = 0
    moe_d_ff: int = 0
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = False
    # x + N2(mixer(N1(x))), x + N4(ffn(N3(x))): a norm after each branch too
    sandwich_norm: bool = False
    # multi-token-prediction modules after the last layer (0 or 1)
    mtp_layers: int = 0
    # "mamba": the Mamba-2 mixer (``Mamba``): heads x head width is its inner
    # width; B and C (d_state wide) are shared by the heads of a group; taps of
    # the depthwise causal convolution; tokens a step of the scan takes
    mamba_heads: int = 0
    mamba_head_dim: int = 0
    mamba_d_state: int = 0
    mamba_groups: int = 1
    mamba_d_conv: int = 4
    mamba_chunk: int = 256
    # the plain ``Attention`` mixer: rotary positions or none (NoPE), and the
    # factor on its scores (0 = 1 / sqrt(head_dim))
    attn_rope: bool = True
    attn_scale: float = 0.0
    # logits = hidden E^T: the embedding is the head (no ``lm_head``)
    tie_embeddings: bool = False
    # "dsa": DeepSeek sparse attention (``DSAttention``): the indexer's query
    # heads and their width (one key head), and the keys each query keeps
    dsa_index_heads: int = 0
    dsa_index_head_dim: int = 0
    dsa_topk: int = 0
    # the expert router's scores (``ops/moe.route``): "sigmoid" or "softmax"
    router_scoring: str = "sigmoid"
    # ... and a selection bias of its own (``router/e_score_correction_bias``):
    # the choice is the top_k of scores + bias, the gates the scores alone
    router_bias: bool = False
    # "mla" without positions (NoPE): no RoPE on q_r or k_r; q_lora_rank 0 is
    # one direct ``wq`` in place of the low-rank query path
    mla_use_nope: bool = False
    # "kda": Kimi Delta Attention (``KDA``): heads and their width (keys and
    # values alike), taps of its three short convolutions
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_conv: int = 4

    def has_experts(self, layer: int) -> bool:
        return self.n_routed_experts > 0 and layer >= self.first_k_dense

    @property
    def has_expert_layers(self) -> bool:
        """Any expert layer, the MTP module's block included (it follows the
        last layer, so it is one wherever the model has any)."""
        return self.n_routed_experts > 0 and (self.mtp_layers > 0 or self.first_k_dense < self.n_layers)

    def mixer(self, layer: int) -> str:
        kind = self.mixer_types[layer] if self.mixer_types else "attention"
        if kind not in MIXERS:
            raise ValueError(f"unknown mixer {kind!r} for layer {layer} (known: {sorted(MIXERS)})")
        return kind

    @property
    def has_kda_layers(self) -> bool:
        """Layers that sow ``KDA_STATS``."""
        return any(self.mixer(i) == "kda" for i in range(self.n_layers))

    @property
    def sparse_selection(self) -> dict:
        """The sparse sizes as ``ops/sparse_attention.sparse_attention`` takes them."""
        return dict(kernel_size=self.sparse_kernel_size, kernel_stride=self.sparse_kernel_stride,
                    block_size=self.sparse_block_size, topk=self.sparse_topk,
                    init_blocks=self.sparse_init_blocks, window_size=self.sparse_window_size,
                    dense_len=self.sparse_dense_len)

    @property
    def has_sparse_layers(self) -> bool:
        """Layers that choose the keys each query attends: they sow what they
        kept, and a remat keeps their choice (``SPARSE_KEEP``)."""
        return any(self.mixer(i) in ("minicpm4", "dsa") for i in range(self.n_layers))

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


def _scoped(name: str):
    """A mixer's ``__call__`` inside ``jax.named_scope(name)``: every device
    op of the module (projections, norms, RoPE, gates) carries
    ``llm.mixer.<kind>`` in its ``op_name``, and what ``ops/`` does for it
    ``llm.mixer.<kind>.core`` inside that (``obs/scopes.py`` reads both)."""
    def wrap(call):
        @functools.wraps(call)
        def scoped(self, *args, **kwargs):
            with jax.named_scope(name):
                return call(self, *args, **kwargs)
        return scoped
    return wrap


def _on_seq_axis(mdl) -> bool:
    return mdl.mesh is not None and bool(mdl.seq_axis) and mdl.mesh.shape[mdl.seq_axis] > 1


def _no_seq_axis(mdl) -> None:
    if _on_seq_axis(mdl):
        raise NotImplementedError(f"{type(mdl).__name__} has no sequence-sharded form yet")


def _no_segments(mdl, segments) -> None:
    if segments is not None:
        raise NotImplementedError(f"{type(mdl).__name__} does not take packed documents yet")


class Attention(nn.Module):
    """Grouped-query softmax attention: RoPE unless ``cfg.attn_rope`` is off,
    scores times ``cfg.attn_scale`` (0: ``1 / sqrt(head_dim)``), causal, and
    with ``segments`` within a document.  Rows on a ``seq`` mesh axis go round
    the ring (unpacked rows only: packed ones have no sequence-sharded form);
    every other row, packed or not, goes through
    ``ops/sparse_attention.block_sparse_attention`` with every block kept and
    K, V at their own ``n_kv_heads``: the fused flash kernel where
    ``attention_path`` finds that it can (one TPU device, a length that
    tiles), else the ``lax`` blockwise pass in chunks of ``row_chunk``.  No
    (b, h, s, s) tensor either way (at 2,048 tokens x 32 heads x 4 rows it was
    2.1 GB in float32, twice a layer; at 32,768 x 32 it would be 137 GB)."""

    cfg: TransformerConfig
    mesh: Optional[Any] = None
    seq_axis: Optional[str] = None

    @nn.compact
    @_scoped("llm.mixer.attention")
    def __call__(self, x, positions, segments=None):
        cfg = self.cfg
        hd = cfg.head_dim or cfg.d_model // cfg.n_heads
        q = _project(self, "wq", x, (cfg.n_heads, hd))
        k = _project(self, "wk", x, (cfg.n_kv_heads, hd))
        v = _project(self, "wv", x, (cfg.n_kv_heads, hd))
        if cfg.attn_rope:
            q = rope(q, positions, cfg.rope_theta)
            k = rope(k, positions, cfg.rope_theta)
        scale = cfg.attn_scale or None
        if _on_seq_axis(self) and segments is None:
            from ..ops.ring_attention import ring_attention
            from ..parallel.mesh import AXIS_DATA, AXIS_MODEL

            if scale is not None:
                raise NotImplementedError("ring attention scales its scores by 1 / sqrt(head_dim)")
            if cfg.n_kv_heads != cfg.n_heads:  # GQA: the ring takes a key per query head
                rep = cfg.n_heads // cfg.n_kv_heads
                k = jnp.repeat(k, rep, axis=2)
                v = jnp.repeat(v, rep, axis=2)
            with jax.named_scope("llm.mixer.attention.core"):
                out = ring_attention(
                    q, k, v, self.mesh, axis=self.seq_axis, causal=True,
                    dp_axis=AXIS_DATA, tp_axis=AXIS_MODEL,
                )
        else:
            from ..ops.sparse_attention import block_sparse_attention, row_chunk

            _no_seq_axis(self)     # packed rows have no ring
            chunk = row_chunk(x.shape[1])
            with jax.named_scope("llm.mixer.attention.core"):
                out = block_sparse_attention(q, k, v, None, q_chunk=chunk, k_chunk=chunk,
                                             scale=scale, mesh=self.mesh, segments=segments)
        return _project(self, "wo", out, cfg.d_model, axis=(-2, -1))


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
    @_scoped("llm.mixer.lightning")
    def __call__(self, x, positions, segments=None):
        from ..ops.lightning_attention import decay_slopes, lightning_attention

        _no_seq_axis(self)
        _no_segments(self, segments)
        cfg = self.cfg
        nh = cfg.lightning_heads or cfg.n_heads
        hd = cfg.lightning_head_dim or cfg.head_dim or cfg.d_model // cfg.n_heads
        q, k, v = (_project(self, n, x, (nh, hd)) for n in ("wq", "wk", "wv"))
        q, k = _qk_normed(cfg, q, k)
        q, k = rope(q, positions, cfg.rope_theta), rope(k, positions, cfg.rope_theta)
        with jax.named_scope("llm.mixer.lightning.core"):
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
    @_scoped("llm.mixer.sparse")
    def __call__(self, x, positions, segments=None):
        from ..ops.sparse_attention import sparse_attention

        _no_seq_axis(self)
        _no_segments(self, segments)
        cfg = self.cfg
        hd = cfg.head_dim or cfg.d_model // cfg.n_heads
        q = _project(self, "wq", x, (cfg.n_heads, hd))
        k = _project(self, "wk", x, (cfg.n_kv_heads, hd))
        v = _project(self, "wv", x, (cfg.n_kv_heads, hd))
        q, k = _qk_normed(cfg, q, k)
        with jax.named_scope("llm.mixer.sparse.core"):
            out, kept, causal = sparse_attention(q, k, v, mesh=self.mesh, **cfg.sparse_selection)
        add = lambda a, b: a + b
        self.sow("stats", "sparse_kept", kept, init_fn=lambda: jnp.float32(0), reduce_fn=add)
        self.sow("stats", "sparse_causal", causal, init_fn=lambda: jnp.float32(0), reduce_fn=add)
        out = _gated(self, x, out)
        return _project(self, "wo", out, cfg.d_model, axis=(-2, -1))


#: heads the blockwise ``lax`` pass takes at a time under a latent-attention
#: mixer: its float32 accumulators and chunked copies of q, k, v scale with
#: the heads it is given (2.6 GB for 128 heads x 8,192 tokens, a quarter of
#: that for 32).  The fused kernel has neither and takes all heads at once.
MLA_HEAD_GROUP = 32


class MLAttention(nn.Module):
    """Multi-head latent attention in its expanded (training) form: queries
    through a low-rank path ``c_q = N_q(x W_dq)``, ``[q_n | q_r] = c_q W_uq``
    (with ``q_lora_rank`` 0 one direct ``[q_n | q_r] = x W_q``); keys and
    values through another, ``[c_kv | k_r] = x W_dkv``, ``[k_n | v] =
    N_kv(c_kv) W_ukv``; RoPE on ``q_r`` and on the ONE ``k_r`` all heads share
    (none with ``mla_use_nope``);
    causal softmax attention of ``[q_n | q_r]`` over ``[k_n | k_r]`` scaled by
    the whole query width, values of their own width; ``W_o`` over (heads x
    v_head_dim).  Blockwise (``ops/sparse_attention.block_sparse_attention``,
    every block kept): 128 heads' (s, s) scores are never whole, and where
    that function finds it can (one TPU device, a sequence that tiles) no
    pair of chunks' scores leaves the chip: the fused flash kernel."""

    cfg: TransformerConfig
    mesh: Optional[Any] = None
    seq_axis: Optional[str] = None

    @nn.compact
    @_scoped("llm.mixer.mla")
    def __call__(self, x, positions, segments=None):
        from ..ops.sparse_attention import CHUNK, block_sparse_attention

        _no_seq_axis(self)
        _no_segments(self, segments)
        cfg = self.cfg
        h, nope, rot, dv = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        if cfg.q_lora_rank:
            c_q = RMSNorm(cfg.norm_eps, name="q_a_norm")(_project(self, "wq_a", x, cfg.q_lora_rank))
            q = _project(self, "wq_b", c_q.astype(cfg.dtype), (h, nope + rot))
        else:
            q = _project(self, "wq", x, (h, nope + rot))
        kv_a = _project(self, "wkv_a", x, cfg.kv_lora_rank + rot)
        c_kv = RMSNorm(cfg.norm_eps, name="kv_a_norm")(kv_a[..., : cfg.kv_lora_rank])
        kv = _project(self, "wkv_b", c_kv.astype(cfg.dtype), (h, nope + dv))
        k_r = kv_a[..., None, cfg.kv_lora_rank:]                                   # (b, s, 1, rot)
        if not cfg.mla_use_nope:
            k_r = rope(k_r, positions, cfg.rope_theta)
            q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], positions, cfg.rope_theta)], -1)
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_r, k_r.shape[:2] + (h, rot))], -1)
        with jax.named_scope("llm.mixer.mla.core"):
            out = block_sparse_attention(q, k, kv[..., nope:], None, q_chunk=CHUNK, k_chunk=CHUNK,
                                         scale=(nope + rot) ** -0.5, mesh=self.mesh,
                                         head_group=MLA_HEAD_GROUP)
        return _project(self, "wo", out, cfg.d_model, axis=(-2, -1))


class Indexer(nn.Module):
    """DeepSeek-V3.2's lightning indexer over the mixer's input ``x``: queries
    ``q_j = rope(x W_q)`` (``dsa_index_heads`` of ``dsa_index_head_dim``), ONE
    key a token ``k = rope(LayerNorm(x W_k))``, weights ``w = x W_w /
    sqrt(heads x head_dim)``; the ``dsa_topk`` keys ``s <= t`` of best
    ``sum_j w_j ReLU(q_j . k_s)`` for each query (``ops/dsa.select_tokens``,
    the choice under ``llm.mixer.dsa.indexer.select``).  Frozen: no adapter
    reads it and no gradient leaves it.  Returns (the choice packed a bit a
    key, (b, s, s / 32) uint32; the pairs chosen, summed over the batch)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, positions):
        from ..ops import dsa

        cfg = self.cfg
        h, d = cfg.dsa_index_heads, cfg.dsa_index_head_dim
        x = jax.lax.stop_gradient(x)
        dense = lambda name, features: nn.DenseGeneral(features, use_bias=False, dtype=cfg.dtype, name=name)(x)
        q = rope(dense("wq", (h, d)), positions, cfg.rope_theta)
        k = nn.LayerNorm(cfg.norm_eps, dtype=jnp.float32, name="k_norm")(dense("wk", d))
        k = rope(k[:, :, None], positions, cfg.rope_theta)[:, :, 0].astype(cfg.dtype)
        w = dense("weights", h).astype(jnp.float32) * (h * d) ** -0.5
        return dsa.select_tokens(q, k, w, cfg.dsa_topk)


class DSAttention(nn.Module):
    """DeepSeek sparse attention (DSA) on grouped-query softmax attention:
    QK-norm and RoPE on ``n_heads`` queries over ``n_kv_heads`` keys and
    values; the ``Indexer`` chooses ``dsa_topk`` keys ``s <= t`` for each
    query, and every head attends those alone
    (``ops/sparse_attention.block_sparse_attention`` with the choice as its
    mask: the ``lax`` blockwise pass, which computes every causal pair of
    chunks and lets the mask drop what was not chosen); then ``W_o``.  The
    choice is named ``SPARSE_KEEP``, so that a remat keeps it (134 MB a layer
    at 32,768 tokens) and does not choose again.  Sows ``sparse_kept`` and
    ``sparse_causal`` as ``SparseAttention`` does (keys, summed over batch,
    KV heads and queries)."""

    cfg: TransformerConfig
    mesh: Optional[Any] = None
    seq_axis: Optional[str] = None

    @nn.compact
    @_scoped("llm.mixer.dsa")
    def __call__(self, x, positions, segments=None):
        from jax.ad_checkpoint import checkpoint_name

        from ..ops.sparse_attention import CHUNK, SPARSE_KEEP, WORD, block_sparse_attention

        _no_seq_axis(self)
        _no_segments(self, segments)
        cfg = self.cfg
        b, s = x.shape[:2]
        hd = cfg.head_dim or cfg.d_model // cfg.n_heads
        q = _project(self, "wq", x, (cfg.n_heads, hd))
        k = _project(self, "wk", x, (cfg.n_kv_heads, hd))
        v = _project(self, "wv", x, (cfg.n_kv_heads, hd))
        q, k = _qk_normed(cfg, q, k)
        q, k = rope(q, positions, cfg.rope_theta), rope(k, positions, cfg.rope_theta)
        with jax.named_scope("llm.mixer.dsa.indexer"):
            keep, chosen = Indexer(cfg, name="indexer")(x, positions)
        keep = checkpoint_name(keep, SPARSE_KEEP)
        with jax.named_scope("llm.mixer.dsa.core"):
            out = block_sparse_attention(q, k, v, keep[:, None], block_size=WORD, q_chunk=CHUNK,
                                         k_chunk=CHUNK, mesh=self.mesh)
        add = lambda a, b: a + b
        kv = jnp.float32(cfg.n_kv_heads)
        self.sow("stats", "sparse_kept", chosen.astype(jnp.float32) * kv, init_fn=lambda: jnp.float32(0),
                 reduce_fn=add)
        self.sow("stats", "sparse_causal", kv * jnp.float32(b * s * (s + 1) / 2), init_fn=lambda: jnp.float32(0),
                 reduce_fn=add)
        return _project(self, "wo", out, cfg.d_model, axis=(-2, -1))


def causal_conv(x, kernel, bias=None, segments=None):
    """Depthwise causal convolution over the sequence: x (b, s, c), kernel
    (taps, c), bias (c,) or None -> ``bias + sum_i kernel[i] * x[t - (taps -
    1) + i]`` in float32, as ``taps`` shifted products; a tap that would reach
    before the row's first token, or with ``segments`` (b, s) before its
    document's, reads zero."""
    taps, s = kernel.shape[0], x.shape[1]
    x32, kernel = x.astype(jnp.float32), kernel.astype(jnp.float32)
    y = kernel[-1] * x32 if bias is None else bias.astype(jnp.float32) + kernel[-1] * x32
    for back in range(1, min(taps, s)):
        shifted = jnp.pad(x32[:, : s - back], ((0, 0), (back, 0), (0, 0)))
        if segments is not None:   # documents are runs: the same id ``back`` tokens ago is the same document
            same = jnp.pad(segments[:, back:] == segments[:, : s - back], ((0, 0), (back, 0)))
            shifted = jnp.where(same[..., None], shifted, 0.0)
        y = y + kernel[taps - 1 - back] * shifted
    return y


class Mamba(nn.Module):
    """The Mamba-2 mixer as HF's ``GraniteMoeHybridMambaLayer`` / Bamba has
    it: ``[z | xBC | dt] = u W_in``; ``xBC = silu(conv(xBC))`` (``causal_conv``,
    with bias); ``xBC -> x`` (heads x head width), ``B``, ``C`` (groups x
    state); ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the selective
    scan ``ops/ssd.ssd`` with the ``D`` skip; ``RMSNorm(y * silu(z)) * w`` over
    the whole inner width (the gate before the norm); ``W_out``.  With
    ``segments`` neither the convolution nor the state crosses a document's
    start."""

    cfg: TransformerConfig
    mesh: Optional[Any] = None
    seq_axis: Optional[str] = None

    @nn.compact
    def __call__(self, x, positions, segments=None):
        from ..ops.ssd import ssd

        _no_seq_axis(self)
        cfg, f32 = self.cfg, jnp.float32
        h, p, n, g = cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_d_state, cfg.mamba_groups
        inner, bc = h * p, 2 * g * n
        with jax.named_scope("llm.mixer.mamba"):
            zxbcdt = _project(self, "in_proj", x, 2 * inner + bc + h)
            z, xbc, dt = jnp.split(zxbcdt, (inner, 2 * inner + bc), axis=-1)
            # HF's defaults: the convolution as torch's Conv1d draws it (uniform within
            # 1 / sqrt(taps)), A = 1..heads, D = 1, dt's bias from a step in [1e-3, 1e-1]
            conv_init = lambda key, shape: jax.random.uniform(
                key, shape, f32, -1.0, 1.0) * cfg.mamba_d_conv ** -0.5
            conv_kernel = self.param("conv_kernel", conv_init, (cfg.mamba_d_conv, inner + bc))
            conv_bias = self.param("conv_bias", conv_init, (inner + bc,))
            a_log = self.param("A_log", lambda key, shape: jnp.log(jnp.arange(1, shape[0] + 1, dtype=f32)), (h,))
            d_skip = self.param("D", nn.initializers.ones, (h,))
            dt_bias = self.param("dt_bias", _dt_bias_init, (h,))
            with jax.named_scope("llm.mixer.mamba.conv"):
                xbc = nn.silu(causal_conv(xbc, conv_kernel, conv_bias, segments)).astype(cfg.dtype)
            xs, b_in, c_in = jnp.split(xbc, (inner, inner + g * n), axis=-1)
            dt = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))
            with jax.named_scope("llm.mixer.mamba.ssd"):
                y = ssd(xs.reshape(*xs.shape[:2], h, p), dt, -jnp.exp(a_log.astype(f32)),
                        b_in.reshape(*b_in.shape[:2], g, n), c_in.reshape(*c_in.shape[:2], g, n),
                        d_skip, segments, cfg.mamba_chunk, self.mesh)
            y = (y.reshape(*y.shape[:2], inner).astype(f32) * nn.silu(z.astype(f32))).astype(cfg.dtype)
            y = RMSNorm(cfg.norm_eps, name="norm")(y).astype(cfg.dtype)
            return _project(self, "out_proj", y, cfg.d_model)


def _dt_bias_init(key, shape, dt_min: float = 1e-3, dt_max: float = 1e-1):
    """``softplus^-1`` of a log-uniform step in [dt_min, dt_max] (Mamba-2's)."""
    u = jax.random.uniform(key, shape, jnp.float32)
    dt = jnp.maximum(jnp.exp(u * (jnp.log(dt_max) - jnp.log(dt_min)) + jnp.log(dt_min)), 1e-4)
    return dt + jnp.log(-jnp.expm1(-dt))


#: what a model with KDA layers sows into collection ``stats``, summed over
#: them: the mean log-decay a chunk lets the state through (``ops/kda.chunk_decay``)
KDA_STATS = ("kda_chunk_decay",)


def _l2_normed(x, eps: float = 1e-6):
    """``x / sqrt(|x|^2 + eps)`` over each head, in float32."""
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt(jnp.sum(x32 * x32, -1, keepdims=True) + eps)).astype(x.dtype)


class KDA(nn.Module):
    """Kimi Delta Attention as flash-linear-attention's ``KimiDeltaAttention``
    has it: ``q, k, v = silu(conv(x W_q|k|v))`` (a depthwise causal
    convolution of ``kda_conv`` taps each, no bias, ``attn/conv_q|k|v``); q
    and k L2-normed per head; a log-decay per channel ``g = -exp(A_log_h)
    softplus(x W_fa W_fb + dt_bias)`` (a rank-``head_dim`` pair); ``beta =
    sigmoid(x W_beta)``; the gated delta rule of ``ops/kda.kda``; then
    ``W_o(RMSNorm_head(o) * sigmoid(x W_ga W_gb + b_g))``.  Scopes
    ``llm.mixer.kda`` and beneath it ``.conv``, ``.gate`` (the decay, beta
    and the output gate) and ``.core`` (the chunked pass).  Sows ``KDA_STATS``
    into collection ``stats``."""

    cfg: TransformerConfig
    mesh: Optional[Any] = None
    seq_axis: Optional[str] = None

    @nn.compact
    @_scoped("llm.mixer.kda")
    def __call__(self, x, positions, segments=None):
        from ..ops.kda import chunk_decay, kda

        _no_seq_axis(self)
        _no_segments(self, segments)
        cfg, f32 = self.cfg, jnp.float32
        h, d = cfg.kda_heads, cfg.kda_head_dim
        b, s = x.shape[:2]
        dense = lambda name, y, features, bias=False: nn.DenseGeneral(
            features, use_bias=bias, dtype=cfg.dtype, name=name)(y)
        # torch's Conv1d draw (uniform within 1 / sqrt(taps)), as Mamba's
        conv_init = lambda key, shape: jax.random.uniform(key, shape, f32, -1.0, 1.0) * cfg.kda_conv ** -0.5

        def short_conv(name):
            y = _project(self, "w" + name, x, (h, d)).reshape(b, s, h * d)
            kernel = self.param("conv_" + name, conv_init, (cfg.kda_conv, h * d))
            with jax.named_scope("llm.mixer.kda.conv"):
                return nn.silu(causal_conv(y, kernel)).astype(cfg.dtype).reshape(b, s, h, d)

        q, k, v = short_conv("q"), short_conv("k"), short_conv("v")
        q, k = _l2_normed(q), _l2_normed(k)
        with jax.named_scope("llm.mixer.kda.gate"):
            # A = 1..16 drawn uniformly (flash-linear-attention's), dt's bias as Mamba-2's
            a_log = self.param("A_log", lambda key, shape: jnp.log(
                jax.random.uniform(key, shape, f32, 1.0, 16.0)), (h,))
            dt_bias = self.param("dt_bias", _dt_bias_init, (h * d,))
            f = dense("wf_b", dense("wf_a", x, d), (h, d)).astype(f32) + dt_bias.reshape(h, d)
            g = -jnp.exp(a_log.astype(f32))[:, None] * jax.nn.softplus(f)
            beta = jax.nn.sigmoid(dense("wbeta", x, h).astype(f32))
        with jax.named_scope("llm.mixer.kda.core"):
            out = kda(q, k, v, g, beta, mesh=self.mesh)
        self.sow("stats", KDA_STATS[0], chunk_decay(g), init_fn=lambda: jnp.float32(0),
                 reduce_fn=lambda a, b: a + b)
        with jax.named_scope("llm.mixer.kda.gate"):
            gate = jax.nn.sigmoid(dense("wg_b", dense("wg_a", x, d), (h, d), bias=True).astype(f32))
            out = (RMSNorm(cfg.norm_eps, name="o_norm")(out).astype(f32) * gate).astype(cfg.dtype)
        return _project(self, "wo", out, cfg.d_model, axis=(-2, -1))


MIXERS = {"attention": Attention, "lightning-attn": LightningAttention, "minicpm4": SparseAttention,
          "mla": MLAttention, "mamba": Mamba, "dsa": DSAttention, "kda": KDA}


class MLP(nn.Module):
    cfg: TransformerConfig
    d_ff: int = 0  # 0 = cfg.d_ff

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        d_ff = self.d_ff or cfg.d_ff
        with jax.named_scope("llm.mlp"):
            gate = nn.Dense(d_ff, use_bias=False, dtype=cfg.dtype, name="w_gate")(x)
            up = nn.Dense(d_ff, use_bias=False, dtype=cfg.dtype, name="w_up")(x)
            return nn.Dense(cfg.d_model, use_bias=False, dtype=cfg.dtype, name="w_down")(
                nn.silu(gate) * up
            )


#: what a model with expert layers sows into collection ``stats``, each summed
#: over its expert layers: the tokens' assignments (tokens x top_k), those that
#: landed on experts held here, and the busiest held expert's tokens
MOE_STATS = ("moe_assignments", "moe_held", "moe_max_load")


class Router(nn.Module):
    """``route`` over a kernel of its own (``router/kernel``) and, with
    ``router_bias``, a selection bias (``router/e_score_correction_bias``)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        from ..ops.moe import route

        cfg = self.cfg
        kernel = self.param("kernel", nn.initializers.lecun_normal(), (x.shape[-1], cfg.n_routed_experts))
        bias = (self.param("e_score_correction_bias", nn.initializers.zeros, (cfg.n_routed_experts,))
                if cfg.router_bias else None)
        return route(x, kernel.astype(x.dtype), cfg.top_k, cfg.routed_scaling_factor, cfg.norm_topk_prob,
                     cfg.router_scoring, bias)


class Experts(nn.Module):
    """The held experts' SwiGLU kernels, stacked (``experts/w_gate|w_up``:
    (held, d_model, moe_d_ff); ``experts/w_down``: (held, moe_d_ff, d_model)),
    and their part of the result (``ops/moe.expert_ffn``)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, idx, gates):
        from ..ops.moe import expert_ffn, round_rows

        cfg = self.cfg
        held, d, f = cfg.experts_held or cfg.n_routed_experts, x.shape[-1], cfg.moe_d_ff
        init = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1, batch_axis=(0,))
        w = [self.param(n, init, shape).astype(cfg.dtype) for n, shape in
             (("w_gate", (held, d, f)), ("w_up", (held, d, f)), ("w_down", (held, f, d)))]
        return expert_ffn(x, idx, gates, *w, cfg.first_expert,
                          round_rows(x.shape[0], cfg.top_k, cfg.n_routed_experts))


class MoE(nn.Module):
    """A sparse expert layer as one expert-parallel rank runs it: every token
    is routed over ALL ``n_routed_experts``; the result is the shared experts'
    SwiGLU (whole, on every rank) plus the held experts' gated outputs for the
    tokens routed to them.  Sows ``MOE_STATS`` into collection ``stats``."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        flat = x.reshape(-1, x.shape[-1])
        with jax.named_scope("llm.moe.router"):
            idx, gates, counts = Router(cfg, name="router")(flat)
        with jax.named_scope("llm.moe.experts"):
            y = Experts(cfg, name="experts")(flat, idx, gates).reshape(x.shape)
        if cfg.n_shared_experts:
            with jax.named_scope("llm.moe.shared"):
                y = y + MLP(cfg, cfg.n_shared_experts * cfg.moe_d_ff, name="shared")(x)
        held = counts[cfg.first_expert: cfg.first_expert + (cfg.experts_held or cfg.n_routed_experts)]
        for name, value in zip(MOE_STATS, (idx.size, jnp.sum(held), jnp.max(held))):
            self.sow("stats", name, jnp.float32(value), init_fn=lambda: jnp.float32(0),
                     reduce_fn=lambda a, b: a + b)
        return y


class Block(nn.Module):
    cfg: TransformerConfig
    mesh: Optional[Any] = None
    seq_axis: Optional[str] = None
    mixer: str = "attention"
    experts: bool = False  # a sparse expert layer (``moe``) in the dense SwiGLU's (``mlp``) place

    @nn.compact
    def __call__(self, x, positions, segments=None):
        cfg = self.cfg
        branch = lambda y: y
        if cfg.scale_depth:  # muP: each residual branch times scale_depth / sqrt(depth)
            # in float32: a bfloat16 1.4 / sqrt(32) is 0.17% short, on every branch alike
            r = cfg.scale_depth / (cfg.mup_depth or cfg.n_layers) ** 0.5
            branch = lambda y: (y.astype(jnp.float32) * r).astype(x.dtype)
        after = lambda name, y: y
        if cfg.sandwich_norm:  # the branch's output is normed before it joins the stream
            after = lambda name, y: RMSNorm(cfg.norm_eps, name=name)(y).astype(x.dtype)
        x = x + branch(after("post_attn_norm", MIXERS[self.mixer](
            cfg, self.mesh, self.seq_axis, name="attn")(
            RMSNorm(cfg.norm_eps, name="attn_norm")(x), positions,
            *(() if segments is None else (segments,))
        )))
        ffn = MoE(cfg, name="moe") if self.experts else MLP(cfg, name="mlp")
        x = x + branch(after("post_mlp_norm", ffn(RMSNorm(cfg.norm_eps, name="mlp_norm")(x))))
        return x


class MTP(nn.Module):
    """One multi-token-prediction module (DeepSeek-V3's): ``h' = [N_e(E[t_{i+1}])
    | N_h(h_i)] W_p``, one block of the model's last kind, a final norm of its
    own.  ``h``: the last layer's output before the final norm; ``emb_next``:
    the embedding of each position's NEXT token.  Returns the hidden the
    shared head reads the token after next from."""

    cfg: TransformerConfig
    block: Any
    mixer: str = "attention"

    @nn.compact
    def __call__(self, h, emb_next, positions):
        cfg = self.cfg
        both = jnp.concatenate([RMSNorm(cfg.norm_eps, name="enorm")(emb_next).astype(cfg.dtype),
                                RMSNorm(cfg.norm_eps, name="hnorm")(h).astype(cfg.dtype)], -1)
        x = nn.Dense(cfg.d_model, use_bias=False, dtype=cfg.dtype, name="proj")(both)
        x = self.block(cfg, None, None, self.mixer, cfg.n_routed_experts > 0, name="block")(x, positions)
        return RMSNorm(cfg.norm_eps, name="final_norm")(x)


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


def targets_in_document(segments):
    """segments (b, s) -> (b, s) bool: the positions of a packed row whose
    target (the row's next token) lies in their own document: not a
    document's last token, not the row's last, not padding (id 0)."""
    following = jnp.pad(segments[:, 1:], ((0, 0), (0, 1)))
    return (segments == following) & (segments != 0)


class Transformer(nn.Module):
    cfg: TransformerConfig
    mesh: Optional[Any] = None
    seq_axis: Optional[str] = None

    @nn.compact
    def __call__(self, tokens, train: bool = True, targets=None, segments=None):
        """Logits (b, s, vocab); or, given ``targets`` (b, s), the float32
        next-token loss of each position (b, s), the head and the softmax
        taken ``cfg.loss_chunk`` positions at a time and rematerialised in the
        backward pass, so that the whole logits matrix never exists.  With
        ``cfg.mtp_layers`` and the targets: that and the MTP module's loss of
        each position (b, s) against the token AFTER its target, through the
        same embedding, head and chunks; the last position has no such token
        and reads 0.  ``segments`` (b, s): the document id of every token of a
        packed row (equal along a document, 0 for padding); no mixer then
        reaches across a document's start, and with the targets the loss of a
        position whose target is not in its own document reads 0
        (``targets_in_document``)."""
        cfg = self.cfg
        b, s = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        embed = nn.Embed(cfg.vocab_size, cfg.d_model, dtype=cfg.dtype, name="embed")
        x = embed(tokens)
        if cfg.scale_emb != 1.0:
            x = (x.astype(jnp.float32) * cfg.scale_emb).astype(cfg.dtype)
        block = Block
        if cfg.remat:
            policy = block_remat_policy(cfg)
            block = nn.remat(Block, static_argnums=(), policy=policy)
        packed = () if segments is None else (segments,)
        for i in range(cfg.n_layers):
            x = block(cfg, self.mesh, self.seq_axis, cfg.mixer(i), cfg.has_experts(i),
                      name=f"layer_{i}")(x, positions, *packed)
        # the module runs where its loss is asked for (and where its parameters are made)
        if cfg.mtp_layers and (targets is not None or self.is_initializing()):
            if cfg.mtp_layers != 1 or cfg.scale_emb != 1.0 or cfg.dim_model_base or segments is not None:
                raise NotImplementedError("one MTP module, on a model without the muP scalars, "
                                          "on rows that are not packed")
            with jax.named_scope("llm.mtp"):
                x_mtp = MTP(cfg, block, cfg.mixer(cfg.n_layers - 1), name="mtp")(
                    x, embed(tokens if targets is None else targets), positions)
        with jax.named_scope("llm.head_loss"):  # the trainer's loss carries the same name
            x = RMSNorm(cfg.norm_eps, name="final_norm")(x)
            if cfg.dim_model_base:
                x = (x.astype(jnp.float32) / (cfg.d_model / cfg.dim_model_base)).astype(x.dtype)
            if cfg.tie_embeddings:   # hidden E^T, in the embedding's dtype
                head, logits = embed, lambda head, xc: head.attend(xc)
            else:
                head = nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.logits_dtype, name="lm_head")
                logits = lambda head, xc: head(xc)
            if targets is None:
                return logits(head, x)

            def chunk_loss(head, xc, yc):
                return optax.softmax_cross_entropy_with_integer_labels(
                    logits(head, xc).astype(jnp.float32), yc)

            n = cfg.loss_chunk or s
            if s % n:
                raise ValueError(f"loss_chunk {n} does not divide the sequence length {s}")

            def in_chunks(x, targets):
                return jnp.concatenate(
                    [nn.remat(chunk_loss)(head, x[:, i: i + n], targets[:, i: i + n])
                     for i in range(0, s, n)], axis=1)

            if segments is not None:
                return in_chunks(x, targets) * targets_in_document(segments)
            if not cfg.mtp_layers:
                return in_chunks(x, targets)
            # position i of the module predicts the target of position i + 1
            after_next = jnp.roll(targets, -1, axis=1)
            return in_chunks(x, targets), in_chunks(x_mtp, after_next) * (jnp.arange(s) < s - 1)
