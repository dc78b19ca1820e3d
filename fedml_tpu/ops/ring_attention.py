"""Ring attention — exact attention over sequence-sharded inputs.

Long-context is first-class here even though the reference has none
(SURVEY.md §5 "long-context: absent" — it only passes flash-attn flags to HF).
This is the blockwise-parallel / ring attention construction (Liu et al.,
"Ring Attention with Blockwise Transformers"): shard the sequence over a mesh
axis; K/V blocks rotate around the ring via ``jax.lax.ppermute`` while each
device keeps its Q block and maintains an online-softmax accumulator
(running max m, normalizer l, weighted sum o).  P steps of compute overlap
P-1 ICI hops; memory per device is O(seq/P), enabling sequences that never
fit one chip.

Causality is handled by global block offsets: a device skips (zero-masks)
K/V blocks strictly in its future.  The math is exact — identical (up to f32
reduction order) to full attention, verified in tests against the dense
reference implementation.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def dense_attention(q, k, v, causal: bool = True, scale: Optional[float] = None):
    """Reference dense attention. q,k,v: (b, s, h, d) -> (b, s, h, d)."""
    b, s, h, d = q.shape
    scale = scale if scale is not None else d ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.tril(jnp.ones((s, k.shape[1]), bool))
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32)).astype(q.dtype)


def _block_attn_accum(q, k, v, q_off, k_off, m, l, o, causal: bool, scale: float):
    """One blockwise online-softmax update.  q: (b, sq, h, d); k/v: (b, sk, h, d);
    m/l: (b, h, sq); o: (b, sq, h, d) f32 accumulators."""
    sq, sk = q.shape[1], k.shape[1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    if causal:
        q_pos = q_off + jnp.arange(sq)
        k_pos = k_off + jnp.arange(sk)
        mask = q_pos[:, None] >= k_pos[None, :]
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    m_new = jnp.maximum(m, logits.max(axis=-1))
    # rescale previous accumulators
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(logits - m_new[..., None])
    l_new = l * alpha + p.sum(axis=-1)
    o_new = o * jnp.transpose(alpha, (0, 2, 1))[..., None] + jnp.einsum(
        "bhqk,bkhd->bqhd", p, v.astype(jnp.float32)
    )
    return m_new, l_new, o_new


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    axis: str = "sp",
    causal: bool = True,
    scale: Optional[float] = None,
    dp_axis: Optional[str] = None,
    tp_axis: Optional[str] = None,
) -> jax.Array:
    """Exact attention with q/k/v sequence-sharded over ``mesh[axis]``.

    q, k, v: (batch, seq, heads, head_dim) GLOBAL shapes; the seq dim must be
    divisible by the axis size.  Returns the same global shape, seq-sharded.

    ``dp_axis``/``tp_axis``: optional batch / heads shardings so attention
    compute stays sharded on hybrid (data, model, seq) meshes instead of
    being all-gathered and replicated across those axes.
    """
    p_size = mesh.shape[axis]
    d = q.shape[-1]
    scale_ = scale if scale is not None else d ** -0.5
    if p_size == 1:
        return dense_attention(q, k, v, causal=causal, scale=scale_)

    def live(name, dim_size_index):
        if name is None or name not in mesh.shape or mesh.shape[name] <= 1:
            return None
        return name if q.shape[dim_size_index] % mesh.shape[name] == 0 else None

    dp = live(dp_axis, 0)
    tp = live(tp_axis, 2)
    spec = P(dp, axis, tp, None)

    def local_fn(q, k, v):
        # local shapes: (b, s_local, h, d)
        b, s_local, h, _ = q.shape
        my_idx = jax.lax.axis_index(axis)
        q_off = my_idx * s_local
        m = jnp.full((b, h, s_local), NEG_INF, jnp.float32)
        l = jnp.zeros((b, h, s_local), jnp.float32)
        o = jnp.zeros(q.shape[:1] + (s_local,) + q.shape[2:], jnp.float32)
        perm = [(i, (i + 1) % p_size) for i in range(p_size)]

        def body(step, carry):
            m, l, o, k_blk, v_blk = carry
            # the block currently held originated at device (my_idx - step) mod P
            src = (my_idx - step) % p_size
            k_off = src * s_local
            if causal:
                # skip blocks strictly in our future (their mask would zero all)
                do_compute = src <= my_idx
            else:
                do_compute = True

            def compute(args):
                m, l, o = args
                return _block_attn_accum(q, k_blk, v_blk, q_off, k_off, m, l, o, causal, scale_)

            if causal:
                m, l, o = jax.lax.cond(do_compute, compute, lambda a: a, (m, l, o))
            else:
                m, l, o = compute((m, l, o))
            k_nxt = jax.lax.ppermute(k_blk, axis, perm)
            v_nxt = jax.lax.ppermute(v_blk, axis, perm)
            return m, l, o, k_nxt, v_nxt

        m, l, o, _, _ = jax.lax.fori_loop(0, p_size, body, (m, l, o, k, v))
        out = o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
        return out.astype(q.dtype)

    return jax.shard_map(
        local_fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)
