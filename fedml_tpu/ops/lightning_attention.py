"""Lightning (linear) attention with a fixed per-head decay, in chunks.

Per head ``h`` with decay ``lam = exp(-slope_h)`` the layer is the recurrence

    S_t = lam * S_{t-1} + k_t^T v_t          (d x d state, S_0 = 0)
    o_t = q_t S_t * scale

(Lightning Attention-2, Qin et al. 2024).  ``lightning_attention`` computes it
a chunk of ``C`` tokens at a time under one ``lax.scan``: inside a chunk the
causal, decayed scores ``(Q K^T) * D`` with ``D_ij = lam^(i-j)`` for ``i >= j``;
across chunks the carried state, ``O += lam^(i+1) * Q S_prev`` and
``S_next = lam^C S_prev + (K * lam^(C-1-j))^T V``.  No ``s x s`` matrix is
built, nothing is divided by a decay (so a fast head underflows to 0 and not
to inf), and the backward pass is the scan's own.

Matrix products take their operands in the inputs' dtype (bfloat16 on the
chip) and accumulate in float32; the decay factors, the state (also as an
operand of ``Q S_prev``) and the sum of the two output parts are float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def decay_slopes(n_heads: int) -> jax.Array:
    """``slope_h = 2^(-8 (h + 1) / n_heads)``: the ALiBi-style slopes of
    Lightning Attention-2, with no layer-dependent factor."""
    return 2.0 ** (-8.0 * jnp.arange(1, n_heads + 1, dtype=jnp.float32) / n_heads)


#: tokens a step of the scan takes: 128 / 256 / 512 read 13.1 / 12.0 / 13.1 ms a layer
#: (forward + backward at 16,384 tokens, 32 heads x 128, on a v5e)
CHUNK = 256


def lightning_attention(q, k, v, slopes, chunk: int = 0, scale=None):
    """q, k, v: (b, s, h, d); slopes: (h,) -> (b, s, h, d) in q's dtype.
    ``chunk`` 0 is ``CHUNK``.  ``s`` need not be a multiple of it: the tail is
    zero-padded (a zero key adds nothing to the state) and cut off again."""
    b, s, h, d = q.shape
    scale = d ** -0.5 if scale is None else scale
    c = min(chunk or CHUNK, s)
    n = -(-s // c)
    if n * c != s:
        pad = ((0, 0), (0, n * c - s), (0, 0), (0, 0))
        q, k, v = (jnp.pad(t, pad) for t in (q, k, v))
    # (n, b, c, h, d): the scan runs over chunks
    qs, ks, vs = (jnp.moveaxis(t.reshape(b, n, c, h, d), 1, 0) for t in (q, k, v))
    slopes = slopes.astype(jnp.float32)
    pos = jnp.arange(c, dtype=jnp.float32)
    delta = pos[:, None] - pos[None, :]
    # D (h, c, c): lam^(i-j) on and under the diagonal, 0 above; the exponent
    # is clamped first so that nothing overflows before the mask
    intra = jnp.where(delta >= 0, jnp.exp(-slopes[:, None, None] * jnp.maximum(delta, 0.0)), 0.0)
    into = jnp.exp(-slopes[None, :] * (pos[:, None] + 1.0))       # (c, h): lam^(i+1)
    keep = jnp.exp(-slopes[None, :] * (c - 1.0 - pos[:, None]))   # (c, h): lam^(c-1-j)
    carry_decay = jnp.exp(-slopes * c)                            # (h,): lam^c
    f32 = jnp.float32

    def one_chunk(state, qkv):
        qc, kc, vc = qkv
        scores = jnp.einsum("bihd,bjhd->bhij", qc, kc, preferred_element_type=f32) * intra
        out = jnp.einsum("bhij,bjhd->bihd", scores.astype(vc.dtype), vc, preferred_element_type=f32)
        # the state stays float32 here (XLA:CPU also has no batched
        # bf16 x bf16 -> f32 product for this one beside the two above)
        out = out + into[None, :, :, None] * jnp.einsum("bihd,bhde->bihe", qc.astype(f32), state)
        k_kept = (kc.astype(f32) * keep[None, :, :, None]).astype(kc.dtype)
        state = carry_decay[None, :, None, None] * state + jnp.einsum(
            "bjhd,bjhe->bhde", k_kept, vc, preferred_element_type=f32)
        return state, (out * scale).astype(qc.dtype)

    _, out = jax.lax.scan(one_chunk, jnp.zeros((b, h, d, d), f32), (qs, ks, vs))
    return jnp.moveaxis(out, 0, 1).reshape(b, n * c, h, d)[:, :s]


def lightning_attention_recurrent(q, k, v, slopes, scale=None):
    """The token-by-token recurrence in float32: what the chunked form is
    held to in the tests."""
    b, s, h, d = q.shape
    scale = d ** -0.5 if scale is None else scale
    lam = jnp.exp(-slopes.astype(jnp.float32))[None, :, None, None]

    def one_token(state, qkv):
        qt, kt, vt = (t.astype(jnp.float32) for t in qkv)
        state = lam * state + jnp.einsum("bhd,bhe->bhde", kt, vt)
        return state, jnp.einsum("bhd,bhde->bhe", qt, state) * scale

    _, out = jax.lax.scan(one_token, jnp.zeros((b, h, d, d), jnp.float32),
                          tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v)))
    return jnp.moveaxis(out, 0, 1)
