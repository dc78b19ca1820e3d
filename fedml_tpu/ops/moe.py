"""A sparse expert layer told which experts it holds: the router over ALL the
experts, and the held experts' part of the result.

Expert parallelism gives a chip ``experts_held`` of a layer's ``n_routed``
experts.  Every chip routes every token of its own over all of them
(``route``: sigmoid or softmax scores, the ``top_k`` best, by a selection
bias where one is given, ties to the lower index, no gradient through the
choice) and computes what its own experts add for the
tokens routed to them (``expert_ffn``).  On one chip the layer runs without
its exchange: what the absent experts would add is left out, and nothing
stands in for them.

``expert_ffn`` is exact for ANY routing, with static shapes and no dropped
assignment.  Each held expert's tokens form a list (in token order); the work
goes in ROUNDS: round ``r`` takes rows ``r c .. (r + 1) c - 1`` of every held
expert's list at once (one batched SwiGLU over ``(held, c, d)``, the kernels
read once), and a round in which no held expert has a row left is skipped
(``lax.cond`` in a ``lax.scan`` of ``ceil(tokens / c)`` rounds).  The backward
pass is written out: it goes through the same rounds, recomputes each round's
SwiGLU once and gives the gradient to the activations and the gates, and to
the kernels only where they are being differentiated (autodiff through the
scan would keep a copy of the kernels for every round, run or skipped: 15 GB
at the benchmark's sizes).  With ``c`` twice an expert's even
share (``round_rows``) balanced traffic takes one round; every token on one
expert takes them all, which is the dense cost and still exact.  Rows are
found without a sort: a token's place in its expert's list is a running count
of the expert's membership column.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .dsa import top_of
from .sparse_attention import best_of

#: a round's rows come in multiples of this (the MXU's tile of rows)
ROW_TILE = 128


def round_rows(tokens: int, top_k: int, n_routed: int) -> int:
    """Rows a held expert takes in one round: twice its even share ``tokens
    x top_k / n_routed`` (a balanced router's busiest expert stays under it),
    up to a multiple of ``ROW_TILE``, at most ``tokens``."""
    share = -(-2 * tokens * top_k // n_routed)
    return min(tokens, -(-share // ROW_TILE) * ROW_TILE)


#: how a router scores the experts: each alone (DeepSeek-V3, Pangu) or
#: against each other (Qwen3-MoE)
SCORINGS = ("sigmoid", "softmax")


def route(x, w_r, top_k: int, scale: float = 1.0, norm: bool = True, scoring: str = "sigmoid",
          bias=None):
    """x: (t, d); w_r: (d, n_routed) -> (idx (t, top_k) int32, ascending;
    gates (t, top_k) float32; counts (n_routed,) int32).  Scores are
    ``sigmoid(x w_r)``, or with ``scoring`` "softmax" ``softmax(x w_r)`` over
    all ``n_routed``, in float32; the ``top_k`` best of each token are chosen
    without a sort and without a gradient (``best_of``: exact, ties to the
    lower index), by ``s + bias`` where a selection bias (n_routed,) is given
    (DeepSeek-V3's ``e_score_correction_bias``; a bias may make a sum
    negative, which ``best_of``'s keys cannot order and ``dsa.top_of``'s
    can); ``gates = scale * s / (sum of the chosen s + 1e-20)`` with
    ``norm``, else ``scale * s``: the bias never reaches a gate; ``counts``
    is how many tokens chose each expert."""
    if scoring not in SCORINGS:
        raise ValueError(f"unknown router scoring {scoring!r} (known: {SCORINGS})")
    logits = jnp.dot(x, w_r, preferred_element_type=jnp.float32)
    s = jax.nn.sigmoid(logits) if scoring == "sigmoid" else jax.nn.softmax(logits, axis=-1)
    if bias is None:
        chosen = best_of(jax.lax.stop_gradient(s), jnp.ones(s.shape, bool), top_k)
    else:
        chosen = top_of(jax.lax.stop_gradient(s + bias.astype(jnp.float32)), jnp.ones(s.shape, bool), top_k)
    rank = jnp.cumsum(chosen, -1, dtype=jnp.int32)[:, None, :]       # 1-based at a chosen expert
    r = jnp.arange(top_k, dtype=jnp.int32)[None, :, None]
    idx = jnp.sum(rank <= r, -1, dtype=jnp.int32)                     # the (r + 1)-th chosen expert
    gates = jnp.sum(jnp.where(chosen[:, None, :] & (rank == r + 1), s[:, None, :], 0.0), -1)
    if norm:
        gates = gates / (jnp.sum(gates, -1, keepdims=True) + 1e-20)
    return idx, gates * scale, jnp.sum(chosen, 0, dtype=jnp.int32)


def _round(upto, counts, gate, r, c: int):
    """Rows ``r c ..`` of every held expert's list: their tokens (held, c)
    (``t``, out of range, past a list's end) and their gates (0 there)."""
    t = upto.shape[0]
    place = r * c + jnp.arange(c, dtype=jnp.int32)
    # the token at place p of expert e's list is the first with upto = p + 1
    tok = jnp.sum(upto[None, :, :] <= place[:, None, None], 1, dtype=jnp.int32).T
    g = jnp.take_along_axis(gate, jnp.minimum(tok, t - 1).T, axis=0).T
    return tok, jnp.where(place[None, :] < counts[:, None], g, 0.0)


def _gated_swiglu(xe, g, w_gate, w_up, w_down):
    """xe: (held, c, d); g: (held, c) float32 -> each expert's SwiGLU of its
    own rows times their gates, (held, c, d) float32."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, w_gate)) * jnp.einsum("ecd,edf->ecf", xe, w_up)
    return jnp.einsum("ecf,efd->ecd", h, w_down).astype(jnp.float32) * g[..., None]


def _over_rounds(one_round, init, t: int, c: int, counts):
    """``one_round(carry, r)`` over the rounds some held expert has a row for."""
    def step(carry, r):
        return jax.lax.cond(r * c < jnp.max(counts), lambda x: one_round(x, r), lambda x: x, carry), None

    return jax.lax.scan(step, init, jnp.arange(-(-t // c), dtype=jnp.int32))[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _held_part(x, gate, upto, counts, w_gate, w_up, w_down, c):
    def one_round(y, r):
        tok, g = _round(upto, counts, gate, r, c)
        xe = jnp.take(x, tok, axis=0, mode="fill", fill_value=0)
        part = _gated_swiglu(xe, g, w_gate, w_up, w_down)
        return y.at[tok.reshape(-1)].add(part.reshape(-1, part.shape[-1]), mode="drop")

    return _over_rounds(one_round, jnp.zeros(x.shape, jnp.float32), x.shape[0], c, counts).astype(x.dtype)


def _held_part_fwd(x, gate, upto, counts, w_gate, w_up, w_down, c):
    args = [a.value for a in (x, gate, upto, counts, w_gate, w_up, w_down)]
    # frozen kernels (adapter fine-tuning) get no gradient computed
    kernels = {"trained": ()} if (w_gate.perturbed or w_up.perturbed or w_down.perturbed) else {}
    return _held_part(*args, c), (args, kernels)


def _held_part_bwd(c, saved, dy):
    (x, gate, upto, counts, *w), kernels = saved
    t, f32 = x.shape[0], jnp.float32
    dy = dy.astype(f32)
    held = jnp.arange(gate.shape[1])[:, None]

    def one_round(carry, r):
        dx, dgate, dw = carry
        tok, g = _round(upto, counts, gate, r, c)
        xe = jnp.take(x, tok, axis=0, mode="fill", fill_value=0)
        d_part = jnp.take(dy, tok, axis=0, mode="fill", fill_value=0)
        if dw is None:
            dxe, dg = jax.vjp(lambda xe, g: _gated_swiglu(xe, g, *w), xe, g)[1](d_part)
        else:
            dxe, dg, *dw_r = jax.vjp(_gated_swiglu, xe, g, *w)[1](d_part)
            dw = tuple(a + b.astype(f32) for a, b in zip(dw, dw_r))
        dx = dx.at[tok.reshape(-1)].add(dxe.astype(f32).reshape(-1, dxe.shape[-1]), mode="drop")
        return dx, dgate.at[tok, held].add(dg, mode="drop"), dw   # a row past its list's end has no gate

    dw = tuple(jnp.zeros(k.shape, f32) for k in w) if "trained" in kernels else None
    dx, dgate, dw = _over_rounds(one_round, (jnp.zeros(x.shape, f32), jnp.zeros(gate.shape, f32), dw),
                                 t, c, counts)
    dw = (None,) * 3 if dw is None else tuple(a.astype(k.dtype) for a, k in zip(dw, w))
    return (dx.astype(x.dtype), dgate, None, None, *dw)


_held_part.defvjp(_held_part_fwd, _held_part_bwd, symbolic_zeros=True)


def expert_ffn(x, idx, gates, w_gate, w_up, w_down, first_expert: int = 0, rows: int = 0):
    """x: (t, d); idx, gates: (t, k) from ``route``; w_gate, w_up: (held, d,
    f); w_down: (held, f, d): the kernels of experts ``first_expert ..
    first_expert + held - 1`` -> (t, d) in x's dtype: ``sum over the held
    experts e in idx[t] of gates[t, e] * SwiGLU_e(x[t])``; a token none of
    whose experts is held gets zeros.  ``rows`` (0: all tokens, one round) is
    a round's rows per expert."""
    t = x.shape[0]
    hit = idx[:, :, None] == first_expert + jnp.arange(w_gate.shape[0], dtype=idx.dtype)   # (t, k, held)
    gate = jnp.sum(jnp.where(hit, gates[:, :, None].astype(jnp.float32), 0.0), 1)        # (t, held)
    upto = jnp.cumsum(hit.any(1), 0, dtype=jnp.int32)     # an expert's rows up to and with token t
    return _held_part(x, gate, upto, upto[-1], w_gate, w_up, w_down, min(rows or t, t))
