"""DeepSeek sparse attention's selection: a learned indexer scores every key a
query may see, and the query keeps the ``topk`` best.

DeepSeek-V3.2's "lightning indexer": ``I_{t,s} = sum_j w_{t,j} ReLU(q_{t,j} .
k_s)`` over ``s <= t`` (``index_scores``), with ``h`` indexer query heads,
ONE indexer key a token and a weight per head and query.  ``I`` is signed,
since ``w`` is, so the choice is ``top_of``: ``best_of`` of
``ops/sparse_attention.py`` (exact, ties to the lower index, no sort) over
the scores' float order, which needs the sign bit too: 32 compare-and-count
passes where non-negative scores take 31.

``select_tokens`` goes by query chunk: a float32 (s, s) score matrix is 4.3 GB
at 32,768 tokens, a chunk of 128 queries' 17 MB (its per-head products before
the sum over heads 268 MB).  What it keeps of a chunk is its selection, bit
by bit: key ``WORD w + j`` of a query is bit ``j`` of the query's word ``w``,
(b, s, s / WORD) uint32 in all, 134 MB a layer at 32,768 tokens, which
``ops/sparse_attention.block_sparse_attention`` takes as its mask.  No
gradient passes through the choice.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .sparse_attention import WORD, _chunk, top_by_key, unpack

__all__ = ["WORD", "INDEX_CHUNK", "SELECT_SCOPE", "order_keys", "top_of", "index_scores", "pack",
           "unpack", "select_tokens"]

#: queries the selection takes at a time
INDEX_CHUNK = 128
#: the named scope of the choice, inside the mixer's ``llm.mixer.dsa.indexer``
SELECT_SCOPE = "llm.mixer.dsa.indexer.select"
_SIGN = 1 << 31


def order_keys(score):
    """float32 -> uint32 whose unsigned order is the scores' float order
    (-0.0 and +0.0 alike): a non-negative float's bits with the sign bit
    set, a negative one's bits all flipped."""
    bits = jax.lax.bitcast_convert_type(jnp.where(score == 0, 0.0, score).astype(jnp.float32),
                                        jnp.uint32)
    sign = jnp.uint32(_SIGN)
    return jnp.where(bits >= sign, ~bits, bits | sign)


def top_of(score, candidate, topk: int):
    """score: (..., n) float32, none NaN; candidate: (..., n) bool -> (..., n)
    bool, the ``topk`` best candidates of each row: a candidate is chosen iff
    fewer than ``topk`` candidates rank before it, where ``j`` ranks before
    ``i`` when it scores higher, or the same with ``j < i`` (what
    ``lax.top_k`` gives); a row with fewer candidates chooses them all.  The
    row's ``topk``-th largest key of ``order_keys`` is found bit by bit, 32
    times one compare and one count over the row (a candidate's key is at
    least 1, any other reads 0: ``top_by_key``), and ties at it are cut by a
    prefix count."""
    keys = jnp.where(candidate, order_keys(score), jnp.uint32(0))
    return top_by_key(keys, topk, 31, tieable=candidate)


def index_scores(q, k, w):
    """q: (b, n, h, d) indexer queries; k: (b, s, d) indexer keys; w: (b, n, h)
    float32 weights -> (b, n, s) float32 ``sum_j w_j ReLU(q_j . k_s)``."""
    logits = jnp.einsum("bnhd,bsd->bnhs", q, k, preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(logits) * w[..., None], axis=2)


def pack(chosen):
    """(..., s) bool -> (..., s / WORD) uint32: key ``WORD w + j`` at bit ``j`` of word ``w``."""
    words = chosen.reshape(*chosen.shape[:-1], -1, WORD)
    return jnp.sum(jnp.where(words, jnp.uint32(1) << jnp.arange(WORD, dtype=jnp.uint32), jnp.uint32(0)),
                   -1, dtype=jnp.uint32)


def select_tokens(q, k, w, topk: int):
    """q: (b, s, h, d), k: (b, s, d), w: (b, s, h) (``index_scores``) ->
    (bits (b, s, s / WORD) uint32: the ``topk`` keys ``s' <= t`` of best
    score for each query ``t``, packed; chosen, the pairs chosen summed over
    the batch, int32).  Chunks of ``INDEX_CHUNK`` queries one after another,
    each chunk's choice under ``SELECT_SCOPE``.  No gradient."""
    b, s, h, d = q.shape
    if s % WORD:
        raise ValueError(f"a selection packs {WORD} keys a word: {s} tokens do not")
    q, k, w = jax.lax.stop_gradient((q, k, w.astype(jnp.float32)))
    cq = _chunk(s, INDEX_CHUNK)
    keys = jnp.arange(s)

    def one_chunk(args):
        qc, wc, t = args                                          # (b, cq, h, d), (b, cq, h), (cq,)
        score = index_scores(qc, k, wc)
        with jax.named_scope(SELECT_SCOPE):
            chosen = top_of(score, jnp.broadcast_to(keys[None, :] <= t[:, None], score.shape), topk)
            return pack(chosen), jnp.sum(chosen, dtype=jnp.int32)

    lead = lambda x: jnp.moveaxis(x.reshape(b, s // cq, cq, *x.shape[2:]), 1, 0)
    bits, chosen = jax.lax.map(one_chunk, (lead(q), lead(w), keys.reshape(s // cq, cq)))
    return jnp.moveaxis(bits, 0, 1).reshape(b, s, s // WORD), jnp.sum(chosen)
