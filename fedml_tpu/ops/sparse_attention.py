"""InfLLM-v2 block-sparse softmax attention: every query picks the key blocks
it attends.

Two stages in plain ``jax.numpy``/``lax`` and the entry that joins them; the
second stage's plain-causal case (no block dropped) runs as one fused Pallas
kernel where ``attention_path`` finds that it can:

``select_blocks``  scores key blocks per query and KV head without a gradient:
    compressed keys (the mean of ``kernel_size`` keys every ``kernel_stride``),
    a softmax of each query head over the compressed keys that end at or
    before it, summed over the heads of the KV head's group; a key block's
    score is the largest of the compressed keys that overlap it.  Kept: the
    first ``init_blocks`` blocks, every block that holds one of the last
    ``window_size`` tokens, and the ``topk`` best of the other blocks that
    start at or before the query (``best_of``: exact, ties to the lower
    index, and without a sort, which XLA:TPU makes of ``lax.top_k``).

``sparse_attention``  the layer's whole mixer: the selection past ``dense_len``
    tokens (every block up to it), then the attention below; what
    ``SparseAttention`` calls and what a benchmark times alone.

``block_sparse_attention``  exact softmax attention over the kept blocks'
    tokens ``j <= t``: query chunks in an outer loop, key chunks in an inner
    scan with the online-softmax accumulator (running max, normaliser,
    weighted sum), key chunks wholly in a query chunk's future skipped.  It
    computes the scores of every key chunk at or before the query chunk and
    lets the mask drop what was not kept: a dense-masked pass, not yet one
    that skips dropped blocks.  The mask is cut by query chunk alone and each
    pair of chunks slices its key chunk's columns from it in place, so that
    its minor axis stays lane-dense (a packed choice's words whole) and the
    whole mask is never relaid into pairs of chunks.  The backward pass is
    written out (the flash-attention one): the forward keeps the output and
    each row's log-sum-exp, the backward recomputes each pair of chunks'
    probabilities once; no score matrix is kept and nothing is
    rematerialised twice.  The values may have a width of their own (latent
    attention's 192-wide keys over 128-wide values), and ``keep=None`` is
    plain causal attention.
    With ``segments`` (packed documents) a query attends its own document's
    tokens alone: a token-level mask beside the blocks', on either path.

``row_chunk``  the chunk that pass takes a plain mixer's rows in, decided
    from their length alone (a length with no useful divisor never runs
    token by token: whole up to a stated size, refused beyond).

``attention_path``  the ONE place that chooses between that pass and
    ``ops/pallas/flash_attention.causal_attention``, the same algorithm with
    each pair's scores held in VMEM from the first product to the last: from
    ``keep``, the caller's mesh, the backend and the shapes, nothing else.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..obs.trace import LLM_ATTENTION_SITES
from .pallas import flash_attention
from .segments import document_index

NEG_INF = -1e30
#: the name a selection's block mask carries, so that a remat policy can keep
#: it (``save_only_these_names``): no gradient passes through the selection,
#: and selecting again in the backward pass would score every block a second
#: time (9 ms at 16,384 tokens on a v5e) to save 8 MB
SPARSE_KEEP = "sparse_keep"
#: queries and keys a blockwise pass takes at a time: 256 / 512 / 1,024 read
#: 200 / 126 / 314 ms of device time a layer (what a remat block holds of it:
#: the selection once, the attention's forward twice and its backward, at
#: 16,384 tokens, 32 query and 2 KV heads x 128, on a v5e; PR 30)
CHUNK = 512
#: keys one uint32 word of a packed token selection holds (``ops/dsa.py``)
WORD = 32


def _chunk(n: int, want: int, multiple: int = 1) -> int:
    """The largest divisor of ``n`` that is at most ``want`` and a multiple of
    ``multiple``."""
    for c in range(min(want, n), 0, -1):
        if n % c == 0 and c % multiple == 0:
            return c
    raise ValueError(f"no chunk of {n} is a multiple of {multiple}")


def row_chunk(s: int) -> int:
    """The chunk a plain mixer takes rows of ``s`` tokens in: the largest
    divisor of ``s`` up to ``CHUNK``.  Where that is under ``CHUNK // 8`` (a
    prime 1,021 would go token by token) the row whole, up to ``4 * CHUNK``
    tokens; a longer row of such a length is refused."""
    c, least = _chunk(s, CHUNK), CHUNK // 8
    if c >= least:
        return c
    if s <= 4 * CHUNK:
        return s
    raise ValueError(f"rows of {s} tokens have no chunk between {least} and {CHUNK} tokens (the largest "
                     f"divisor of {s} up to {CHUNK} is {c}): pad them to a multiple of {least}")


def compress_keys(k, kernel_size: int, kernel_stride: int):
    """k: (b, s, kv, d) -> (b, m, kv, d), ``m = (s - kernel_size) // kernel_stride + 1``
    means of ``kernel_size`` consecutive keys, float32."""
    s = k.shape[1]
    m = (s - kernel_size) // kernel_stride + 1
    idx = jnp.arange(m)[:, None] * kernel_stride + jnp.arange(kernel_size)[None, :]
    return jnp.mean(k.astype(jnp.float32)[:, idx], axis=2)


def best_of(score, candidate, topk: int):
    """score: (..., n) float32, every entry ``>= +0.0`` and none NaN;
    candidate: (..., n) bool -> (..., n) bool, the ``topk`` best candidates of
    each row: a candidate is chosen iff fewer than ``topk`` candidates rank
    before it, where ``j`` ranks before ``i`` when it scores higher, or the
    same with ``j < i`` (what ``lax.top_k`` and a stable descending sort give);
    a row with fewer candidates chooses them all.  No sort: non-negative
    floats order as their bit patterns do, so the row's ``topk``-th largest
    pattern is found bit by bit, 31 times one compare and one count over the
    row, and ties at it are cut by a prefix count."""
    return top_by_key(jnp.where(candidate, jax.lax.bitcast_convert_type(score, jnp.int32), -1), topk, 30)


def top_by_key(keys, topk: int, top_bit: int, tieable=None):
    """keys: (..., n) integers below ``2 ** (top_bit + 1)``, a non-candidate's
    below every candidate's -> (..., n) bool, the ``topk`` largest keys of
    each row, ties to the lower index.  The row's ``topk``-th largest key is
    found bit by bit from ``top_bit`` down, one compare and one count over the
    row a bit, and ties at it are cut by a prefix count; only ``tieable``
    entries, where given, may be chosen at the cut."""
    at = jnp.zeros((*keys.shape[:-1], 1), keys.dtype)     # largest v with #(keys >= v) >= topk
    for bit in range(top_bit, -1, -1):
        trial = at | keys.dtype.type(1 << bit)
        at = jnp.where(jnp.sum(keys >= trial, -1, keepdims=True) >= topk, trial, at)
    above, tied = keys > at, keys == at
    if tieable is not None:
        tied = tied & tieable
    room = topk - jnp.sum(above, -1, keepdims=True)
    return above | (tied & (jnp.cumsum(tied, -1) <= room))


def select_blocks(q, k, *, kernel_size: int, kernel_stride: int, block_size: int, topk: int,
                  init_blocks: int, window_size: int, q_chunk: int = 1024):
    """q: (b, s, h, d), k: (b, s, kv, d) -> (keep, kept, causal): ``keep``
    (b, kv, s, s // block_size) bool, the blocks each query attends; ``kept``
    the keys ``j <= t`` inside them, summed over batch, KV heads and queries,
    and ``causal`` the same sum for plain causal attention (float32)."""
    b, s, h, d = q.shape
    kv = k.shape[2]
    if s % block_size or block_size % kernel_stride or kernel_size % kernel_stride:
        raise ValueError("block selection needs seq % block_size == 0 and the stride to divide "
                         f"block and kernel: {s}, {block_size}, {kernel_size}, {kernel_stride}")
    q, k = jax.lax.stop_gradient((q, k))
    nblk, per_block, before = s // block_size, block_size // kernel_stride, kernel_size // kernel_stride - 1
    kbar = compress_keys(k, kernel_size, kernel_stride)
    m = kbar.shape[1]
    ends = jnp.arange(m) * kernel_stride + kernel_size - 1   # a compressed key's last token
    blocks = jnp.arange(nblk)
    cq = _chunk(s, q_chunk)
    qg = q.astype(jnp.float32).reshape(b, s // cq, cq, kv, h // kv, d)

    def one_chunk(args):
        qc, t = args                                          # (b, cq, kv, g, d), (cq,)
        logits = jnp.einsum("bqkgd,bmkd->bkgqm", qc, kbar,
                            precision=jax.lax.Precision.HIGHEST) * d ** -0.5
        valid = ends[None, :] <= t[:, None]                   # (cq, m)
        logits = jnp.where(valid, logits, NEG_INF)
        e = jnp.where(valid, jnp.exp(logits - logits.max(-1, keepdims=True)), 0.0)
        p = (e / jnp.maximum(e.sum(-1, keepdims=True), 1e-30)).sum(2)   # (b, kv, cq, m)
        # a block's compressed keys: the per_block that start inside it and
        # the `before` that start ahead of it and reach into it
        p = jnp.pad(p, ((0, 0), (0, 0), (0, 0), (before, nblk * per_block - m)))
        score = p[..., before:].reshape(*p.shape[:-1], nblk, per_block).max(-1)
        for j in range(before):
            score = jnp.maximum(score, p[..., j: j + nblk * per_block: per_block])
        visible = blocks[None, :] * block_size <= t[:, None]  # starts at or before the query
        forced = visible & ((blocks[None, :] < init_blocks)
                            | (blocks[None, :] >= (t[:, None] - window_size + 1) // block_size))
        return forced | best_of(score, visible & ~forced, topk)   # (b, kv, cq, nblk)

    t_all = jnp.arange(s).reshape(s // cq, cq)
    keep = jax.lax.map(one_chunk, (jnp.moveaxis(qg, 1, 0), t_all))     # (n, b, kv, cq, nblk)
    keep = jnp.moveaxis(keep, 0, 2).reshape(b, kv, s, nblk)
    held = jnp.clip(jnp.arange(s)[:, None] - blocks[None, :] * block_size + 1, 0, block_size)
    kept = jnp.sum(jnp.where(keep, held.astype(jnp.float32), 0.0))
    return keep, kept, jnp.float32(b * kv) * (s * (s + 1) / 2)


def _chunks(q, k, v, keep, cq: int, ck: int):
    """The operands cut into chunks, the chunk index leading: q (nq, b, cq,
    kv, g, d), k (nk, b, ck, kv, d), v (nk, b, ck, kv, dv), and the mask by
    query chunk alone, (nq, b, kv or 1, cq, n) with its ``n`` columns whole:
    each pair of chunks slices its key chunk's columns in place
    (``_masked_logits``).  Only leading axes move, so the mask keeps its
    lane-dense minor axis (a packed choice's 1,024 words at 32,768 tokens,
    where a pair of chunks holds 16) and is never relaid whole."""
    b, s, h, d = q.shape
    kv = k.shape[2]
    nq, nk = s // cq, s // ck
    qs = jnp.moveaxis(q.reshape(b, nq, cq, kv, h // kv, d), 1, 0)
    ks = jnp.moveaxis(k.reshape(b, nk, ck, kv, d), 1, 0)
    vs = jnp.moveaxis(v.reshape(b, nk, ck, kv, v.shape[-1]), 1, 0)
    keeps = jnp.moveaxis(keep.reshape(b, keep.shape[1], nq, cq, keep.shape[-1]), 2, 0)
    return qs, ks, vs, keeps


def _doc_chunks(doc, cq: int, ck: int):
    """The tokens' document indices by query chunk (nq, b, cq) and by key
    chunk (nk, b, ck); a pair of ``None`` where the rows are not packed (the
    scans then carry nothing for them)."""
    if doc is None:
        return None, None
    b, s = doc.shape
    return (jnp.moveaxis(doc.reshape(b, s // cq, cq), 1, 0),
            jnp.moveaxis(doc.reshape(b, s // ck, ck), 1, 0))


def _zeros_for(ks, vs):
    """Float32 zeros shaped as keys and as values: one array for both where
    the two widths are equal."""
    zk = jnp.zeros(ks.shape, jnp.float32)
    return zk, (zk if vs.shape == ks.shape else jnp.zeros(vs.shape, jnp.float32))


def unpack(bits):
    """A token selection packed ``WORD`` keys a uint32 word, (..., n) ->
    (..., n x WORD) bool: key ``WORD w + j`` is bit ``j`` of word ``w``."""
    one = (bits[..., None] >> jnp.arange(WORD, dtype=jnp.uint32)) & jnp.uint32(1)
    return one.astype(bool).reshape(*bits.shape[:-1], -1)


def _keys_kept(keep_qk, block_size: int):
    """A pair of chunks' mask by key: each kept block's ``block_size`` keys,
    or the bits of a packed token selection."""
    if keep_qk.dtype == jnp.uint32:
        return unpack(keep_qk)
    return jnp.repeat(keep_qk, block_size, axis=-1)


def _masked_logits(qc, kc, keep_q, iq, ik, block_size: int, scale: float, docs=(None, None)):
    """Scores of one pair of chunks, (b, kv, g, cq, ck) float32, with their
    mask: the kept blocks' tokens at or before each query, and with ``docs``
    (the chunks' document indices, (b, cq) and (b, ck)) in its document.
    ``keep_q`` is the query chunk's whole mask (b, kv or 1, cq, n); the key
    chunk's columns are sliced from it here, so that the slice fuses into the
    unpacking and the mask."""
    cq, ck = qc.shape[1], kc.shape[1]
    cols = ck // block_size
    keep_qk = jax.lax.dynamic_slice_in_dim(keep_q, ik * cols, cols, axis=-1)
    q_pos, k_pos = iq * cq + jnp.arange(cq), ik * ck + jnp.arange(ck)
    mask = (_keys_kept(keep_qk, block_size) & (q_pos[:, None] >= k_pos[None, :]))[:, :, None]
    if docs[0] is not None:
        mask = mask & (docs[0][:, :, None] == docs[1][:, None, :])[:, None, None]
    logits = jnp.einsum("bqkgd,btkd->bkgqt", qc, kc, preferred_element_type=jnp.float32) * scale
    return jnp.where(mask, logits, NEG_INF), mask


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _attend(q, k, v, keep, block_size, cq, ck, scale, doc=None):
    return _attend_fwd(q, k, v, keep, block_size, cq, ck, scale, doc)[0]


def _attend_fwd(q, k, v, keep, block_size, cq, ck, scale, doc=None):
    """Online softmax over key chunks for each query chunk; keeps the output
    and each row's log-sum-exp for the backward pass, and no score.  ``doc``
    (b, s): the tokens' document indices on packed rows."""
    b, s, h, d = q.shape
    kv, f32 = k.shape[2], jnp.float32
    qs, ks, vs, keeps = _chunks(q, k, v, keep, cq, ck)
    doc_qs, doc_ks = _doc_chunks(doc, cq, ck)

    def one_query_chunk(args):
        qc, keep_q, iq, doc_q = args

        def one_key_chunk(carry, xs):
            kc, vc, ik, doc_k = xs

            def attend(carry):
                m, l, o = carry
                logits, mask = _masked_logits(qc, kc, keep_q, iq, ik, block_size, scale,
                                              (doc_q, doc_k))
                m_new = jnp.maximum(m, logits.max(-1))
                alpha = jnp.exp(m - m_new)
                p = jnp.where(mask, jnp.exp(logits - m_new[..., None]), 0.0)
                o = o * jnp.moveaxis(alpha, 3, 1)[..., None] + jnp.einsum(
                    "bkgqt,btkd->bqkgd", p.astype(vc.dtype), vc, preferred_element_type=f32)
                return m_new, l * alpha + p.sum(-1), o

            # a key chunk wholly in the query chunk's future adds nothing
            return jax.lax.cond(ik * ck <= iq * cq + cq - 1, attend, lambda c: c, carry), None

        init = (jnp.full((b, kv, h // kv, cq), NEG_INF, f32), jnp.zeros((b, kv, h // kv, cq), f32),
                jnp.zeros((b, cq, kv, h // kv, v.shape[-1]), f32))
        (m, l, o), _ = jax.lax.scan(one_key_chunk, init,
                                    (ks, vs, jnp.arange(s // ck), doc_ks))
        l = jnp.maximum(l, 1e-30)
        return (o / jnp.moveaxis(l, 3, 1)[..., None]).astype(q.dtype), m + jnp.log(l)

    out, lse = jax.lax.map(one_query_chunk, (qs, keeps, jnp.arange(s // cq), doc_qs))
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, h, v.shape[-1])
    return out, (q, k, v, keep, doc, out, lse)


def _attend_bwd(block_size, cq, ck, scale, saved, d_out):
    """The flash-attention backward: each pair of chunks recomputes its
    probabilities from the saved log-sum-exp, once."""
    q, k, v, keep, doc, out, lse = saved                 # lse: (nq, b, kv, g, cq)
    b, s, h, d = q.shape
    kv, f32 = k.shape[2], jnp.float32
    qs, ks, vs, keeps = _chunks(q, k, v, keep, cq, ck)
    doc_qs, doc_ks = _doc_chunks(doc, cq, ck)
    dos = jnp.moveaxis(d_out.reshape(b, s // cq, cq, kv, h // kv, v.shape[-1]), 1, 0)
    # delta_t = sum_j p_tj dp_tj = do_t . o_t
    deltas = jnp.moveaxis(jnp.sum(d_out.astype(f32) * out.astype(f32), -1)
                          .reshape(b, s // cq, cq, kv, h // kv), 1, 0)

    def one_query_chunk(dkv, args):
        qc, doc, keep_q, lse_q, delta_q, iq, doc_q = args
        delta_q = jnp.transpose(delta_q, (0, 2, 3, 1))   # (b, kv, g, cq)

        def one_key_chunk(dq, xs):
            kc, vc, ik, doc_k = xs

            def attend(dq):
                logits, mask = _masked_logits(qc, kc, keep_q, iq, ik, block_size, scale,
                                              (doc_q, doc_k))
                p = jnp.where(mask, jnp.exp(logits - lse_q[..., None]), 0.0)
                dv = jnp.einsum("bkgqt,bqkgd->btkd", p.astype(doc.dtype), doc, preferred_element_type=f32)
                dp = jnp.einsum("bqkgd,btkd->bkgqt", doc, vc, preferred_element_type=f32)
                ds = (p * (dp - delta_q[..., None]) * scale).astype(qc.dtype)
                dq = dq + jnp.einsum("bkgqt,btkd->bqkgd", ds, kc, preferred_element_type=f32)
                dk = jnp.einsum("bkgqt,bqkgd->btkd", ds, qc, preferred_element_type=f32)
                return dq, (dk, dv)

            nothing = _zeros_for(kc, vc)
            return jax.lax.cond(ik * ck <= iq * cq + cq - 1, attend,
                                lambda dq: (dq, nothing), dq)

        dq, (dk, dv) = jax.lax.scan(one_key_chunk, jnp.zeros(qc.shape, f32),
                                    (ks, vs, jnp.arange(s // ck), doc_ks))
        return (dkv[0] + dk, dkv[1] + dv), dq.astype(q.dtype)

    (dk, dv), dq = jax.lax.scan(one_query_chunk, _zeros_for(ks, vs),
                                (qs, dos, keeps, lse, deltas, jnp.arange(s // cq), doc_qs))
    unchunk = lambda t, like: jnp.moveaxis(t, 0, 1).reshape(like.shape).astype(like.dtype)
    return unchunk(dq, q), unchunk(dk, k), unchunk(dv, v), None, None


_attend.defvjp(_attend_fwd, _attend_bwd)


#: the paths a blockwise-attention call site is built on
ATTENTION_PATHS = ("kernel", "blockwise")


def attention_sites() -> dict:
    """Call sites counted so far, by path (``fedml_llm_attention_sites_total``):
    a program's own are what its tracing adds."""
    return {path: int(LLM_ATTENTION_SITES.value(path=path)) for path in ATTENTION_PATHS}


def attention_path(q, k, v, keep, mesh) -> str:
    """``"kernel"`` where the fused flash kernel can stand for the blockwise
    pass, else ``"blockwise"``: no block dropped (``keep is None``; a block
    mask is the ``lax`` pass's), no mesh in the caller's hands (jax cannot
    partition a Mosaic call), a TPU backend, and shapes the kernel tiles.
    Counts the call site under the path it takes: called while a program is
    traced, so once for each site the program has."""
    kernel = (keep is None and mesh is None and jax.default_backend() == "tpu"
              and flash_attention.tiles(q, k, v))
    path = "kernel" if kernel else "blockwise"
    LLM_ATTENTION_SITES.inc(1, path=path)
    return path


def _by_head_groups(fn, group: int, q, k, v):
    """``fn(q, k, v)`` over (b, s, heads, d) operands with a key per head,
    ``group`` heads at a time, one group after another."""
    h = q.shape[2]
    if h <= group:
        return fn(q, k, v)
    return jnp.concatenate([fn(*(t[:, :, i: i + group] for t in (q, k, v)))
                            for i in range(0, h, group)], axis=2)


def block_sparse_attention(q, k, v, keep=None, *, block_size: int = 64, q_chunk: int = 1024,
                           k_chunk: int = 1024, scale=None, mesh=None, head_group: int = 0,
                           segments=None):
    """q: (b, s, h, d); k: (b, s, kv, d); v: (b, s, kv, dv), a value width of
    its own allowed (latent attention: 192-wide queries and keys, 128-wide
    values); keep: (b, kv, s, s // block_size) bool, or (b, 1, ...) for all KV
    heads alike, or None (every block), or with ``block_size`` 32 a token
    selection packed into uint32 words (``ops/dsa.py:pack``, a bit a key) ->
    softmax attention of each query over the tokens ``j <= t`` of its kept
    blocks, (b, s, h, dv) in q's dtype.
    ``mesh`` is the mesh the calling module holds, if any: what it computes on
    may be sharded, which keeps it on the ``lax`` pass (``attention_path``).
    ``head_group``, with ``keep=None`` and a key per head: the heads the
    ``lax`` pass takes at a time (its float32 accumulators scale with them);
    0 is all of them.  ``segments`` (b, s): document ids of packed rows,
    equal along a document; a query then attends its own document alone."""
    b, s, h, d = q.shape
    scale = d ** -0.5 if scale is None else scale
    doc = None if segments is None else document_index(segments)
    if attention_path(q, k, v, keep, mesh) == "kernel":
        return flash_attention.causal_attention(q, k, v, scale=scale, segments=doc)
    cq, scale = _chunk(s, q_chunk), float(scale)
    if keep is not None:
        return _attend(q, k, v, keep, block_size, cq, _chunk(s, k_chunk, block_size), scale, doc)
    # any block size will do: one that divides a chunk of keys
    ck = _chunk(s, k_chunk)
    block_size = _chunk(ck, block_size)
    keep = jnp.ones((b, 1, s, s // block_size), bool)
    return _by_head_groups(lambda q, k, v: _attend(q, k, v, keep, block_size, cq, ck, scale, doc),
                           head_group or h, q, k, v)


def sparse_attention(q, k, v, *, dense_len: int, chunk: int = 0, mesh=None, **selection):
    """The InfLLM-v2 mixer on projected q (b, s, h, d) and k, v (b, s, kv, d):
    plain causal attention for at most ``dense_len`` tokens, else attention
    over the blocks ``select_blocks(**selection)`` keeps -> (out, kept,
    causal), the counts as ``select_blocks`` gives them.  ``chunk`` 0 is
    ``CHUNK``; ``mesh`` as ``block_sparse_attention`` takes it."""
    b, s = q.shape[:2]
    chunk = chunk or CHUNK
    if s > dense_len:
        keep, kept, causal = select_blocks(q, k, q_chunk=chunk, **selection)
        keep = checkpoint_name(keep, SPARSE_KEEP)
    else:
        keep = None
        kept = causal = jnp.float32(b * k.shape[2]) * (s * (s + 1) / 2)
    out = block_sparse_attention(q, k, v, keep, block_size=selection["block_size"],
                                 q_chunk=chunk, k_chunk=chunk, mesh=mesh)
    return out, kept, causal
